"""Persistent, content-addressed compilation cache for AOT executables.

The flagship ``train_step`` and ``stream_step`` each take the better part
of a minute or more to compile, and the serve path gates readiness on
compiling the whole bucket ladder at boot.  Compile cost is the central
systems problem for this workload class (TpuGraphs, arXiv:2308.13490), and
the fix is the graph-reuse discipline PyGraph applies to CUDA graphs
(arXiv:2503.19779):
key every lowered program by WHAT it computes, persist the compiled
artifact, and never compile the same program twice on the same platform.

`CompileCache` wraps ``jit_fn.lower(*args).compile()`` +
``jax.experimental.serialize_executable``:

  * **content-addressed** — an entry's directory name IS the canonical
    fingerprint of (program name, argument avals + pytree layout, caller
    ``extra`` material such as model architecture and donation spec,
    jax/jaxlib/libtpu versions, backend platform + device kind, the ids of
    the devices the program runs on, host ISA fingerprint on CPU).  Any
    drift along any axis is a different fingerprint, so a stale executable
    can never be reused — the worst a corrupt cache can do is cost one
    fresh compile;
  * **atomic** — entries are written to a tmp directory and renamed into
    place (rename(2) is atomic on one filesystem), so concurrent
    processes sharing a cache volume see whole entries or nothing;
  * **bounded** — ``prune()`` applies an LRU disk bound (last-use is an
    ``os.utime`` stamp on the entry dir, refreshed on every hit);
  * **fail-open** — every failure mode (no backend support, version
    skew, truncated payload, unpicklable tree, read-only volume) falls
    back to the live jit path, journals the cause, and never raises into
    the caller.  A cache can make boot fast; it must never break serving.

Metrics: ``nerrf_compile_cache_{hits,misses,bytes}_total`` and
``nerrf_compile_seconds{program,source=cache|fresh}``.  Journal records of
kind ``compile`` carry (program, fingerprint, source, seconds, reason) —
`nerrf doctor <bundle>` reconstructs compile provenance from them offline.

Spans: a resolution is one ``compile_resolve`` whose children are its
stages (``compile_resolve.fingerprint`` / ``read`` / ``deserialize`` on a
hit; ``lower`` / ``compile`` / ``serialize`` / ``persist`` after a miss).
What JAX compiles on its own (every jitted function no `CompileCache`
fronts) is recorded too: `install_jit_listener` turns each of JAX's
trace / lower / backend-compile / persistent-cache-retrieval durations into
a ``jit_compile`` span with the function's name and counts it in
``nerrf_jit_compiles_total{stage}``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import shutil
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from nerrf_tpu.tracing import record, span

PAYLOAD = "executable.bin"
TREES = "trees.pkl"
META = "meta.json"

# compile-seconds histogram ladder: sub-second deserialize hits up to the
# flagship compile (minutes on a CPU, tens of seconds on a chip)
COMPILE_SECONDS_BUCKETS = (0.05, 0.25, 1.0, 5.0, 15.0, 60.0, 180.0, 600.0)

# default disk bound for a cache root (override per instance / `nerrf
# cache prune --max-bytes`): big enough for every ladder bucket at serve
# shapes plus the train programs, small enough for a pod cache volume
DEFAULT_MAX_BYTES = 2 << 30


@dataclasses.dataclass(frozen=True)
class CompileInfo:
    """Provenance of one load_or_compile resolution."""

    program: str
    fingerprint: str
    source: str              # "cache" | "fresh" | "live"
    seconds: float           # deserialize (cache) or lower+compile (fresh)
    reason: Optional[str] = None   # miss/fallback cause, None on a hit


def aval_signature(args: tuple, kwargs: dict) -> dict:
    """Canonical (shape, dtype, treedef) description of a call signature —
    the cache key's view of the arguments.  Weak-typed scalars hash by
    their numpy dtype, which is what the lowered program sees.  Public:
    the deep static pass (analysis/programs/cachekey.py) fingerprints
    candidate programs through exactly this view, so its coverage proof
    and the runtime cache can never disagree about what a key sees."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))

    def leaf_sig(leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        return [list(shape), str(dtype) if dtype is not None
                else type(leaf).__name__]

    return {"tree": str(treedef), "leaves": [leaf_sig(l) for l in leaves]}


def _host_isa_fingerprint() -> str:
    """Host ISA identity for CPU executables: XLA:CPU AOT artifacts are
    specialized to the compiling machine (SIGILL risk on a narrower host —
    see utils.enable_compilation_cache, which learned this live), so CPU
    cache keys carry the same machine|model|flags digest."""
    import platform

    flags = model = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if not flags and line.startswith(("flags", "Features")):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                if not model and line.startswith(("model name", "CPU part")):
                    model = line.split(":", 1)[1].strip()
                if flags and model:
                    break
    except OSError:
        pass
    return hashlib.sha256(
        f"{platform.machine()}|{model}|{flags}".encode()).hexdigest()[:12]


def source_digest(root: str | Path | None = None) -> str:
    """Digest of every ``.py`` file under ``root`` (this package): the
    identity of the source a program was lowered from.  The other key axes
    describe the call and the environment; none sees an edit to the model
    or to an op, and an executable compiled before the edit would answer
    the same lookup after it (measured: a step whose only change was its
    `jax.named_scope`s was served the old, scope-less executable from a
    warm cache).  Conservative like the rest of the key: an edit that would
    not have changed the HLO costs one fresh compile."""
    root = Path(root) if root is not None else Path(__file__).parents[1]
    h = hashlib.blake2s(digest_size=8)
    for path in sorted(root.rglob("*.py")):
        h.update(path.relative_to(root).as_posix().encode() + b"\0"
                 + path.read_bytes() + b"\0")
    return h.hexdigest()


def call_devices(args: tuple, kwargs: dict) -> list:
    """Ids of the devices a call with these arguments runs on: those its
    committed array arguments live on (a mesh-sharded batch, a replica's
    pinned params), else the default device.  An executable is compiled
    FOR a device assignment, so this is key material — on a four-chip
    host the program for chip 0 must not answer a lookup from chip 2."""
    import jax

    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        if isinstance(leaf, jax.Array) and leaf.committed:
            return sorted(d.id for d in leaf.sharding.device_set)
    return [(jax.config.jax_default_device or jax.devices()[0]).id]


def environment_key() -> dict:
    """The environment axes that invalidate an executable: jax/jaxlib (and
    libtpu when present) versions, this package's source (`source_digest`),
    backend platform, device kind, the ids of the devices the program was
    compiled for (here the default device; `load_or_compile` puts each
    call's own — `call_devices` — in its place), and — on CPU, where the
    artifact is ISA-specific — the host ISA."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    key = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "source": source_digest(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "devices": [dev.id],
    }
    try:  # pragma: no cover — only present on real TPU hosts
        import libtpu  # type: ignore

        key["libtpu"] = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        pass
    if dev.platform == "cpu":
        key["host_isa"] = _host_isa_fingerprint()
    return key


def compute_fingerprint(program: str, avals: dict, extra: Optional[dict],
                        env: Optional[dict] = None) -> Tuple[str, dict]:
    """→ (fingerprint, key_material).  The material is stamped into the
    entry's meta.json so `nerrf cache ls|verify` can explain every entry."""
    material = {
        "program": program,
        "avals": avals,
        "extra": extra or {},
        "env": env if env is not None else environment_key(),
    }
    canon = json.dumps(material, sort_keys=True, separators=(",", ":"),
                       default=str)
    return hashlib.blake2s(canon.encode(), digest_size=16).hexdigest(), \
        material


def _stage(name: str, **args):
    """One stage of a resolution: a child span of the open
    ``compile_resolve`` (on the profiler's host plane too, like it)."""
    return span(f"compile_resolve.{name}", device=True, **args)


# jax.monitoring's duration events -> the ``stage`` of a `jit_compile` span
JIT_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
# An event shorter than this is counted and not spanned: tracing one step
# function fires thousands of microsecond-long trace events for the jitted
# library functions inside it (3,456 for a toy NerrfNet step, 96 % of them
# under a millisecond), all inside their caller's own event, and a ring of
# 65,536 spans must still hold the whole run for its readers.
JIT_SPAN_MIN_SECONDS = 1e-3
_jit_listener_lock = threading.Lock()
_jit_listener_installed = False


def _on_jit_duration(event: str, secs: float, **kwargs) -> None:
    stage = JIT_STAGES.get(event)
    if stage is None:
        return
    from nerrf_tpu.observability import DEFAULT_REGISTRY

    if secs >= JIT_SPAN_MIN_SECONDS:
        # told when it is over: the span ends now.  Inside an open
        # `compile_resolve` stage it is that stage's child by the parent rule
        record("jit_compile", secs, device=True, stage=stage,
               fun=kwargs.get("fun_name"))
    DEFAULT_REGISTRY.counter_inc(
        "jit_compiles_total", labels={"stage": stage},
        help="JAX trace / lower / backend-compile / persistent-cache "
             "retrieval events; rising while a loop steps means a program "
             "is being built in the hot path")


def install_jit_listener() -> None:
    """Record JAX's own compile-stage durations as ``jit_compile`` spans,
    process-wide, once however often it is called.  A loaded executable's
    call emits no such event, so a step costs nothing."""
    global _jit_listener_installed
    with _jit_listener_lock:
        if _jit_listener_installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_jit_duration)
        _jit_listener_installed = True


def default_cache_dir() -> str:
    """The standard cache root: ``aot/`` under `utils.compile_cache_dir`
    ($JAX_COMPILATION_CACHE_DIR when set — the serve manifest mounts a
    volume there — else the fixed in-checkout directory), beside JAX's own
    persistent cache.  No host subdirectory — the key material carries the
    ISA axis instead, so one volume can serve heterogeneous hosts without
    ever cross-loading."""
    from nerrf_tpu.utils import compile_cache_dir

    return os.path.join(compile_cache_dir(), "aot")


class CompileCache:
    """One cache root.  Fail-open by contract: `get`/`put` return
    None/False on any failure; `load_or_compile` always returns a callable
    (worst case the live jit fn) plus a `CompileInfo` saying what happened.

    ``seed_dirs`` are read-only secondary roots — a checkpoint's
    ``executables/`` sidecar published by the registry.  A primary miss
    that hits a seed copies the entry in (atomic) and loads it, so a pod
    booting from a published version warms its local cache on first use.
    """

    def __init__(self, root: str | Path | None = None,
                 max_bytes: int = DEFAULT_MAX_BYTES,
                 seed_dirs: Tuple[str, ...] = (),
                 registry=None, journal=None, log=None) -> None:
        self.root = Path(root if root is not None
                         else default_cache_dir()).absolute()
        self.max_bytes = int(max_bytes)
        self.seed_dirs = tuple(Path(d).absolute() for d in seed_dirs if d)
        self._registry = registry
        self._journal = journal
        self._log = log or (lambda msg: None)
        self._env: Optional[dict] = None  # resolved lazily (needs a backend)
        install_jit_listener()

    # -- wiring ---------------------------------------------------------------

    def _reg(self):
        if self._registry is None:
            from nerrf_tpu.observability import DEFAULT_REGISTRY

            self._registry = DEFAULT_REGISTRY
        return self._registry

    def _jrn(self):
        if self._journal is None:
            from nerrf_tpu.flight.journal import DEFAULT_JOURNAL

            self._journal = DEFAULT_JOURNAL
        return self._journal

    def env(self) -> dict:
        if self._env is None:
            self._env = environment_key()
        return self._env

    def add_seed_dir(self, path) -> None:
        """Register a read-only secondary root (a published version's
        ``executables/`` sidecar) for future misses to fall back to."""
        p = Path(path).absolute()
        if p not in self.seed_dirs:
            self.seed_dirs = self.seed_dirs + (p,)

    def entry_dir(self, fingerprint: str) -> Path:
        return self.root / fingerprint

    # -- observability --------------------------------------------------------

    def _record(self, info: CompileInfo) -> None:
        reg = self._reg()
        if info.source == "cache":
            reg.counter_inc(
                "compile_cache_hits_total",
                labels={"program": info.program},
                help="compiled programs served from the persistent cache")
        else:
            reg.counter_inc(
                "compile_cache_misses_total",
                labels={"program": info.program,
                        "reason": info.reason or "absent"},
                help="cache lookups that fell back to a live compile, by "
                     "miss cause")
        reg.histogram_observe(
            "compile_seconds", info.seconds,
            buckets=COMPILE_SECONDS_BUCKETS,
            labels={"program": info.program, "source": info.source},
            help="wall seconds to obtain an executable, cache-deserialize "
                 "vs fresh XLA compile")
        self._jrn().record(
            "compile", program=info.program, fingerprint=info.fingerprint,
            source=info.source, seconds=round(info.seconds, 3),
            **({"reason": info.reason} if info.reason else {}))

    # -- read side ------------------------------------------------------------

    def get(self, fingerprint: str):
        """→ a loaded `jax.stages.Compiled`, or None (fail-open: any
        unreadable/corrupt/foreign entry is a miss, never an error).  Two
        stages: ``read`` (find, adopt from a seed, read the files) and
        ``deserialize``; a lookup that finds nothing is a ``read`` of 0
        bytes."""
        entry = None
        try:
            with _stage("read", bytes=0) as sp:
                entry, adopted = self._find_entry(fingerprint)
                if entry is None:
                    return None
                if adopted:
                    sp.args["adopted"] = True
                from nerrf_tpu import chaos

                # chaos fault point (no-op disarmed): bit rot / torn write
                # in the entry payload — deserialize must fail below and
                # take the evict-and-compile-live fail-open path, never
                # serve a damaged executable
                payload = chaos.mangle(
                    "compilecache.corrupt_payload",
                    (entry / PAYLOAD).read_bytes(), key=fingerprint)
                trees = (entry / TREES).read_bytes()
                sp.args["bytes"] = len(payload) + len(trees)
            with _stage("deserialize"):
                import jax
                from jax.experimental import serialize_executable as se

                in_tree, out_tree, device_ids = pickle.loads(trees)
                # the entry's own device assignment, in order: left to its
                # default, jax 0.9 reloads every executable over ALL devices
                # of the backend, and a single-device program then fails at
                # call time on any host with more than one
                by_id = {d.id: d for d in jax.devices()}
                compiled = se.deserialize_and_load(
                    payload, in_tree, out_tree,
                    execution_devices=[by_id[i] for i in device_ids])
        except Exception as e:  # noqa: BLE001 — fail-open by contract
            self._log(f"compile cache: entry {fingerprint} unreadable "
                      f"({type(e).__name__}: {e}); compiling live")
            # evict the corrupt entry (primary root only — seeds are
            # read-only) so the fresh compile that follows can repair it;
            # without this, `put` would keep deferring to the broken copy
            # and every future boot would re-pay the compile
            primary = self.entry_dir(fingerprint)
            if entry == primary:
                shutil.rmtree(primary, ignore_errors=True)
            return None
        try:  # LRU stamp; never worth failing a hit over
            os.utime(entry)
        except OSError:
            pass
        return compiled

    def _find_entry(self, fingerprint: str) -> Tuple[Optional[Path], bool]:
        """→ (the entry's directory or None, whether it was copied in from
        a seed root just now)."""
        primary = self.entry_dir(fingerprint)
        if (primary / PAYLOAD).is_file() and (primary / TREES).is_file():
            return primary, False
        for seed in self.seed_dirs:
            cand = seed / fingerprint
            if (cand / PAYLOAD).is_file() and (cand / TREES).is_file():
                adopted = self._adopt(cand, fingerprint)
                return adopted or cand, adopted is not None
        return None, False

    def _adopt(self, seed_entry: Path, fingerprint: str) -> Optional[Path]:
        """Copy a seed entry into the primary root (atomic, best-effort) so
        subsequent boots on this host hit locally."""
        target = self.entry_dir(fingerprint)
        try:
            self.root.mkdir(parents=True, exist_ok=True)
            tmp = Path(tempfile.mkdtemp(prefix=".adopt-", dir=self.root))
            try:
                for name in (PAYLOAD, TREES, META):
                    src = seed_entry / name
                    if src.is_file():
                        shutil.copy2(src, tmp / name)
                # an invalid husk at the target (crash mid-eviction) makes
                # rename fail ENOTEMPTY forever — and because the seed hit
                # succeeds, put() never runs to repair it, so every boot
                # would re-read across the (possibly remote) seed volume.
                # Replace it, exactly as put() does.
                if target.exists() and not (
                        (target / PAYLOAD).is_file()
                        and (target / TREES).is_file()):
                    shutil.rmtree(target, ignore_errors=True)
                os.rename(tmp, target)
            except OSError:
                shutil.rmtree(tmp, ignore_errors=True)
                return None
            return target
        except OSError:
            return None

    # -- write side -----------------------------------------------------------

    def put(self, fingerprint: str, compiled, material: dict,
            program: str, compile_seconds: float) -> Optional[str]:
        """Serialize + persist one compiled program (atomic tmp-then-
        rename).  Returns None on success, or the failure cause —
        "unserializable" (backend executables that do not support
        serialization) vs "unwritable" (read-only volume, disk full) —
        the distinction operators need to diagnose which; never raises."""
        try:
            with _stage("serialize"):
                from jax.experimental import serialize_executable as se

                payload, in_tree, out_tree = se.serialize(compiled)
                device_ids = [d.id for d in
                              compiled.runtime_executable().local_devices()]
                trees = pickle.dumps((in_tree, out_tree, device_ids))
        except Exception as e:  # noqa: BLE001 — fail-open by contract
            self._log(f"compile cache: cannot serialize {program} "
                      f"({type(e).__name__}: {e}); running uncached")
            return "unserializable"
        meta = {
            "schema_version": 2,
            "program": program,
            "fingerprint": fingerprint,
            "key": material,
            "payload_bytes": len(payload),
            "compile_seconds": round(compile_seconds, 3),
            "created_at": time.time(),
        }
        with _stage("persist", bytes=len(payload) + len(trees)):
            try:
                self._write_entry(fingerprint, payload, trees, meta)
            except OSError as e:
                self._log(f"compile cache: cannot persist {program} "
                          f"({type(e).__name__}: {e}); result stays "
                          "in-process")
                return "unwritable"
            self._reg().counter_inc(
                "compile_cache_bytes_total", float(len(payload)),
                help="serialized executable bytes written into the cache")
            self.prune()
        return None

    def _write_entry(self, fingerprint: str, payload: bytes, trees: bytes,
                     meta: dict) -> None:
        """One entry into the root, atomically (tmp directory, then
        rename); raises OSError where the root cannot take it."""
        self.root.mkdir(parents=True, exist_ok=True)
        tmp = Path(tempfile.mkdtemp(prefix=".put-", dir=self.root))
        try:
            (tmp / PAYLOAD).write_bytes(payload)
            (tmp / TREES).write_bytes(trees)
            (tmp / META).write_text(json.dumps(meta, indent=2))
            target = self.entry_dir(fingerprint)
            if (target / PAYLOAD).is_file() and (target / TREES).is_file():
                # concurrent writer won with a complete entry; keep it
                shutil.rmtree(tmp, ignore_errors=True)
            else:
                # absent, or an invalid husk (partial delete, missing
                # trees) that _find_entry skips — replace so a damaged
                # entry is repaired by the very compile it caused
                if target.exists():
                    shutil.rmtree(target, ignore_errors=True)
                os.rename(tmp, target)
        except OSError:
            shutil.rmtree(tmp, ignore_errors=True)
            raise

    # -- the one entry point --------------------------------------------------

    def load_or_compile(self, jit_fn, args: tuple, kwargs: dict | None = None,
                        program: str = "program",
                        extra: Optional[dict] = None):
        """→ (callable, CompileInfo).

        Hit: the deserialized `Compiled` (no tracing, no XLA).  Miss:
        ``jit_fn.lower(*args, **kwargs).compile()``, persisted for next
        time.  Total failure (lower/compile/serialize machinery broken):
        the live ``jit_fn`` itself, source="live" — serving always works.

        The whole resolution is one ``compile_resolve`` span and each
        stage (fingerprint, read, deserialize, or lower, compile,
        serialize, persist) a child of it; ``CompileInfo.seconds`` stays
        the read's or the compile's time alone.
        """
        with span("compile_resolve", device=True, program=program) as sp:
            fn, info = self._load_or_compile(jit_fn, args, kwargs or {},
                                             program, extra)
            sp.args.update(source=info.source, reason=info.reason)
        return fn, info

    def _load_or_compile(self, jit_fn, args: tuple, kwargs: dict,
                         program: str, extra: Optional[dict]):
        try:
            with _stage("fingerprint"):
                avals = aval_signature(args, kwargs)
                env = {**self.env(), "devices": call_devices(args, kwargs)}
                fp, material = compute_fingerprint(program, avals, extra,
                                                   env=env)
        except Exception as e:  # noqa: BLE001 — fail-open by contract
            info = CompileInfo(program=program, fingerprint="",
                               source="live", seconds=0.0,
                               reason=f"fingerprint: {type(e).__name__}: {e}")
            self._record(info)
            return jit_fn, info
        t0 = time.perf_counter()
        compiled = self.get(fp)
        if compiled is not None:
            info = CompileInfo(program=program, fingerprint=fp,
                               source="cache",
                               seconds=time.perf_counter() - t0)
            self._record(info)
            return compiled, info
        reason = "absent"
        t0 = time.perf_counter()
        try:
            compiled = self._compile_fresh(jit_fn, args, kwargs)
        except Exception as e:  # noqa: BLE001 — fail-open by contract
            info = CompileInfo(
                program=program, fingerprint=fp, source="live",
                seconds=time.perf_counter() - t0,
                reason=f"lower/compile: {type(e).__name__}: {e}")
            self._record(info)
            self._log(f"compile cache: AOT path failed for {program} "
                      f"({info.reason}); using the live jit function")
            return jit_fn, info
        seconds = time.perf_counter() - t0
        put_err = self.put(fp, compiled, material, program, seconds)
        if put_err:
            reason = put_err
        info = CompileInfo(program=program, fingerprint=fp, source="fresh",
                           seconds=seconds, reason=reason)
        self._record(info)
        return compiled, info

    @staticmethod
    def _compile_fresh(jit_fn, args: tuple, kwargs: dict):
        """``lower().compile()`` with JAX's own persistent compilation
        cache suspended.  Serializing an executable that was ITSELF loaded
        from that cache produces a payload whose compiled symbols are
        unresolvable in any other process ("Symbols not found" at
        deserialize — measured live on XLA:CPU), so a to-be-serialized
        compile must always be fresh.  Costs one full compile when only
        jax's cache was warm; this cache then persists the self-contained
        result, so it is paid at most once per program.

        Suspension has to go through the ``jax_enable_compilation_cache``
        flag AND ``compilation_cache.reset_cache()``: the installed jax
        (0.9) still memoizes its is-the-cache-used verdict process-wide on
        first compile (``compilation_cache.is_cache_used``), so flipping
        the flag alone is a silent no-op once anything has compiled
        (measured live: the e2e pre-flight caught poisoned payloads
        written exactly that way)."""
        import jax
        from jax.experimental.compilation_cache.compilation_cache import (
            reset_cache,
        )

        suspend = bool(jax.config.jax_compilation_cache_dir
                       and jax.config.jax_enable_compilation_cache)
        if suspend:
            jax.config.update("jax_enable_compilation_cache", False)
            reset_cache()  # drop the memoized verdict so the flag is re-read
        try:
            with _stage("lower"):
                lowered = jit_fn.lower(*args, **kwargs)
            with _stage("compile"):
                return lowered.compile()
        finally:
            if suspend:
                jax.config.update("jax_enable_compilation_cache", True)
                reset_cache()  # re-arm jax's cache for everyone else

    # -- maintenance (the `nerrf cache` surface) ------------------------------

    def entries(self) -> List[dict]:
        """Inventory, oldest-last-used first: [{fingerprint, program,
        bytes, created_at, last_used, valid}, ...]."""
        out = []
        if not self.root.is_dir():
            return out
        for d in sorted(self.root.iterdir()):
            if not d.is_dir() or d.name.startswith("."):
                continue
            meta = {}
            try:
                meta = json.loads((d / META).read_text())
            except (OSError, ValueError):
                pass
            size = 0
            for f in d.iterdir():
                try:
                    size += f.stat().st_size
                except OSError:
                    pass
            try:
                last_used = d.stat().st_mtime
            except OSError:
                last_used = 0.0
            out.append({
                "fingerprint": d.name,
                "program": meta.get("program"),
                "bytes": size,
                "created_at": meta.get("created_at"),
                "compile_seconds": meta.get("compile_seconds"),
                "last_used": last_used,
                "valid": (d / PAYLOAD).is_file() and (d / TREES).is_file(),
            })
        out.sort(key=lambda e: e["last_used"])
        return out

    def prune(self, max_bytes: Optional[int] = None) -> List[str]:
        """LRU disk bound: evict oldest-last-used entries until the root
        fits.  Returns evicted fingerprints.  Best-effort — an entry that
        cannot be removed (NFS silly-rename, permissions) is skipped."""
        limit = self.max_bytes if max_bytes is None else int(max_bytes)
        entries = self.entries()
        total = sum(e["bytes"] for e in entries)
        evicted = []
        for e in entries:
            if total <= limit:
                break
            try:
                shutil.rmtree(self.entry_dir(e["fingerprint"]))
            except OSError:
                continue
            total -= e["bytes"]
            evicted.append(e["fingerprint"])
        if evicted:
            self._jrn().record("compile_cache_prune", evicted=len(evicted),
                               kept_bytes=total, limit_bytes=limit)
        return evicted

    def verify(self) -> List[dict]:
        """Integrity pass: every entry's files present, meta parseable, and
        the stamped fingerprint matching the directory name.  Returns the
        problems ([] = clean); read-only (deleting is `prune`'s job)."""
        problems = []
        if not self.root.is_dir():
            return problems
        for d in sorted(self.root.iterdir()):
            if not d.is_dir() or d.name.startswith("."):
                continue
            for name in (PAYLOAD, TREES, META):
                if not (d / name).is_file():
                    problems.append({"fingerprint": d.name,
                                     "problem": f"missing {name}"})
            meta_file = d / META
            if meta_file.is_file():
                try:
                    meta = json.loads(meta_file.read_text())
                    if meta.get("fingerprint") != d.name:
                        problems.append(
                            {"fingerprint": d.name,
                             "problem": "meta fingerprint mismatch "
                                        f"({meta.get('fingerprint')})"})
                    want = meta.get("payload_bytes")
                    payload = d / PAYLOAD
                    if want is not None and payload.is_file() and \
                            payload.stat().st_size != want:
                        problems.append(
                            {"fingerprint": d.name,
                             "problem": f"payload truncated "
                                        f"({payload.stat().st_size} != "
                                        f"{want} bytes)"})
                except (OSError, ValueError) as e:
                    problems.append({"fingerprint": d.name,
                                     "problem": f"meta unreadable: {e}"})
        return problems
