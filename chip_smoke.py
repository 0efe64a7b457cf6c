#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # on a TPU; anywhere else: exit 1

One process (a chip belongs to one process at a time), every phase through
the entry points a user calls, at the full width of the flagship model
(`JointConfig()`: 28 x 160 GraphSAGE-T + 2 x 256 BiLSTM):

  barrier   a jitted call timed to `jax.block_until_ready` and to
            `utils.fetch_value` — the two barriers must agree
  kernels   the ops that have two routes, each route against a host
            reference at 1024n/2048e/160 and 4096n/8192e/160, forward and
            VJP, under vmap: `ops.gather_rows` (the selection matmul on a
            TPU) beside `jnp.take`, and the SAGE aggregate as gathers and
            scatter-adds (`segment`) beside the adjacency matmul
            (`dense_adj`)
  train     `nerrf_tpu.train.run` on an experiment that copies
            configs/joint-100h.json's `dataset` and `train.model` and shrinks
            only the corpus and the step count: at 1024n/2048e and,
            re-padded, at 4096n/8192e (`auto` takes the dense adjacency at
            both)
  serve     `nerrf serve-detect` on the checkpoint the trainer wrote, a
            sparse and a dense seeded trace, over both buckets
  four      `train.run` on configs/multihost-online.json's dp x tp mesh —
            only where JAX reports four or more devices

A phase that fails raises: nothing is caught, logged and continued.  The
last line of stdout is one JSON object naming the device as JAX reports it.

`--rehearsal` runs the same code on the CPU at a toy width — for debugging
the script, and for tier-1.  It
stamps `rehearsal` on every line it prints; a number from it is not a
device metric.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent

# what the chip run and the CPU rehearsal each size the phases to; the code
# below is the same for both
CHIP = dict(
    barrier_n=2048,             # a 64-matmul chain of n x n: ~7 ms on a v5e
    kernel_shapes=((1024, 2048, 160), (4096, 8192, 160)),   # N, E, F
    train_buckets=((1024, 2048), (4096, 8192)),
    # bench.py's recipe (make_corpus: 24 files, 40 Hz), cut to three traces
    corpus=dict(num_traces=3, duration_sec=120.0, num_target_files=24,
                benign_rate_hz=40.0),
    num_steps=60,
    model=None,                 # joint-100h's train.model, verbatim
    gather_route="xla_selection_matmul",   # ops.gather_rows on a TPU
    serve_buckets=("1024x2048x128", "4096x8192x128"),
    # windows of ~600 and ~3,200 nodes: one trace for each bucket
    serve_traces=(dict(duration_sec=120.0, num_target_files=24,
                       benign_rate_hz=40.0, seed=101),
                  dict(duration_sec=90.0, num_target_files=45,
                       benign_rate_hz=550.0, seed=102)),
)
REHEARSAL = dict(
    barrier_n=256,
    kernel_shapes=((128, 256, 32), (256, 512, 32)),
    train_buckets=((256, 512), (512, 1024)),
    corpus=dict(num_traces=3, duration_sec=60.0, num_target_files=4,
                benign_rate_hz=6.0),
    num_steps=6,
    # off a TPU `auto` resolves to segment/rnn: name the modes the chip
    # takes, so the rehearsal traces the code the chip will run
    # (hidden 64: the narrowest kernel `parallel.mesh` still splits over tp)
    model=dict(gnn=dict(hidden=64, num_layers=2),
               lstm=dict(hidden=32, num_layers=1, impl="fused")),
    # nothing names `gather_rows`' route: it reads the backend, and off a
    # TPU the compiler's gather serves
    gather_route="xla",
    serve_buckets=("256x512x32", "512x1024x32"),
    serve_traces=(dict(duration_sec=60.0, num_target_files=4,
                       benign_rate_hz=6.0, seed=103),
                  dict(duration_sec=60.0, num_target_files=8,
                       benign_rate_hz=30.0, seed=104)),
)

TAG = ""          # " rehearsal" under --rehearsal: stamped on every line


def say(msg: str) -> None:
    print(f"[chip_smoke{TAG}] {msg}", flush=True)


# --------------------------------------------------------------------------
# per-phase accounting: compile seconds, cache traffic, device memory
# --------------------------------------------------------------------------

_COMPILE = {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0}
_JAX_CACHE = {"hits": 0, "misses": 0}


def _listen() -> None:
    """Sum JAX's own compile-stage durations and persistent-cache events."""
    import jax.monitoring as mon

    stage = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
             "/jax/core/compile/backend_compile_duration": "backend_s"}

    def on_duration(event, secs, **_):
        if event in stage:
            _COMPILE[stage[event]] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _JAX_CACHE["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _JAX_CACHE["misses"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)


def _counters(name: str, **match) -> float:
    """Sum of a counter over every label set containing ``match``."""
    from nerrf_tpu.observability import DEFAULT_REGISTRY

    series = DEFAULT_REGISTRY.snapshot()["counters"].get(name, {})
    want = {f"{k}={v}" for k, v in match.items()}
    return sum(v for labels, v in series.items()
               if want <= set(labels.split(",")))


def _cache_entries() -> int:
    """Entries on disk under the compile-cache root: JAX's own files plus
    the AOT cache's directories."""
    from nerrf_tpu.utils import compile_cache_dir

    root = Path(compile_cache_dir())
    aot = root / "aot"
    return (len(list(root.glob("*-cache")))
            + (sum(d.is_dir() and not d.name.startswith(".")
                   for d in aot.iterdir()) if aot.is_dir() else 0))


def _snapshot() -> dict:
    return {**_COMPILE, **{f"jax_{k}": v for k, v in _JAX_CACHE.items()},
            "aot_hits": _counters("compile_cache_hits_total"),
            "aot_misses": _counters("compile_cache_misses_total"),
            "entries": _cache_entries()}


@contextlib.contextmanager
def phase(name: str):
    """Time one phase and print what it compiled, what the caches did and
    the device's peak memory so far.  Exceptions pass straight through."""
    import jax

    before, t0 = _snapshot(), time.perf_counter()
    say(f"phase {name}: start")
    yield
    after, wall = _snapshot(), time.perf_counter() - t0
    d = {k: after[k] - before[k] for k in after}
    stats = jax.devices()[0].memory_stats() or {}
    say(f"phase {name}: ok wall_s={wall:.1f} "
        f"compile_s={d['trace_s'] + d['lower_s'] + d['backend_s']:.1f} "
        f"(trace {d['trace_s']:.1f} lower {d['lower_s']:.1f} "
        f"backend {d['backend_s']:.1f}) "
        f"jax_cache hits={d['jax_hits']:.0f} misses={d['jax_misses']:.0f} "
        f"aot_cache hits={d['aot_hits']:.0f} misses={d['aot_misses']:.0f} "
        f"cache_entries {before['entries']}->{after['entries']} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke{TAG}: FAILED — {what}")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def barrier_phase(n: int) -> None:
    """`block_until_ready` and a fetch must both wait for the device."""
    import jax
    import jax.numpy as jnp

    from nerrf_tpu.utils import fetch_value

    @jax.jit
    def chain(x):
        y = jax.lax.fori_loop(0, 64, lambda _, a: jnp.tanh(a @ x), x)
        return jnp.sum(y.astype(jnp.float32))     # a scalar: nothing to copy

    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)
    fetch_value(chain(x))                          # compile + warm

    def timed(wait):
        t0 = time.perf_counter()
        wait(chain(x))
        return time.perf_counter() - t0

    # the least of several runs: the estimate host noise disturbs least
    dispatch = min(timed(lambda y: None) for _ in range(7))
    jax.block_until_ready(chain(x))                # drain the un-waited calls
    block = min(timed(jax.block_until_ready) for _ in range(7))
    fetch = min(timed(fetch_value) for _ in range(7))
    say(f"barrier: dispatch_only={dispatch * 1e3:.2f}ms "
        f"block_until_ready={block * 1e3:.2f}ms "
        f"fetch_value={fetch * 1e3:.2f}ms")
    # a barrier that does not wait returns in dispatch time — orders of
    # magnitude early, not tens of percent
    check(0.5 <= block / fetch <= 2.0,
          f"block_until_ready ({block * 1e3:.2f} ms) and fetch_value "
          f"({fetch * 1e3:.2f} ms) disagree about when the call ended")


def kernels_phase(shapes) -> None:
    """The ops that have more than one route, each route against a host
    (numpy, float64) reference: forward and VJP, jitted, under vmap.  Every
    route is code the compiler writes; the oracle is the host, not one of
    them: either side can be the one that is wrong."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nerrf_tpu.models.graphsage import dense_adjacency
    from nerrf_tpu.ops import gather_rows, sage_aggregate
    from nerrf_tpu.ops.segment import gather_rows_route

    B, tol = 2, 2e-4

    def scatter(rows, ids, n):           # [B,E,F] rows summed into [B,n,F]
        out = np.zeros((B, n, rows.shape[-1]))
        for b in range(B):
            np.add.at(out[b], ids[b], rows[b])
        return out

    def gather(table, ids):              # [B,n,F] → [B,E,F]
        return np.stack([table[b][ids[b]] for b in range(B)])

    def compare(name, impls, host_fwd, host_vjp, x, *rest):
        """fwd and VJP (against one fixed cotangent) of each implementation
        vs the host's; ``x`` is the differentiated argument."""
        cot = np.random.default_rng(5).normal(size=host_fwd.shape)
        want_vjp = host_vjp(cot)
        for label, fn in impls.items():
            fwd = np.asarray(jax.jit(fn)(x, *rest))
            # the cotangent goes in as an ARGUMENT, as every cotangent in
            # training is a computed value.  Closed over, it becomes a
            # constant of the program, and XLA:TPU (jax 0.9.0 / libtpu
            # 0.0.34, v5e) miscompiles the batched gather that reads it:
            # one feature column filled, the rest zero (PERF.md, PR 21)
            vjp = np.asarray(jax.jit(jax.grad(lambda a, c, fn=fn: jnp.sum(
                fn(a, *rest) * c)))(x, jnp.asarray(cot, jnp.float32)))
            e_fwd = float(np.max(np.abs(fwd - host_fwd)
                                 / (1.0 + np.abs(host_fwd))))
            e_vjp = float(np.max(np.abs(vjp - want_vjp)
                                 / (1.0 + np.abs(want_vjp))))
            say(f"kernel {name} [{label}]: fwd_err={e_fwd:.2e} "
                f"vjp_err={e_vjp:.2e} (tol {tol:.0e})")
            check(np.isfinite(fwd).all() and np.isfinite(vjp).all()
                  and e_fwd <= tol and e_vjp <= tol,
                  f"{name} [{label}] disagrees with the host reference: "
                  f"fwd {e_fwd:.2e} vjp {e_vjp:.2e}")

    for N, E, F in shapes:
        rng = np.random.default_rng(N)
        src = rng.integers(0, N, (B, E)).astype(np.int32)
        dst = np.sort(rng.integers(0, N, (B, E)), axis=1).astype(np.int32)
        table = rng.normal(size=(B, N, F)).astype(np.float32)
        shape = f"@{N}n/{E}e/{F}"

        compare(f"gather_rows{shape}", {
            f"ops: {gather_rows_route(N)}": jax.vmap(gather_rows),
            "take": jax.vmap(lambda t, i: jnp.take(t, i, axis=0))},
            gather(table, src), lambda g: scatter(g, src, N),
            jnp.asarray(table), jnp.asarray(src))

        # one random graph in both sorted orders, weights in both: the
        # aggregate as `segment` / `fused` sum it (gathers and scatter-adds)
        # and as `dense_adj` does (one matmul against the [N,N] adjacency;
        # float32 at `highest`, or the MXU's bf16 passes are what differs)
        order = np.argsort(src, axis=1)
        by_src = lambda a: np.take_along_axis(a, order, 1)
        wf = rng.uniform(0.1, 1.0, (B, E)).astype(np.float32)
        wr = rng.uniform(0.1, 1.0, (B, E)).astype(np.float32)
        edges = (dst, src, by_src(src), by_src(dst),
                 wf, by_src(wf), by_src(wr), wr)
        one, zero = jnp.ones((N,), jnp.float32), jnp.zeros((N,), jnp.float32)

        def dense(m, dst, src, _s, _d, wf, _wfs, _wrs, wr):
            # the model's own build, once a direction (its two weights are
            # one vector there; here they differ so that a swap would show)
            fwd = dense_adjacency(src, dst, wf, one, zero, N, m.dtype)
            rev = dense_adjacency(src, dst, wr, zero, one, N, m.dtype)
            return (fwd + rev) @ m

        with jax.default_matmul_precision("highest"):
            compare(f"sage_aggregate{shape}", {
                "segment": jax.vmap(lambda m, *e: sage_aggregate(m, *e, N)),
                "dense_adj": jax.vmap(dense)},
                scatter(wf[..., None] * gather(table, src), dst, N)
                + scatter(wr[..., None] * gather(table, dst), src, N),
                lambda g: scatter(wf[..., None] * gather(g, dst), src, N)
                + scatter(wr[..., None] * gather(g, src), dst, N),
                jnp.asarray(table), *map(jnp.asarray, edges))


def _experiment(cfg: dict, source: str, name: str, bucket=None,
                mode=None) -> dict:
    """An experiment JSON that copies ``configs/<source>.json``'s dataset,
    train and mesh blocks and shrinks only the corpus and the step count
    (under --rehearsal also the model, to a toy width, with ``mode`` — the
    aggregation the chip resolves `auto` to — named outright)."""
    base = json.loads((REPO / "configs" / f"{source}.json").read_text())
    exp = {**base, "name": name, "corpus_dir": None, "stream": None,
           "description": f"chip_smoke: {source} cut to a few steps",
           "corpus": {**base["corpus"], **cfg["corpus"]},
           "train": {**base["train"], "num_steps": cfg["num_steps"]}}
    if bucket:
        exp["dataset"]["graph"].update(max_nodes=bucket[0],
                                       max_edges=bucket[1])
    if cfg["model"]:                       # rehearsal only
        toy = cfg["model"]
        exp["train"]["model"]["gnn"].update(toy["gnn"], aggregation=mode)
        exp["train"]["model"]["lstm"].update(toy["lstm"])
        exp["train"]["batch_size"] = 4
        exp["dataset"].update(seq_len=16, max_seqs=32)
    return exp


def _train(exp: dict, work: Path, aot) -> dict:
    from nerrf_tpu.train.run import run_experiment

    path = work / f"{exp['name']}.json"
    path.write_text(json.dumps(exp, indent=2))
    # calibrate=False: the held-out threshold sweep is post-processing of a
    # finished run (minutes of it), not part of starting on the chip
    report = run_experiment(str(path), work / exp["name"],
                            compile_cache=aot, calibrate=False)
    loss = report["loss"]
    say(f"train {exp['name']}: steps={report['num_steps']} "
        f"loss {loss['first']:.4f} -> {loss['last']:.4f} "
        f"steps_per_sec={report['steps_per_sec']} "
        f"kernel_path={report['kernel_path']} metrics={report['metrics']}")
    check(all(map(math.isfinite, (loss["first"], loss["last"],
                                  *report["metrics"].values()))),
          f"{exp['name']}: non-finite loss or eval metric")
    check(loss["last"] < loss["first"], f"{exp['name']}: loss did not fall")
    return report


def train_phase(cfg: dict, work: Path, aot, idx: int) -> Path:
    """A few tens of steps at one bucket; returns the checkpoint dir."""
    bucket = cfg["train_buckets"][idx]
    want_mode = "dense_adj"     # `auto` on a TPU, at both buckets
    exp = _experiment(cfg, "joint-100h", f"smoke-{bucket[0]}n", bucket,
                      mode=want_mode)
    report = _train(exp, work, aot)
    kp = dict(report["kernel_path"])
    modes = (kp.pop("gnn_aggregation"), kp.pop("lstm_impl"))
    check(modes == (want_mode, "fused"),
          f"aggregation/LSTM resolved to {modes}, not ({want_mode}, fused)")
    # nothing else has a route: every op is compiler-written
    check(kp == {"gather_rows": cfg["gather_route"]},
          f"not the routes a TPU takes: {report['kernel_path']}")
    return work / exp["name"] / "model"


def serve_phase(cfg: dict, work: Path, model_dir: Path) -> None:
    """serve-detect on the trainer's checkpoint, one trace for each bucket."""
    from nerrf_tpu import cli
    from nerrf_tpu.data import SimConfig, simulate_trace
    from nerrf_tpu.schema.events import events_to_jsonl

    args = ["serve-detect", "--model-dir", str(model_dir),
            "--metrics-port", "-1", "--buckets", *cfg["serve_buckets"]]
    for i, sim in enumerate(cfg["serve_traces"]):
        trace = simulate_trace(SimConfig(attack=True, **sim))
        path = work / f"serve_trace_{i}.jsonl"
        path.write_text(events_to_jsonl(trace.events, trace.strings))
        args += ["--trace", str(path)]

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = cli.main(args)
    # the command returns 0 whatever its summary holds: the summary and the
    # counters judge, not the exit code
    check(rc == 0, f"serve-detect exited {rc}")
    s = json.loads(out.getvalue())
    failures = {
        "failed_batches": _counters("serve_batch_failures_total"),
        "bisected_batches": _counters("serve_poison_bisections_total"),
        "call_failed": _counters("compile_cache_misses_total",
                                 reason="call_failed"),
        "oversize": s["admission_dropped"]["oversize"],
        "recompiles_after_warmup": s["recompiles_after_warmup"],
    }
    per_bucket = {b: _counters("serve_batches_total", bucket="{}n/{}e/{}s"
                               .format(*b.split("x")))
                  for b in cfg["serve_buckets"]}
    say(f"serve: windows_scored={s['windows_scored']:.0f} "
        f"alerts={s['alerts']} batches_per_bucket={per_bucket} "
        f"{failures} streams={s['streams']}")
    check(s["windows_scored"] > 0, "no window was scored")
    check(all(st["done"] and st["error"] is None
              for st in s["streams"].values()), "a stream did not finish")
    check(not any(failures.values()), f"serve failures: {failures}")
    check(all(per_bucket.values()), f"a bucket served nothing: {per_bucket}")


def four_chip_phase(cfg: dict, work: Path, aot) -> None:
    """multihost-online's dp x tp mesh over every device of the host."""
    import jax

    exp = _experiment(cfg, "multihost-online", "smoke-mesh",
                      mode="dense_adj")
    report = _train(exp, work, aot)
    sh = report["sharding"]
    say(f"four: sharding as placed {sh}")
    check(sh["mesh"]["tp"] == 2 and sh["tp_sharded_leaves"] > 0,
          "the tp axis partitions no parameter")
    check(sh["batch_devices"] == jax.device_count() >= 4,
          f"batch on {sh['batch_devices']} of {jax.device_count()} devices")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    global TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, toy width; every line says so and nothing "
                         "printed is a device metric")
    args = ap.parse_args(argv)
    cfg = REHEARSAL if args.rehearsal else CHIP
    TAG = " rehearsal" if args.rehearsal else ""

    import jax
    import jaxlib

    from nerrf_tpu.devtime.peaks import chip_peaks
    from nerrf_tpu.utils import compile_cache_dir, enable_compilation_cache

    enable_compilation_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    say(f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"count={device['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_version} "
        f"compile_cache={compile_cache_dir()} "
        f"(entries: {_cache_entries()})")
    if not args.rehearsal:
        if dev.platform != "tpu":
            raise SystemExit(
                f"chip_smoke: no TPU — JAX picked {dev.platform!r}; the "
                "smoke runs on the chip (or, to debug the script itself, "
                "with --rehearsal)")
        if chip_peaks(dev) is None:
            raise SystemExit(
                f"chip_smoke: device_kind {dev.device_kind!r} is not in "
                "nerrf_tpu/devtime/peaks.py — add its row, with its "
                "source, before measuring on it")
    _listen()

    from nerrf_tpu.compilecache import CompileCache

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        work = Path(tmp)
        aot = CompileCache(log=lambda m: say(f"aot: {m}"))
        with phase("barrier"):
            barrier_phase(cfg["barrier_n"])
        with phase("kernels"):
            kernels_phase(cfg["kernel_shapes"])
        for idx, bucket in enumerate(cfg["train_buckets"]):
            with phase(f"train@{bucket[0]}n/{bucket[1]}e"):
                model_dir = train_phase(cfg, work, aot, idx)
        with phase("serve"):
            serve_phase(cfg, work, model_dir)
        if jax.device_count() >= 4:
            with phase("four"):
                four_chip_phase(cfg, work, aot)
        else:
            say(f"phase four: skipped ({jax.device_count()} device(s); the "
                "dp x tp leg needs four)")
    print(json.dumps({"ok": True, "device": device,
                      **({"rehearsal": True} if args.rehearsal else {})}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
