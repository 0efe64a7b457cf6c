"""Persistent compile cache: content-addressed keys, AOT roundtrips,
fail-open fallback, and the warm-boot serve acceptance criteria.

Key-invalidation coverage is the safety half of the contract: any drift in
architecture, bucket shape, jax/device identity, or donation spec MUST
miss (a stale executable can never be reused); corruption coverage is the
availability half: a damaged cache costs one live compile and a journal
record, never an exception and never readiness.

Cache-mechanics tests use a trivial jit function (compiles in
milliseconds); the serve tests at the end compile the real small model
once per module and prove second-boot `source=cache` for every bucket plus
bit-parity with offline `model_detect` when scoring runs on a deserialized
executable.
"""

import dataclasses
import json
import os
import shutil
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nerrf_tpu.compilecache import (
    CompileCache,
    StepCache,
    compute_fingerprint,
    environment_key,
    export_executables,
    read_manifest,
)
from nerrf_tpu.compilecache.cache import (
    META,
    PAYLOAD,
    TREES,
    aval_signature,
    source_digest,
)
from nerrf_tpu.flight.journal import EventJournal
from nerrf_tpu.observability import MetricsRegistry

BUCKET = (256, 512, 64)  # test_serve's parity bucket: windows always fit


def _tiny_jit():
    return jax.jit(lambda x: x * 2.0 + 1.0)


def _args(n=4):
    return (np.arange(n, dtype=np.float32),)


def _cache(tmp_path, **kw):
    kw.setdefault("registry", MetricsRegistry(namespace="test"))
    kw.setdefault("journal", EventJournal(registry=kw["registry"]))
    return CompileCache(root=tmp_path / "aot", **kw)


def _compile_records(journal):
    return [r for r in journal.tail() if r.kind == "compile"]


# -- fingerprint axes ---------------------------------------------------------

def test_fingerprint_invalidates_on_every_axis():
    """Changing ANY of (program, arg shapes/dtypes/tree, architecture,
    donation spec, jax version, jaxlib version, device kind, the devices
    compiled for, platform) produces a different fingerprint — the no-stale-reuse
    guarantee is structural, not probabilistic."""
    avals = aval_signature(_args(), {})
    env = {"jax": "0.4.30", "jaxlib": "0.4.30", "platform": "cpu",
           "device_kind": "cpu", "devices": [0]}
    extra = {"model": "JointConfig(hidden=32)", "donate": "(params,)"}
    base, _ = compute_fingerprint("train_step", avals, extra, env=env)

    variants = [
        ("program", compute_fingerprint("stream_step", avals, extra,
                                        env=env)[0]),
        ("arg shape", compute_fingerprint(
            "train_step", aval_signature(_args(8), {}), extra, env=env)[0]),
        ("arg dtype", compute_fingerprint(
            "train_step",
            aval_signature((np.arange(4, dtype=np.float64),), {}),
            extra, env=env)[0]),
        ("pytree layout", compute_fingerprint(
            "train_step", aval_signature(({"x": _args()[0]},), {}),
            extra, env=env)[0]),
        ("architecture", compute_fingerprint(
            "train_step", avals,
            {**extra, "model": "JointConfig(hidden=64)"}, env=env)[0]),
        ("donation spec", compute_fingerprint(
            "train_step", avals, {**extra, "donate": "()"}, env=env)[0]),
        ("jax version", compute_fingerprint(
            "train_step", avals, extra, env={**env, "jax": "0.4.31"})[0]),
        ("jaxlib version", compute_fingerprint(
            "train_step", avals, extra,
            env={**env, "jaxlib": "0.4.31"})[0]),
        ("device kind", compute_fingerprint(
            "train_step", avals, extra,
            env={**env, "device_kind": "TPU v4"})[0]),
        ("devices", compute_fingerprint(
            "train_step", avals, extra, env={**env, "devices": [2]})[0]),
        ("platform", compute_fingerprint(
            "train_step", avals, extra, env={**env, "platform": "tpu"})[0]),
    ]
    fps = [fp for _, fp in variants]
    for axis, fp in variants:
        assert fp != base, f"{axis} drift did not invalidate"
    assert len(set(fps)) == len(fps), "axis collisions"
    # determinism: same material → same fingerprint
    assert compute_fingerprint("train_step", avals, extra,
                               env=env)[0] == base


def test_environment_key_carries_live_identity():
    env = environment_key()
    assert env["jax"] and env["jaxlib"]
    assert env["platform"] == jax.devices()[0].platform
    assert env["devices"] == [jax.devices()[0].id]
    if env["platform"] == "cpu":
        # CPU AOT artifacts are ISA-specific — the key must say whose
        assert env["host_isa"]
    assert env["source"] == source_digest()


def test_source_digest_follows_every_edit_under_the_root(tmp_path):
    """An executable compiled before an edit to the program must not answer
    a lookup after it: the digest moves with any .py file's content or
    name, and with nothing else."""
    (tmp_path / "models").mkdir()
    (tmp_path / "models" / "a.py").write_text("x = 1\n")
    (tmp_path / "b.py").write_text("y = 2\n")
    (tmp_path / "notes.txt").write_text("not source")
    base = source_digest(tmp_path)
    assert base == source_digest(tmp_path)
    (tmp_path / "notes.txt").write_text("still not source")
    assert source_digest(tmp_path) == base
    (tmp_path / "models" / "a.py").write_text("x = 2\n")
    edited = source_digest(tmp_path)
    assert edited != base
    (tmp_path / "models" / "a.py").rename(tmp_path / "models" / "c.py")
    assert source_digest(tmp_path) not in (base, edited)


def test_train_step_key_extra_tracks_config():
    from nerrf_tpu.train import TrainConfig
    from nerrf_tpu.train.loop import step_key_extra

    a = step_key_extra(TrainConfig(), "train_step")
    b = step_key_extra(TrainConfig(learning_rate=1e-4), "train_step")
    c = step_key_extra(TrainConfig(), "train_step_resident")
    assert a != b, "optimizer hyperparameters must ride the cache key"
    assert a != c, "step flavor must ride the cache key"
    assert a == step_key_extra(TrainConfig(), "train_step")


# -- roundtrip + provenance ---------------------------------------------------

def test_hit_roundtrip_metrics_and_journal(tmp_path):
    reg = MetricsRegistry(namespace="test")
    jrn = EventJournal(registry=reg)
    fn = _tiny_jit()

    c1 = _cache(tmp_path, registry=reg, journal=jrn)
    g1, i1 = c1.load_or_compile(fn, _args(), program="tiny")
    assert i1.source == "fresh" and i1.fingerprint
    # a second process (fresh instance, same root) must deserialize
    c2 = _cache(tmp_path, registry=reg, journal=jrn)
    g2, i2 = c2.load_or_compile(fn, _args(), program="tiny")
    assert i2.source == "cache" and i2.fingerprint == i1.fingerprint
    np.testing.assert_array_equal(np.asarray(g1(*_args())),
                                  np.asarray(g2(*_args())))

    assert reg.value("compile_cache_hits_total",
                     labels={"program": "tiny"}) == 1
    assert reg.value("compile_cache_misses_total",
                     labels={"program": "tiny", "reason": "absent"}) == 1
    assert reg.value("compile_cache_bytes_total") > 0
    recs = _compile_records(jrn)
    assert [r.data["source"] for r in recs] == ["fresh", "cache"]
    assert all(r.data["fingerprint"] == i1.fingerprint for r in recs)

    # meta.json records the full key material for `nerrf cache ls|verify`
    meta = json.loads(
        (c1.entry_dir(i1.fingerprint) / META).read_text())
    assert meta["fingerprint"] == i1.fingerprint
    assert meta["key"]["program"] == "tiny"
    assert meta["key"]["env"]["jax"]


def test_reload_keeps_the_device_assignment(tmp_path):
    """jax 0.9's ``deserialize_and_load`` defaults ``execution_devices`` to
    every device of the backend; the entry carries its own assignment so a
    program pinned to one device, and one sharded over a permuted 2x2 mesh,
    each reload onto exactly the devices they were compiled for (conftest's
    8 virtual CPU devices stand in for a multi-chip host)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    fn = _tiny_jit()
    devs = jax.devices()
    assert len(devs) >= 4

    x0 = _args()[0]
    x3 = jax.device_put(x0, devs[3])
    _, on0 = _cache(tmp_path).load_or_compile(fn, (x0,), program="tiny")
    _, on3 = _cache(tmp_path).load_or_compile(fn, (x3,), program="tiny")
    assert on3.source == "fresh" and on3.fingerprint != on0.fingerprint, (
        "the device compiled for must ride the key")
    g3, hit3 = _cache(tmp_path).load_or_compile(fn, (x3,), program="tiny")
    assert hit3.source == "cache"
    assert g3(x3).sharding.device_set == {devs[3]}

    mesh = Mesh(np.array(devs[:4])[[2, 0, 3, 1]].reshape(2, 2), ("a", "b"))
    xs = jax.device_put(np.arange(8, dtype=np.float32).reshape(4, 2),
                        NamedSharding(mesh, P("a", "b")))
    _, fresh = _cache(tmp_path).load_or_compile(fn, (xs,), program="tiny")
    gs, hit = _cache(tmp_path).load_or_compile(fn, (xs,), program="tiny")
    assert (fresh.source, hit.source) == ("fresh", "cache")
    out = gs(xs)
    assert out.sharding.device_set == set(devs[:4])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(fn(xs)))


def test_distinct_signatures_distinct_entries(tmp_path):
    c = _cache(tmp_path)
    fn = _tiny_jit()
    _, a = c.load_or_compile(fn, _args(4), program="tiny")
    _, b = c.load_or_compile(fn, _args(8), program="tiny")
    assert a.fingerprint != b.fingerprint
    assert {e["fingerprint"] for e in c.entries()} == {a.fingerprint,
                                                       b.fingerprint}


# -- fail-open ----------------------------------------------------------------

@pytest.mark.parametrize("victim", [PAYLOAD, TREES])
def test_corrupt_entry_falls_back_live_and_repairs(tmp_path, victim):
    """The availability half of the contract: a truncated/corrupt entry is
    a miss (live compile, journal record), never an exception — and the
    compile it caused REPAIRS the entry so the damage is paid once."""
    reg = MetricsRegistry(namespace="test")
    jrn = EventJournal(registry=reg)
    fn = _tiny_jit()
    c = _cache(tmp_path, registry=reg, journal=jrn)
    _, info = c.load_or_compile(fn, _args(), program="tiny")
    (c.entry_dir(info.fingerprint) / victim).write_bytes(b"garbage")

    c2 = _cache(tmp_path, registry=reg, journal=jrn)
    g, i2 = c2.load_or_compile(fn, _args(), program="tiny")
    assert i2.source == "fresh", "corruption must not be served"
    np.testing.assert_array_equal(np.asarray(g(*_args())),
                                  np.asarray(fn(*_args())))
    assert _compile_records(jrn)[-1].data["source"] == "fresh"

    c3 = _cache(tmp_path, registry=reg, journal=jrn)
    _, i3 = c3.load_or_compile(fn, _args(), program="tiny")
    assert i3.source == "cache", "the fresh compile must repair the entry"


def test_husk_entry_is_repaired(tmp_path):
    """An entry that lost trees.pkl entirely (partial delete) is invisible
    to lookup but still occupies the target dir — `put` must replace it,
    not defer to it forever."""
    c = _cache(tmp_path)
    fn = _tiny_jit()
    _, info = c.load_or_compile(fn, _args(), program="tiny")
    (c.entry_dir(info.fingerprint) / TREES).unlink()
    _, i2 = _cache(tmp_path).load_or_compile(fn, _args(), program="tiny")
    assert i2.source == "fresh"
    _, i3 = _cache(tmp_path).load_or_compile(fn, _args(), program="tiny")
    assert i3.source == "cache"


def test_unwritable_root_stays_functional(tmp_path):
    """A cache rooted somewhere that cannot be a directory (here: an
    existing FILE) still returns a working executable — persistence just
    silently degrades to per-process."""
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("occupied")
    reg = MetricsRegistry(namespace="test")
    c = CompileCache(root=blocker, registry=reg,
                     journal=EventJournal(registry=reg))
    fn = _tiny_jit()
    g, info = c.load_or_compile(fn, _args(), program="tiny")
    # the miss reason distinguishes a volume problem from a backend that
    # cannot serialize — operators chase very different fixes for each
    assert info.source == "fresh" and info.reason == "unwritable"
    np.testing.assert_array_equal(np.asarray(g(*_args())),
                                  np.asarray(fn(*_args())))
    assert c.entries() == []


# -- maintenance --------------------------------------------------------------

def test_prune_evicts_lru_first(tmp_path):
    c = _cache(tmp_path)
    fn = _tiny_jit()
    infos = []
    for n in (4, 8, 16):
        _, i = c.load_or_compile(fn, _args(n), program=f"tiny{n}")
        infos.append(i)
    # age the first two, then touch the first again → LRU order: 8, 4, 16
    now = time.time()
    os.utime(c.entry_dir(infos[0].fingerprint), (now - 100, now - 100))
    os.utime(c.entry_dir(infos[1].fingerprint), (now - 200, now - 200))
    sizes = {e["fingerprint"]: e["bytes"] for e in c.entries()}
    keep = sizes[infos[2].fingerprint] + sizes[infos[0].fingerprint]
    evicted = c.prune(max_bytes=keep)
    assert evicted == [infos[1].fingerprint]
    assert {e["fingerprint"] for e in c.entries()} == {
        infos[0].fingerprint, infos[2].fingerprint}
    assert c.prune(max_bytes=keep) == []  # already within bound


def test_verify_reports_damage(tmp_path):
    c = _cache(tmp_path)
    fn = _tiny_jit()
    _, a = c.load_or_compile(fn, _args(4), program="tiny")
    _, b = c.load_or_compile(fn, _args(8), program="tiny")
    assert c.verify() == []
    # three damage modes: missing file, truncation, fingerprint mismatch
    (c.entry_dir(a.fingerprint) / TREES).unlink()
    payload = c.entry_dir(b.fingerprint) / PAYLOAD
    payload.write_bytes(payload.read_bytes()[:16])
    third = c.root / ("0" * 32)
    shutil.copytree(c.entry_dir(b.fingerprint), third)
    problems = c.verify()
    probs = {(p["fingerprint"], p["problem"].split()[0]) for p in problems}
    assert (a.fingerprint, "missing") in probs
    assert (b.fingerprint, "payload") in probs
    assert any(fp == "0" * 32 and kind == "meta"
               for fp, kind in probs)


def test_seed_dir_adoption(tmp_path):
    """A published version's executables/ sidecar acts as a read-only seed
    root: a primary miss that hits the seed copies the entry in (so later
    boots hit locally) and never writes to the seed."""
    seed_cache = CompileCache(root=tmp_path / "sidecar",
                              registry=MetricsRegistry(namespace="test"),
                              journal=EventJournal())
    fn = _tiny_jit()
    _, info = seed_cache.load_or_compile(fn, _args(), program="tiny")

    local = CompileCache(root=tmp_path / "local",
                         seed_dirs=(tmp_path / "sidecar",),
                         registry=MetricsRegistry(namespace="test"),
                         journal=EventJournal())
    g, i2 = local.load_or_compile(fn, _args(), program="tiny")
    assert i2.source == "cache"
    assert (local.entry_dir(info.fingerprint) / PAYLOAD).is_file(), \
        "seed hit must be adopted into the primary root"
    np.testing.assert_array_equal(np.asarray(g(*_args())),
                                  np.asarray(fn(*_args())))


# -- the set-up timeline: stages and JAX's own compiles as spans ---------------

def _resolution_spans(n0):
    """(the `compile_resolve` recorded since ring position ``n0``, its
    stage children in the order they ran)."""
    from nerrf_tpu.tracing import DEFAULT_TRACER

    recs = DEFAULT_TRACER.records()[n0:]
    (resolve,) = [r for r in recs if r.name == "compile_resolve"]
    stages = sorted((r for r in recs if r.parent == resolve.id
                     and r.name.startswith("compile_resolve.")),
                    key=lambda r: r.t0)
    return resolve, stages


class _NoLower:
    """A jit function whose AOT path is broken: `lower` raises, calling it
    still works (the live fallback)."""

    def __init__(self):
        self._fn = _tiny_jit()

    def lower(self, *a, **k):
        raise RuntimeError("no AOT here")

    def __call__(self, *a):
        return self._fn(*a)


_FRESH = ["fingerprint", "read", "lower", "compile", "serialize", "persist"]


@pytest.mark.parametrize("case, want, source, reason", [
    ("miss", _FRESH, "fresh", "absent"),
    ("hit", ["fingerprint", "read", "deserialize"], "cache", None),
    ("adopted", ["fingerprint", "read", "deserialize"], "cache", None),
    ("corrupt_payload", ["fingerprint", "read", "deserialize"] + _FRESH[2:],
     "fresh", "absent"),
    ("unwritable", _FRESH, "fresh", "unwritable"),
    ("unserializable", _FRESH[:-1], "fresh", "unserializable"),
    ("lower_fails", ["fingerprint", "read", "lower"], "live", "lower/compile"),
    ("fingerprint_fails", ["fingerprint"], "live", "fingerprint"),
])
def test_resolution_stages_are_children_of_compile_resolve(
        tmp_path, monkeypatch, case, want, source, reason):
    """Every resolution is one `compile_resolve` whose children are the
    stages it went through, in order, each closed where a fail-open path
    left it; the miss is recorded with its cause as before."""
    from nerrf_tpu.compilecache import cache as cc
    from nerrf_tpu.tracing import DEFAULT_TRACER

    reg = MetricsRegistry(namespace="test")
    fn = _tiny_jit()
    kw = dict(registry=reg, journal=EventJournal(registry=reg))
    # what primes the root counts in a registry of its own
    if case in ("hit", "corrupt_payload"):
        _, first = _cache(tmp_path).load_or_compile(
            fn, _args(), program="tiny")
        if case == "corrupt_payload":
            (tmp_path / "aot" / first.fingerprint / PAYLOAD).write_bytes(
                b"garbage")
    if case == "adopted":
        _cache(tmp_path / "sidecar").load_or_compile(
            fn, _args(), program="tiny")
        kw["seed_dirs"] = (tmp_path / "sidecar" / "aot",)
    if case == "unwritable":
        (tmp_path / "aot").write_text("occupied")
    if case == "unserializable":
        from jax.experimental import serialize_executable as se

        def refuse(_compiled):
            raise ValueError("this backend does not serialize")
        monkeypatch.setattr(se, "serialize", refuse)
    if case == "lower_fails":
        fn = _NoLower()
    if case == "fingerprint_fails":
        def broken(*_a, **_k):
            raise TypeError("no avals")
        monkeypatch.setattr(cc, "aval_signature", broken)

    n0 = len(DEFAULT_TRACER.records())
    g, info = _cache(tmp_path, **kw).load_or_compile(fn, _args(),
                                                     program="tiny")
    np.testing.assert_array_equal(np.asarray(g(*_args())),
                                  np.asarray(_tiny_jit()(*_args())))
    resolve, stages = _resolution_spans(n0)
    assert [s.name.split(".", 1)[1] for s in stages] == want
    assert info.source == source
    assert (info.reason or "").startswith(reason or "")
    assert resolve.args["program"] == "tiny"
    assert resolve.args["source"] == source
    assert resolve.args["reason"] == info.reason
    # the miss is recorded as before, with its cause
    assert reg.value("compile_cache_hits_total",
                     labels={"program": "tiny"}) == (
        1 if source == "cache" else 0)
    assert reg.value("compile_cache_misses_total", labels={
        "program": "tiny", "reason": info.reason or "absent"}) == (
        0 if source == "cache" else 1)
    # inside their parent, one after the other
    lo, hi = resolve.t0, resolve.t0 + resolve.dur
    for a, b in zip(stages, stages[1:]):
        assert a.t0 + a.dur <= b.t0
    assert all(lo <= s.t0 and s.t0 + s.dur <= hi for s in stages)
    # `CompileInfo.seconds` keeps its meaning: the read's or the compile's
    by_name = {s.name.split(".", 1)[1]: s for s in stages}
    if source == "cache":
        assert info.seconds >= by_name["read"].dur + by_name["deserialize"].dur
    if source == "fresh":
        assert info.seconds >= by_name["lower"].dur + by_name["compile"].dur
        assert info.seconds < resolve.dur
    if "read" in by_name:
        read = by_name["read"].args
        on_disk = case in ("hit", "adopted", "corrupt_payload")
        assert (read["bytes"] > 0) == on_disk
        assert read.get("adopted", False) == (case == "adopted")
    if "persist" in by_name:
        assert by_name["persist"].args["bytes"] > 0


def test_jit_listener_is_installed_once_however_many_caches(tmp_path):
    from jax._src import monitoring as mon

    from nerrf_tpu.compilecache import cache as cc

    for k in range(3):
        _cache(tmp_path / str(k))
    cc.install_jit_listener()
    mine = [f for f in mon.get_event_duration_listeners()
            if f is cc._on_jit_duration]
    assert len(mine) == 1


def test_jit_compile_spans_name_the_function_and_loaded_calls_emit_none(
        tmp_path):
    """What JAX compiles on its own becomes a `jit_compile` span carrying
    the function's name and a count in ``jit_compiles_total{stage}``; an
    event under the floor is counted and not spanned; calling a loaded
    executable emits nothing at all (no per-step cost)."""
    import jax.monitoring as mon

    from nerrf_tpu.compilecache import cache as cc
    from nerrf_tpu.observability import DEFAULT_REGISTRY as reg
    from nerrf_tpu.tracing import DEFAULT_TRACER

    cache = _cache(tmp_path)          # its construction installs the listener
    jits = lambda: [r for r in DEFAULT_TRACER.records()       # noqa: E731
                    if r.name == "jit_compile"]
    count = lambda stage: reg.value(                          # noqa: E731
        "jit_compiles_total", labels={"stage": stage}) or 0.0

    n0, c0 = len(jits()), count("backend_compile")
    # an eager op on a shape nothing else uses: one implicit compile
    jnp.arctanh(np.full((3, 7, 11), 0.5, np.float32)).block_until_ready()
    backend = [r for r in jits()[n0:]
               if r.args["stage"] == "backend_compile"]
    (mine,) = [r for r in backend if "arctanh" in r.args["fun"]]
    assert mine.dur >= cc.JIT_SPAN_MIN_SECONDS and mine.parent is None
    assert count("backend_compile") == c0 + len(backend)

    # the floor: counted, not spanned; other events: neither
    n1, t0 = len(jits()), count("trace")
    event = "/jax/core/compile/jaxpr_trace_duration"
    mon.record_event_duration_secs(event, 1e-5, fun_name="tiny")
    mon.record_event_duration_secs(event, 0.5, fun_name="slow")
    mon.record_event_duration_secs("/jax/some/other_duration", 0.5)
    assert count("trace") == t0 + 2
    (slow,) = jits()[n1:]
    assert slow.args == {"stage": "trace", "fun": "slow"} and slow.dur == 0.5

    # inside a resolution the events are the open stage's children
    n2 = len(DEFAULT_TRACER.records())
    fn = jax.jit(lambda x: jnp.tanh(x) * 3.0)
    g, info = cache.load_or_compile(fn, _args(5), program="tiny5")
    assert info.source == "fresh"
    resolve, stages = _resolution_spans(n2)
    stage_ids = {s.id: s.name for s in stages}
    inside = [r for r in DEFAULT_TRACER.records()[n2:]
              if r.name == "jit_compile"]
    assert inside and all(r.parent in stage_ids for r in inside)
    assert {stage_ids[r.parent] for r in inside
            if r.args["stage"] == "backend_compile"} == {
                "compile_resolve.compile"}

    # calls of the loaded executable: no event, no span, no count
    n3 = len(jits())
    totals = [count(s) for s in cc.JIT_STAGES.values()]
    x = jax.device_put(np.arange(5, dtype=np.float32))
    for _ in range(5):
        x = g(x)
    x.block_until_ready()
    assert len(jits()) == n3
    assert [count(s) for s in cc.JIT_STAGES.values()] == totals


# -- StepCache ----------------------------------------------------------------

def test_seed_adoption_replaces_husk(tmp_path):
    """A crash mid-eviction can leave an invalid husk at the primary
    target; adoption must replace it (rename would fail ENOTEMPTY and —
    because the seed hit still succeeds — put() would never run to
    repair it, leaving every boot reading across the seed volume)."""
    seed_cache = CompileCache(root=tmp_path / "sidecar",
                              registry=MetricsRegistry(namespace="test"),
                              journal=EventJournal())
    fn = _tiny_jit()
    _, info = seed_cache.load_or_compile(fn, _args(), program="tiny")

    local_root = tmp_path / "local"
    husk = local_root / info.fingerprint
    husk.mkdir(parents=True)
    (husk / META).write_text("{}")  # meta only: invalid, but non-empty
    local = CompileCache(root=local_root,
                         seed_dirs=(tmp_path / "sidecar",),
                         registry=MetricsRegistry(namespace="test"),
                         journal=EventJournal())
    _, i2 = local.load_or_compile(fn, _args(), program="tiny")
    assert i2.source == "cache"
    assert (local.entry_dir(info.fingerprint) / PAYLOAD).is_file(), \
        "husk must be replaced by the adopted entry"


def test_compile_fresh_respects_operator_disabled_jax_cache(tmp_path):
    """An operator who disabled jax's compilation cache outright must not
    find it silently re-enabled after a CompileCache miss (the suspension
    machinery restores the PRIOR flag value, never a hardcoded True)."""
    import jax

    prev_dir = getattr(jax.config, "jax_compilation_cache_dir", None)
    prev_on = getattr(jax.config, "jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "xla"))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        c = _cache(tmp_path)
        _, info = c.load_or_compile(_tiny_jit(), _args(), program="tiny")
        assert info.source == "fresh"
        assert jax.config.jax_enable_compilation_cache is False, \
            "operator's disable must survive a cache miss"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_enable_compilation_cache", prev_on)


def test_stepcache_resolves_once_per_signature(tmp_path):
    c = _cache(tmp_path)
    sc = StepCache(c, _tiny_jit(), program="step")
    out4 = sc(*_args(4))
    out8 = sc(*_args(8))
    sc(*_args(4))  # same signature — no new resolution
    assert len(sc.infos) == 2
    assert all(i.source == "fresh" for i in sc.infos)
    np.testing.assert_array_equal(np.asarray(out4),
                                  np.arange(4, dtype=np.float32) * 2 + 1)
    np.testing.assert_array_equal(np.asarray(out8),
                                  np.arange(8, dtype=np.float32) * 2 + 1)

    sc2 = StepCache(_cache(tmp_path), _tiny_jit(), program="step")
    sc2(*_args(4)), sc2(*_args(8))
    assert [i.source for i in sc2.infos] == ["cache", "cache"]


def test_stepcache_tail_binding(tmp_path):
    """Trailing jit parameters (device-resident dataset/schedule arrays)
    bind at construction and ride the cache key."""
    c = _cache(tmp_path)
    fn = jax.jit(lambda x, table: x + table[0])
    table = np.full((3,), 10.0, np.float32)
    sc = StepCache(c, fn, program="step", tail=(table,))
    np.testing.assert_array_equal(np.asarray(sc(*_args(4))),
                                  np.arange(4, dtype=np.float32) + 10.0)
    assert len(sc.infos) == 1 and sc.infos[0].source == "fresh"


# -- the serve acceptance criteria -------------------------------------------

def _sim(seed=3, duration=45.0, attack=True):
    from nerrf_tpu.data.synth import SimConfig, simulate_trace

    return simulate_trace(SimConfig(duration_sec=duration, attack=attack,
                                    attack_start_sec=duration / 3,
                                    num_target_files=6, benign_rate_hz=6.0,
                                    seed=seed))


def _blocks(trace, size=200):
    ev = trace.events
    for i in range(0, len(ev), size):
        yield type(ev)(**{f.name: getattr(ev, f.name)[i:i + size]
                          for f in dataclasses.fields(ev)})


@pytest.fixture(scope="module")
def warm_serve(tmp_path_factory):
    """The real small model compiled ONCE into a module-shared cache root
    (every serve test after this boots from it)."""
    from nerrf_tpu.models import JointConfig, NerrfNet
    from nerrf_tpu.serve import (
        OnlineDetectionService,
        ServeConfig,
        init_untrained_params,
    )

    root = tmp_path_factory.mktemp("aot-serve")
    cfg = ServeConfig(buckets=(BUCKET,), batch_size=4,
                      window_sec=15.0, stride_sec=5.0)
    model = NerrfNet(JointConfig().small)
    params = init_untrained_params(model, cfg)
    reg = MetricsRegistry(namespace="test")
    jrn = EventJournal(registry=reg)
    svc = OnlineDetectionService(
        params, model, cfg=cfg, registry=reg, journal=jrn,
        compile_cache=CompileCache(root=root, registry=reg, journal=jrn))
    svc.start()
    svc.stop()
    assert set(svc.warmup_source.values()) == {"fresh"}
    return root, cfg, model, params


def _boot(root, cfg, model, params, executables_dir=None, seed_only=False):
    from nerrf_tpu.serve import OnlineDetectionService

    reg = MetricsRegistry(namespace="test")
    jrn = EventJournal(registry=reg)
    cache = CompileCache(root=root, registry=reg, journal=jrn)
    svc = OnlineDetectionService(params, model, cfg=cfg, registry=reg,
                                 journal=jrn, compile_cache=cache,
                                 executables_dir=executables_dir)
    return svc, reg, jrn


def test_second_boot_sources_cache_for_every_bucket(warm_serve):
    """The warm-boot acceptance criterion: with a populated cache the
    service reaches ready WITHOUT re-tracing any bucket program, and the
    warmup gauge is exported per bucket."""
    root, cfg, model, params = warm_serve
    svc, reg, jrn = _boot(root, cfg, model, params)
    svc.start()
    try:
        assert svc.ready()[0]
        assert set(svc.warmup_source.values()) == {"cache"}, \
            svc.warmup_source
        for tag, sec in svc.warmup_seconds.items():
            assert reg.value("serve_warmup_seconds",
                             labels={"bucket": tag}) == sec
        hits = reg.value("compile_cache_hits_total",
                         labels={"program": f"serve_eval[{_tag(cfg)}]"})
        assert hits == len(cfg.buckets)
    finally:
        svc.stop()


def _tag(cfg):
    from nerrf_tpu.serve.config import bucket_tag

    return bucket_tag(cfg.buckets[0])


def test_cached_executable_scoring_bit_parity(warm_serve):
    """Single-stream scoring THROUGH A DESERIALIZED EXECUTABLE is
    bit-identical to offline model_detect — the cache changes where the
    program comes from, never what it computes."""
    from nerrf_tpu.data.loaders import Trace
    from nerrf_tpu.pipeline import model_detect

    root, cfg, model, params = warm_serve
    svc, _, _ = _boot(root, cfg, model, params)
    svc.start()
    try:
        assert set(svc.warmup_source.values()) == {"cache"}
        tr = _sim(seed=11)
        svc.join("s0")
        for b in _blocks(tr):
            svc.feed("s0", b, tr.strings)
        det = svc.leave("s0", timeout=60.0)
    finally:
        svc.stop()
    offline = model_detect(
        Trace(events=tr.events, strings=tr.strings, ground_truth=None,
              labels=None, name="s0"),
        params, model, ds_cfg=cfg.dataset_config(BUCKET),
        auto_capacity=False, batch_size=cfg.batch_size)
    assert det.file_scores == offline.file_scores
    assert det.file_window_scores == offline.file_window_scores
    assert det.proc_scores == offline.proc_scores
    assert det.threshold == offline.threshold


def test_corrupt_cache_never_blocks_readiness(warm_serve):
    """Fail-open proven at the service level: corrupt every entry mid-
    fleet — the next boot compiles live, journals the misses, and
    readiness still flips."""
    root, cfg, model, params = warm_serve
    wreck = root.parent / "wrecked"
    shutil.copytree(root, wreck)
    for d in wreck.iterdir():
        if d.is_dir():
            (d / PAYLOAD).write_bytes(b"not an executable")
    svc, reg, jrn = _boot(wreck, cfg, model, params)
    svc.start()
    try:
        assert svc.ready()[0]
        assert set(svc.warmup_source.values()) == {"fresh"}
        assert reg.value("compile_cache_misses_total",
                         labels={"program": f"serve_eval[{_tag(cfg)}]",
                                 "reason": "absent"}) >= 1
        assert any(r.data.get("source") == "fresh"
                   for r in _compile_records(jrn))
    finally:
        svc.stop()


def test_export_publish_sidecar_and_seeded_boot(warm_serve, tmp_path):
    """Publish-time AOT: export the ladder's executables as a sidecar,
    publish it with the checkpoint, and boot a pod with an EMPTY local
    cache seeded from the sidecar — every bucket sources from cache."""
    from nerrf_tpu.registry.store import ModelRegistry
    from nerrf_tpu.train.checkpoint import save_checkpoint

    root, cfg, model, params = warm_serve
    exe_dir = tmp_path / "exported"
    manifest = export_executables(exe_dir, params, model, cfg)
    tag = _tag(cfg)
    assert manifest["programs"][tag]["fingerprint"]
    assert read_manifest(exe_dir)["env"]["jax"]

    ckpt = tmp_path / "ckpt"
    save_checkpoint(ckpt, params, model.cfg)
    reg = ModelRegistry(tmp_path / "registry")
    version = reg.publish("lin", ckpt, executables=exe_dir)
    sidecar = reg.executables_dir("lin", version)
    assert sidecar is not None
    assert reg.status("lin")["versions"][0]["executables"] is True
    # versions published without a sidecar read as absent, not broken
    v2 = reg.publish("lin", ckpt)
    assert reg.executables_dir("lin", v2) is None

    svc, _, _ = _boot(tmp_path / "empty-local", cfg, model, params,
                      executables_dir=sidecar)
    svc.start()
    try:
        assert set(svc.warmup_source.values()) == {"cache"}, \
            "sidecar seed must eliminate the boot compile sweep"
    finally:
        svc.stop()


def test_payload_self_contained_when_jax_cache_warm(tmp_path):
    """The poisoned-payload regression (caught live by the e2e
    pre-flight): with jax's own persistent compilation cache WARM for a
    program, a CompileCache entry serialized for it must still
    deserialize in a fresh process.  jax memoizes its is-the-cache-used
    verdict process-wide, so suspending the cache by clearing the dir
    config alone is a silent no-op — an executable loaded from jax's
    cache serializes into a payload whose symbols resolve nowhere else
    ("Symbols not found"), and every later boot re-compiles forever."""
    import subprocess
    import sys

    def warm(aot):
        env = dict(os.environ,
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"),
                   JAX_PLATFORMS="cpu",
                   # persist even sub-second CPU compiles so the shared
                   # jax cache is genuinely warm for step 2
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        r = subprocess.run(
            [sys.executable, "-m", "nerrf_tpu.cli", "cache", "warm",
             "--cache-dir", str(tmp_path / aot), "--buckets", "64x128x32"],
            env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr
        return json.loads(r.stdout)["source"]["64n/128e/32s"]

    assert warm("aot-a") == "fresh"      # jax cache cold: baseline
    # jax cache now warm, fresh AOT root: the compile MUST NOT come from
    # jax's cache (that payload would be poisoned)
    assert warm("aot-b") == "fresh"
    # ...proven by a fresh process deserializing what it wrote
    assert warm("aot-b") == "cache"


# -- doctor provenance --------------------------------------------------------

def test_doctor_surfaces_compile_provenance():
    """Slow-boot incidents are diagnosable offline: the doctor report has
    a compile-provenance section built from the journal's `compile`
    records (program, source, fingerprint, miss reason)."""
    from nerrf_tpu.flight.doctor import compile_provenance, format_report

    j = EventJournal(registry=MetricsRegistry(namespace="test"))
    j.record("compile", program="serve_eval[256n/512e/64s]",
             fingerprint="abc123", source="cache", seconds=0.41)
    j.record("compile", program="train_step", fingerprint="def456",
             source="fresh", seconds=130.2, reason="absent")
    j.record("readiness", ready=True)
    bundle = {"manifest": {"trigger": "test", "reason": "slow boot",
                           "created_unix": time.time()},
              "records": j.tail(), "events": [], "metrics": "",
              "missing": []}
    prov = compile_provenance(bundle["records"])
    assert [p["source"] for p in prov] == ["cache", "fresh"]
    assert prov[1]["reason"] == "absent"
    report = format_report(bundle)
    assert "compile provenance (2 resolutions" in report
    assert "abc123" in report and "def456" in report
    assert "absent" in report


# -- CLI ----------------------------------------------------------------------

def test_cache_cli_ls_prune_verify(tmp_path, capsys):
    from nerrf_tpu.cli import main

    root = tmp_path / "aot"
    c = CompileCache(root=root, registry=MetricsRegistry(namespace="test"),
                     journal=EventJournal())
    fn = _tiny_jit()
    _, info = c.load_or_compile(fn, _args(), program="tiny")

    assert main(["cache", "ls", "--cache-dir", str(root)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["entries"][0]["fingerprint"] == info.fingerprint
    assert out["total_bytes"] > 0

    assert main(["cache", "verify", "--cache-dir", str(root)]) == 0
    capsys.readouterr()

    assert main(["cache", "prune", "--cache-dir", str(root),
                 "--max-bytes", "0"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["evicted"] == [info.fingerprint] and out["kept"] == 0

    (root / "deadbeef").mkdir()
    assert main(["cache", "verify", "--cache-dir", str(root)]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["problems"]
