"""Tracing spine: spans, Chrome export, dual-write, train-loop attribution."""

import json
import threading
import time

import pytest

from nerrf_tpu import tracing
from nerrf_tpu.observability import MetricsRegistry


def test_span_records_and_dual_writes():
    reg = MetricsRegistry(namespace="t")
    tr = tracing.Tracer(registry=reg)
    with tr.span("train_step_wait", step=3) as sp:
        time.sleep(0.002)
        sp.args["reason"] = "logged"
    recs = tr.records()
    assert len(recs) == 1 and recs[0].name == "train_step_wait"
    assert recs[0].dur >= 0.002
    assert recs[0].args == {"step": 3, "reason": "logged"}
    # dual-write: the same span landed in the per-stage histogram, so
    # Prometheus and the trace agree from one instrumentation point
    assert reg.value(tracing.STAGE_HISTOGRAM,
                     labels={"stage": "train_step_wait"}, stat="count") == 1
    assert reg.value(tracing.STAGE_HISTOGRAM,
                     labels={"stage": "train_step_wait"}, stat="sum") >= 0.002
    text = reg.render()
    assert "# TYPE t_stage_latency_seconds histogram" in text
    assert 'stage="train_step_wait"' in text
    # recording has no switch: nothing can opt a loop into another mode
    assert not hasattr(tr, "enabled") and not hasattr(tracing, "set_enabled")


def test_process_start_against_a_subprocess_own_clock(repo_root):
    """`Tracer.process_start` is the kernel's record of the process's start
    on the tracer's clock: negative, before the child's first statement,
    after the moment the parent spawned it (10 ms resolution), exported in
    the Chrome trace; jax stays unimported by the package's import."""
    import subprocess
    import sys

    code = (
        "import time; first = time.time()\n"
        "import sys, json\n"
        "import nerrf_tpu\n"
        "from nerrf_tpu.tracing import DEFAULT_TRACER as t\n"
        "print(json.dumps({'first': first, 'start': t.process_start,\n"
        "    'epoch': t._t0_epoch, 'jax': 'jax' in sys.modules,\n"
        "    'other': t.chrome_trace()['otherData']}))\n")
    spawned = time.time()
    out = subprocess.run([sys.executable, "-c", code], cwd=repo_root,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["jax"] is False
    assert got["start"] < 0
    started = got["epoch"] + got["start"]        # on the wall clock
    assert spawned - 0.02 <= started <= got["first"] + 0.011
    assert got["other"]["process_start_sec"] == got["start"]
    assert got["other"]["epoch_anchor_unix_sec"] == got["epoch"]


def test_process_start_is_none_where_the_platform_does_not_say(monkeypatch):
    def no_proc(*_a, **_k):
        raise FileNotFoundError("/proc/self/stat")

    monkeypatch.setattr(tracing, "open", no_proc, raising=False)
    tr = tracing.Tracer(registry=MetricsRegistry())
    assert tr.process_start is None                 # never a guess
    assert tr.chrome_trace()["otherData"]["process_start_sec"] is None


def test_record_appends_an_ended_span_with_parent_id_and_dual_write():
    """`record` is `span` for an event that is already over: it ended now,
    lasted ``dur``, its parent is the innermost span open on the thread."""
    reg = MetricsRegistry(namespace="t")
    tr = tracing.Tracer(registry=reg)
    with tr.span("compile_resolve") as outer:
        with tr.span("compile_resolve.lower") as stage:
            time.sleep(0.003)
            before = time.perf_counter() - tr._t0_perf
            got = tr.record("jit_compile", 0.002, stage="trace", fun="f")
            after = time.perf_counter() - tr._t0_perf
    top = tr.record("jit_compile", 0.5, device=True, stage="lower", fun="g")
    assert got.parent == stage.id and stage.parent == outer.id
    assert top.parent is None
    assert len({outer.id, stage.id, got.id, top.id}) == 4
    assert got.dur == 0.002 and before <= got.t0 + got.dur <= after
    assert stage.t0 <= got.t0 and got.t0 + got.dur <= stage.t0 + stage.dur
    assert got.args == {"stage": "trace", "fun": "f"}
    # recorded in the order things ended, like every span
    assert [r.name for r in tr.records()] == [
        "jit_compile", "compile_resolve.lower", "compile_resolve",
        "jit_compile"]
    assert reg.value(tracing.STAGE_HISTOGRAM, labels={"stage": "jit_compile"},
                     stat="count") == 2
    assert reg.value(tracing.STAGE_HISTOGRAM, labels={"stage": "jit_compile"},
                     stat="sum") == pytest.approx(0.502)
    events = [e for e in tr.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert {e["id"]: e["parent"] for e in events}[got.id] == stage.id
    # a negative duration (a clock that stepped) is no span of negative length
    assert tr.record("jit_compile", -1.0).dur == 0.0


def test_the_naming_scheme_lists_every_span_the_package_opens(repo_root):
    """`tracing.py`'s docstring is the scheme of record: a span opened
    anywhere in the package (``span("...")``, a `compile_resolve` stage,
    a ``record("...")`` of the tracer) has its line there."""
    import re

    scheme = tracing.__doc__
    pkg = repo_root / "nerrf_tpu"
    opened = {"jit_compile", "module_import"}          # the `record`ed ones
    for path in pkg.rglob("*.py"):
        text = path.read_text()
        opened |= set(re.findall(r'span\(\s*"([a-z_]+)"', text))
        opened |= {f"compile_resolve.{stage}" for stage in
                   re.findall(r'\b_stage\("([a-z]+)"', text)}
    assert {"train_step_call", "compile_resolve.deserialize", "trace_lower",
            "step_build", "serve_admit"} <= opened
    missing = sorted(n for n in opened if not re.search(
        rf"(?<![a-z_.]){re.escape(n)}(?![a-z_.])", scheme))
    assert not missing, missing


def test_spans_carry_id_and_parent_per_thread():
    """``parent`` is the innermost span open on the SAME thread: a span on
    another thread started while this thread's span is open is a root."""
    tr = tracing.Tracer(registry=MetricsRegistry())
    with tr.span("outer") as outer:
        with tr.span("mid") as mid:
            with tr.span("leaf") as leaf:
                pass
        with tr.span("sibling") as sibling:
            def other():
                with tr.span("other_thread"):
                    with tr.span("other_child"):
                        pass
            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
    with tr.span("after") as after:
        pass
    by_name = {r.name: r for r in tr.records()}
    assert len(by_name) == 7
    assert len({r.id for r in by_name.values()}) == 7
    assert outer.parent is None and after.parent is None
    assert mid.parent == outer.id and leaf.parent == mid.id
    assert sibling.parent == outer.id
    assert by_name["other_thread"].parent is None
    assert by_name["other_child"].parent == by_name["other_thread"].id
    # a body that raises still closes its span: the next one is no child
    with pytest.raises(RuntimeError):
        with tr.span("fails"):
            raise RuntimeError("boom")
    with tr.span("next") as nxt:
        pass
    assert nxt.parent is None


def test_self_time_is_duration_less_what_children_cover():
    # parent [0, 100]; children [10, 30] and [20, 50] overlap (40 covered),
    # a gap, then [80, 120] sticks out and is clipped to 20; the grandchild
    # is its parent's business alone
    events = [
        {"name": "p", "ph": "X", "ts": 0.0, "dur": 100.0, "id": 1, "parent": None},
        {"name": "c", "ph": "X", "ts": 10.0, "dur": 20.0, "id": 2, "parent": 1},
        {"name": "c", "ph": "X", "ts": 20.0, "dur": 30.0, "id": 3, "parent": 1},
        {"name": "g", "ph": "X", "ts": 22.0, "dur": 5.0, "id": 4, "parent": 3},
        {"name": "c", "ph": "X", "ts": 80.0, "dur": 40.0, "id": 5, "parent": 1},
        {"name": "old", "ph": "X", "ts": 200.0, "dur": 7.0},  # pre-id file
    ]
    assert tracing.self_time(events) == pytest.approx(
        [40.0, 20.0, 25.0, 5.0, 40.0, 7.0])
    summary = tracing.stage_summary(events)
    assert summary["p"]["self_ms"] == pytest.approx(0.040)
    assert summary["c"]["self_ms"] == pytest.approx(0.085)
    assert summary["c"]["total_ms"] == pytest.approx(0.090)
    assert "self_ms" in tracing.format_stage_table(events)


def test_chrome_trace_export_round_trips(tmp_path):
    tr = tracing.Tracer(registry=MetricsRegistry())
    with tr.span("graph_lower", events=10):
        with tr.span("inner"):
            pass
    path = tr.write(tmp_path / "trace.json")
    data = json.loads((tmp_path / "trace.json").read_text())
    xs = [e for e in data["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in xs} == {"graph_lower", "inner"}
    assert all("ts" in e and "dur" in e and "tid" in e for e in xs)
    # id and parent survive the file: self time can be read offline
    outer, inner = (next(e for e in xs if e["name"] == n)
                    for n in ("graph_lower", "inner"))
    assert outer["parent"] is None and inner["parent"] == outer["id"]
    # thread metadata present so Perfetto names the rows
    assert any(e.get("name") == "thread_name" for e in data["traceEvents"])

    events = tracing.load_chrome_trace(path)
    summary = tracing.stage_summary(events)
    assert summary["graph_lower"]["count"] == 1
    assert summary["graph_lower"]["self_ms"] == pytest.approx(
        (outer["dur"] - inner["dur"]) / 1e3, abs=1e-3)
    table = tracing.format_stage_table(events)
    assert "graph_lower" in table and "%wall" in table


def test_tracer_thread_safety():
    reg = MetricsRegistry()
    tr = tracing.Tracer(registry=reg)

    def worker(i):
        for _ in range(200):
            with tr.span(f"stage_{i}"):
                pass

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.records()) == 800
    for i in range(4):
        assert reg.value(tracing.STAGE_HISTOGRAM,
                         labels={"stage": f"stage_{i}"}, stat="count") == 200


def test_coverage_is_an_interval_union():
    events = [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 50.0},
        {"name": "b", "ph": "X", "ts": 25.0, "dur": 50.0},  # overlaps a
        {"name": "c", "ph": "X", "ts": 90.0, "dur": 10.0},
    ]
    assert tracing.wall_clock_us(events) == 100.0
    # union [0,75] ∪ [90,100] = 85 of 100 — overlap counted once
    assert tracing.coverage(events) == pytest.approx(0.85)
    assert tracing.coverage([]) == 0.0


def test_ring_buffer_is_bounded():
    tr = tracing.Tracer(capacity=16, registry=MetricsRegistry())
    for i in range(64):
        with tr.span("s", i=i):
            pass
    recs = tr.records()
    assert len(recs) == 16
    assert recs[-1].args["i"] == 63  # newest kept


def test_ring_wraparound_keeps_exact_tail_in_order():
    """Tail-after-wrap semantics the flight recorder depends on: after the
    ring wraps, records()/chrome_trace() hold EXACTLY the newest
    ``capacity`` spans, in recording order, with timestamps intact."""
    reg = MetricsRegistry()
    tr = tracing.Tracer(capacity=8, registry=reg)
    for i in range(27):
        with tr.span("s", i=i):
            pass
    recs = tr.records()
    assert [r.args["i"] for r in recs] == list(range(19, 27))
    # timestamps stay monotone across the wrap (no epoch reset)
    t0s = [r.t0 for r in recs]
    assert t0s == sorted(t0s)
    xs = [e for e in tr.chrome_trace()["traceEvents"] if e.get("ph") == "X"]
    assert [e["args"]["i"] for e in xs] == list(range(19, 27))
    assert tracing.stage_summary(xs)["s"]["count"] == 8
    # the dual-written histogram is CUMULATIVE (it never wraps): the span
    # count diverges from the ring length by design, all 27 recorded
    assert reg.value(tracing.STAGE_HISTOGRAM, labels={"stage": "s"},
                     stat="count") == 27


def test_wrapped_export_extent_starts_at_the_tail():
    """After a wrap the exported trace's extent must begin at the OLDEST
    *kept* span — evicted spans must not stretch wall_clock_us or dilute
    coverage (the doctor's attribution tables read the export verbatim)."""
    tr = tracing.Tracer(capacity=4, registry=MetricsRegistry())
    for i in range(12):
        with tr.span("s", i=i):
            time.sleep(0.001)
    recs = tr.records()
    xs = [e for e in tr.chrome_trace()["traceEvents"] if e.get("ph") == "X"]
    lo = min(e["ts"] for e in xs)
    assert lo == pytest.approx(recs[0].t0 * 1e6, rel=1e-6)
    assert lo > 0  # strictly after tracer epoch: the head was evicted
    assert tracing.wall_clock_us(xs) < 12 * 50_000  # tail extent, not 12 spans
    # sequential non-overlapping spans: the union over the tail's own
    # extent is dominated by the spans themselves
    assert tracing.coverage(xs) > 0.5


def test_coverage_clamps_spans_to_the_requested_interval():
    """coverage(lo, hi) on a wrapped-style buffer: spans straddling or
    outside [lo, hi] contribute only their clamped overlap — the exact
    semantics the recorder's tail-window attribution relies on."""
    events = [
        {"name": "evicted", "ph": "X", "ts": 0.0, "dur": 40.0},
        {"name": "kept", "ph": "X", "ts": 30.0, "dur": 30.0},   # straddles lo
        {"name": "kept", "ph": "X", "ts": 70.0, "dur": 20.0},
        {"name": "kept", "ph": "X", "ts": 95.0, "dur": 20.0},   # straddles hi
    ]
    # window [50, 100]: [50,60] ∪ [70,90] ∪ [95,100] = 35 of 50
    assert tracing.coverage(events, lo_us=50.0, hi_us=100.0) \
        == pytest.approx(0.7)
    # a window entirely past every span covers nothing; degenerate → 0
    assert tracing.coverage(events, lo_us=200.0, hi_us=300.0) == 0.0
    assert tracing.coverage(events, lo_us=100.0, hi_us=100.0) == 0.0
    # explicit lo only: hi defaults to the spans' own max end (115), so
    # the window is [90, 115] and only the last span's [95, 115] counts
    assert tracing.coverage(events, lo_us=90.0) \
        == pytest.approx(20.0 / 25.0)


def test_train_loop_emits_covering_trace(tmp_path, monkeypatch):
    """Acceptance: a 20-step synthetic-corpus run emits a Chrome trace whose
    spans cover ≥95% of the run's wall-clock with no switch thrown and no
    sync added: one `train_step_call` per step, `train_step_wait` around
    the waits the loop has anyway, and both attribution gauges."""
    from nerrf_tpu.data import make_corpus
    from nerrf_tpu.graph import GraphConfig
    from nerrf_tpu.models import JointConfig
    from nerrf_tpu.observability import DEFAULT_REGISTRY
    from nerrf_tpu.tracing import DEFAULT_TRACER
    from nerrf_tpu.train import TrainConfig, build_dataset
    from nerrf_tpu.train import loop
    from nerrf_tpu.train.data import DatasetConfig

    DEFAULT_TRACER.clear()
    corpus = make_corpus(2, attack_fraction=0.5, base_seed=5,
                         duration_sec=60.0, num_target_files=4,
                         benign_rate_hz=10.0)
    ds = build_dataset(corpus, DatasetConfig(
        graph=GraphConfig(window_sec=45.0, stride_sec=25.0,
                          max_nodes=64, max_edges=128),
        seq_len=16, max_seqs=16))
    simulated = [r for r in DEFAULT_TRACER.records()
                 if r.name == "corpus_simulate"]
    assert [r.args["trace"] for r in simulated] == [0, 1]
    assert all(r.args["events"] > 0 for r in simulated)
    DEFAULT_TRACER.clear()
    syncs = []
    real_sync = loop.sync_result
    monkeypatch.setattr(loop, "sync_result",
                        lambda x: (syncs.append(1), real_sync(x))[1])
    res = loop.train_nerrfnet(ds, None, TrainConfig(
        model=JointConfig().small, batch_size=4, num_steps=20,
        eval_every=10, warmup_steps=2))
    assert res.steps_per_sec > 0
    # the step-0 barrier and the final one: the loop syncs where it did
    assert len(syncs) == 2

    path = DEFAULT_TRACER.write(tmp_path / "train_trace.json")
    events = tracing.load_chrome_trace(path)
    names = {e["name"] for e in events}
    assert {"train_setup", "train_loop", "train_step_call", "train_step_wait",
            "dataset_upload", "eval"} <= names
    assert "device_step" not in names
    calls = [e for e in events if e["name"] == "train_step_call"]
    assert [e["args"]["call"] for e in calls] == list(range(20))
    loop_ev = next(e for e in events if e["name"] == "train_loop")
    assert all(e["parent"] == loop_ev["id"] for e in calls)
    waits = [e for e in events if e["name"] == "train_step_wait"]
    # step-0 barrier, the logged steps 0 / 10 / 19, the end of the loop
    assert [e["args"]["step"] for e in waits] == [0, 0, 10, 19, 20]
    assert all(e["parent"] == loop_ev["id"] for e in waits)
    upload = next(e for e in events if e["name"] == "dataset_upload")
    assert upload["args"]["bytes"] == sum(
        v.nbytes for v in ds.arrays.values())
    assert tracing.coverage(events) >= 0.95, tracing.format_stage_table(events)
    # non-vacuous attribution: the per-step LEAF spans alone must cover the
    # train_loop interval — the enclosing wrapper spans cannot satisfy this,
    # so silently dropping the per-step instrumentation fails here
    leaves = calls + waits
    leaf_cov = tracing.coverage(
        leaves, lo_us=loop_ev["ts"], hi_us=loop_ev["ts"] + loop_ev["dur"])
    assert leaf_cov >= 0.9, tracing.format_stage_table(events)

    text = DEFAULT_REGISTRY.render()
    for stage in ("train_step_call", "train_step_wait", "eval", "train_loop",
                  "graph_lower", "corpus_simulate"):
        assert f'stage="{stage}"' in text, stage
    assert 0.0 < DEFAULT_REGISTRY.value("train_host_blocked_fraction") <= 1.0
    assert DEFAULT_REGISTRY.value("train_data_wait_fraction") == 0.0
    assert 'nerrf_train_padding_waste_fraction{bucket="64n/128e",kind="node"}' \
        in text


def test_train_stream_emits_covering_trace(tmp_path):
    """The stream loop's twin of `test_train_loop_emits_covering_trace`: a
    toy `train_stream` run's spans cover >= 95 % of its wall clock, with
    `train_setup` around the state and the step functions, `step_build`
    and its `dataset_upload`, `train_loop` around the calls, and
    `train_step_wait` around the floats the loop does anyway."""
    import numpy as np

    import jax.numpy as jnp
    from nerrf_tpu.data.stream import STREAM_FEATURE_DIM
    from nerrf_tpu.models.stream import StreamConfig
    from nerrf_tpu.observability import DEFAULT_REGISTRY
    from nerrf_tpu.tracing import DEFAULT_TRACER
    from nerrf_tpu.train.loop import TrainConfig
    from nerrf_tpu.train.stream import train_stream

    r = np.random.default_rng(0)
    arrays = {"feat": r.normal(size=(8, 32, STREAM_FEATURE_DIM)
                               ).astype(np.float32),
              "mask": np.ones((8, 32), bool),
              "label": (r.random((8, 32)) < 0.3).astype(np.float32)}
    scfg = StreamConfig(dim=32, num_layers=2, mlp_mult=2, dropout=0.0,
                        dtype=jnp.float32)
    DEFAULT_TRACER.clear()
    t0 = time.perf_counter()
    res = train_stream(arrays, scfg, TrainConfig(
        batch_size=4, num_steps=20, eval_every=10, warmup_steps=2),
        log=lambda _: None)
    wall_us = (time.perf_counter() - t0) * 1e6
    assert res.steps_per_sec > 0 and len(res.history) == 3

    path = DEFAULT_TRACER.write(tmp_path / "stream_trace.json")
    events = tracing.load_chrome_trace(path)
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert {"train_setup", "step_build", "dataset_upload", "train_loop",
            "train_step_call", "train_step_wait"} <= set(by_name)
    setups = by_name["train_setup"]
    assert [e.get("args", {}).get("phase") for e in setups] == [
        None, "step_fns"]
    (build,), (upload,) = by_name["step_build"], by_name["dataset_upload"]
    assert build["parent"] == setups[1]["id"]
    assert upload["parent"] == build["id"]
    assert upload["args"]["bytes"] == sum(v.nbytes for v in arrays.values())
    (loop_ev,) = by_name["train_loop"]
    calls, waits = by_name["train_step_call"], by_name["train_step_wait"]
    assert [e["args"]["call"] for e in calls] == list(range(20))
    # step 0, every eval_every, the last: the floats the loop already did
    assert [e["args"]["step"] for e in waits] == [0, 9, 19]
    assert all(e["parent"] == loop_ev["id"] for e in calls + waits)
    # no span was opened inside a step call but its own children
    inside = [e for e in events if e["parent"] in {c["id"] for c in calls}]
    assert {e["name"] for e in inside} <= {"train_step_execute",
                                           "compile_resolve", "jit_compile"}
    assert tracing.coverage(events) >= 0.95, tracing.format_stage_table(events)
    # ... and of the call's own wall clock, not only of the spans' extent
    assert tracing.wall_clock_us(events) >= 0.95 * wall_us
    leaf_cov = tracing.coverage(
        calls + waits, lo_us=loop_ev["ts"],
        hi_us=loop_ev["ts"] + loop_ev["dur"])
    assert leaf_cov >= 0.9, tracing.format_stage_table(events)
    assert 0.0 <= DEFAULT_REGISTRY.value("train_host_blocked_fraction") <= 1.0


def _toy_cached_step(tmp_path):
    """A `CachedTrainStep` around a one-line flat program: the real step
    classes without the model's compile."""
    import jax
    import jax.numpy as jnp
    import optax
    from flax.training import train_state

    from nerrf_tpu.compilecache import CompileCache
    from nerrf_tpu.train.loop import CachedTrainStep

    @jax.jit
    def flat(params, opt_state, step_no, x):
        params = jax.tree_util.tree_map(lambda p: p + x.sum(), params)
        return (params, opt_state, step_no + 1), x.sum(), {}, x

    state = train_state.TrainState.create(
        apply_fn=None, params={"w": jnp.ones((4,))}, tx=optax.sgd(0.1))
    step = CachedTrainStep(CompileCache(root=tmp_path / "aot"), flat,
                           program="toy_step")
    return state, step, jnp.ones((3,))


def test_cached_step_spans_nest_and_count_calls(tmp_path):
    """`train_step_call` holds `compile_resolve` (first call only) and
    `train_step_execute`; its self time is what is left."""
    state, step, x = _toy_cached_step(tmp_path)
    tr = tracing.DEFAULT_TRACER
    n0 = len(tr.records())
    for _ in range(3):
        state, _loss, _aux, x = step(state, x)
    assert int(state.step) == 3
    recs = tr.records()[n0:]
    calls = [r for r in recs if r.name == "train_step_call"]
    assert [r.args["call"] for r in calls] == [0, 1, 2]
    executes = [r for r in recs if r.name == "train_step_execute"]
    assert [r.parent for r in executes] == [r.id for r in calls]
    (resolve,) = [r for r in recs if r.name == "compile_resolve"]
    assert resolve.parent == calls[0].id
    assert resolve.args == {"program": "toy_step", "source": "fresh",
                            "reason": "absent"}
    assert resolve.dur >= step.infos[0].seconds
    for call, ex in zip(calls, executes):
        assert call.t0 <= ex.t0 and ex.t0 + ex.dur <= call.t0 + call.dur


def test_execute_annotation_lies_inside_its_call_on_the_host_plane(tmp_path):
    """Inside a `jax.profiler` session the two step spans are on the
    ``/host:CPU`` plane, on the clock the device events share, each
    `train_step_execute` inside its `train_step_call`, the call's number
    beside it as a stat."""
    import glob

    import jax
    from jax.profiler import ProfileData

    state, step, x = _toy_cached_step(tmp_path)
    state, _loss, _aux, x = step(state, x)        # resolved outside
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=options)
    try:
        for _ in range(4):
            state, _loss, _aux, x = step(state, x)
        jax.block_until_ready(state.params)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = {"train_step_call": [], "train_step_execute": []}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in found:
                    found[e.name].append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    calls = sorted(found["train_step_call"])
    executes = sorted(found["train_step_execute"])
    assert len(calls) == len(executes) == 4
    assert [c[2]["call"] for c in calls] == [1, 2, 3, 4]
    for (c0, c1, _), (e0, e1, _) in zip(calls, executes):
        assert c0 <= e0 and e1 <= c1


def test_cli_trace_subcommand(tmp_path, capsys):
    from nerrf_tpu.cli import main

    tr = tracing.Tracer(registry=MetricsRegistry())
    with tr.span("ingest_decode", events=64):
        time.sleep(0.001)
    path = tr.write(tmp_path / "t.json")
    assert main(["trace", "--file", str(path)]) == 0
    out = capsys.readouterr().out
    assert "ingest_decode" in out and "coverage" in out
    # missing / corrupt files fail politely, not with a traceback
    assert main(["trace", "--file", str(tmp_path / "absent.json")]) == 2
    (tmp_path / "empty.json").write_text('{"traceEvents": []}')
    assert main(["trace", "--file", str(tmp_path / "empty.json")]) == 1
    # well-formed JSON that is not a trace: no spans, not a traceback
    (tmp_path / "scalar.json").write_text("3")
    assert main(["trace", "--file", str(tmp_path / "scalar.json")]) == 1
    (tmp_path / "strings.json").write_text('["a", "b"]')
    assert main(["trace", "--file", str(tmp_path / "strings.json")]) == 1
    (tmp_path / "bin.trace").write_bytes(bytes(range(256)))  # not UTF-8
    assert main(["trace", "--file", str(tmp_path / "bin.trace")]) == 2
