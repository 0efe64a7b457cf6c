"""The readers of the program's own spans (`chipbench/program_spans.py`):
which spans of the ring are the window's and which are set-up's, each
reader on a synthetic run, the aggregate's required work pinned to its
shapes, and a rehearsal that prints the span metrics in the traced run
alone."""

import copy
import json
import types

import pytest

from chipbench import program_spans as ps
from chipbench import rehearse, run
from chipbench.work import nerrfnet_aggregate

SPAN_METRICS = ("step_call_ms.train", "step_call_self_ms.train",
                "step_resolves_in_window.train")


class Ring:
    """A synthetic span ring: spans are appended in the order they end,
    as the program's tracer appends them."""

    def __init__(self):
        self.spans, self._ids = [], iter(range(1, 10_000))

    def add(self, name, t0, dur, parent=None, **args):
        span = types.SimpleNamespace(name=name, t0=t0, dur=dur, args=args,
                                     id=next(self._ids), parent=parent)
        self.spans.append(span)
        return span

    def step_call(self, call, t0, dur, execute, resolve=0.0):
        """One `train_step_call` with its children inside it."""
        span = self.add(ps.STEP_CALL, t0, dur, call=call)
        self.spans.pop()
        at = t0 + 0.1 * (dur - execute - resolve)
        if resolve:
            self.add(ps.RESOLVE, at, resolve, parent=span.id,
                     program="train_step")
        self.add("train_step_execute", at + resolve, execute, parent=span.id)
        self.spans.append(span)
        return span


def one_run(ring, t, steps, cold=0.0):
    """Set-up and a window of ``steps`` calls from time ``t`` on: 2 s of
    simulation, 3 s of lowering that overlaps it by 1 s, an upload, a
    resolution inside warm-up call 0, then 8 ms calls of which 6 ms are the
    executable's."""
    ring.add("corpus_simulate", t, 2.0, trace=0, events=10)
    ring.add("graph_lower", t + 1.0, 3.0)
    ring.add("dataset_upload", t + 5.0, 0.5, bytes=1 << 20)
    ring.add("train_setup", t + 6.0, 0.25)
    t += 7.0
    for k in range(ps.WARMUP_CALLS + steps):
        resolve = (1.5 + cold) if k == 0 else 0.0
        ring.step_call(k, t, 0.008 + resolve, 0.006, resolve)
        t += 0.010 + resolve
    return t


def test_window_is_the_last_steps_calls_and_setup_ends_where_it_starts():
    ring = Ring()
    one_run(ring, 100.0, steps=5)
    parts = ps.split_run(ring.spans, 5)
    assert [s.args["call"] for s in parts["window"]] == [3, 4, 5, 6, 7]
    names = [s.name for s in parts["setup"]]
    # the three warm-up calls and the resolution inside the first are
    # set-up's; nothing of the window is
    assert names.count(ps.STEP_CALL) == ps.WARMUP_CALLS
    assert names.count(ps.RESOLVE) == 1
    assert {"corpus_simulate", "graph_lower", "dataset_upload"} <= set(names)
    start = parts["window"][0].t0
    assert all(s.t0 + s.dur <= start for s in parts["setup"])
    assert all(s.t0 >= start for s in parts["after"])
    assert len(parts["after"]) == 2 * 5


@pytest.mark.parametrize("steps, why", [
    (6, "only two calls before the window: a warm-up call is missing"),
    (9, "more steps than the run has calls"),
    (0, "an empty window"),
])
def test_missing_calls_give_none_never_the_wrong_calls(steps, why):
    ring = Ring()
    one_run(ring, 0.0, steps=5)
    assert ps.split_run(ring.spans, steps) is None, why


def test_rings_that_are_not_a_whole_run_give_none():
    assert ps.split_run([], 4) is None
    ring = Ring()
    ring.add("graph_lower", 0.0, 1.0)          # the parent's ring: no call
    assert ps.split_run(ring.spans, 4) is None
    # a ring whose head was evicted: the calls do not start at 0
    ring = Ring()
    one_run(ring, 0.0, steps=5)
    tail = [s for s in ring.spans
            if s.name != ps.STEP_CALL or s.args["call"] > 0]
    assert ps.split_run(tail, 5) is None


def test_an_earlier_run_in_the_process_is_left_out():
    ring = Ring()
    end = one_run(ring, 0.0, steps=4, cold=60.0)
    ring.add("graph_lower", end + 0.5, 1.0)    # between the runs: the next's
    one_run(ring, end + 2.0, steps=6)
    parts = ps.split_run(ring.spans, 6)
    assert [s.args["call"] for s in parts["window"]] == list(range(3, 9))
    resolves = [s for s in parts["setup"] if s.name == ps.RESOLVE]
    assert [s.dur for s in resolves] == [1.5]  # not the first run's 61.5
    assert min(s.t0 for s in parts["setup"]) == pytest.approx(end + 0.5)


def test_each_reader_on_a_synthetic_run(monkeypatch):
    ring = Ring()
    one_run(ring, 50.0, steps=5)
    # a resolution in the window, inside the fifth window call
    late = ring.step_call(8, 60.0, 0.108, 0.006, resolve=0.1)
    monkeypatch.setattr(ps, "program_ring", lambda: ring.spans)
    run_ = {"counters": {"steps": 6}}
    assert late.args["call"] == 8
    want = {
        "step_call_ms.train": (5 * 8.0 + 108.0) / 6,
        "step_call_self_ms.train": 2.0,       # every call: 2 ms of its own
        "step_resolves_in_window.train": 1.0,
    }
    for name, value in want.items():
        assert run.read_metric(name, run_) == pytest.approx(value), name
    # fewer calls than the run says it made: silence, in every reader
    for name in want:
        assert run.read_metric(name, {"counters": {"steps": 7}}) is None
        assert run.read_metric(name, {"counters": {}}) is None


def test_self_time_clips_children_and_counts_overlaps_once():
    ring = Ring()
    call = ring.add(ps.STEP_CALL, 10.0, 1.0, call=0)
    ring.add("a", 10.1, 0.3, parent=call.id)
    ring.add("b", 10.2, 0.3, parent=call.id)     # overlaps a: [10.1, 10.5]
    ring.add("c", 10.9, 0.5, parent=call.id)     # sticks out: clipped to 0.1
    ring.add("d", 10.6, 0.1, parent=999)         # somebody else's child
    assert ps.self_seconds(call, ring.spans) == pytest.approx(0.5)


def _config(cell):
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return json.loads((run.ROOT / conf["file"]).read_text())


@pytest.mark.parametrize("cell, megabytes, megaflops", [
    ("train-1024", 38.08, 73.4), ("train-4096", 152.3, 293.6)])
def test_aggregate_work_is_pinned_to_shapes_and_twice_forward(
        cell, megabytes, megaflops):
    config = _config(cell)
    forward = nerrfnet_aggregate.forward_work(config)
    train = nerrfnet_aggregate.train_work(config)
    # no weights in the aggregate: backward is the gradient w.r.t. msg alone
    assert train == {k: 2 * v for k, v in forward.items()}
    assert train["bytes"] / 1e6 == pytest.approx(megabytes, rel=5e-4)
    assert train["flops"] / 1e6 == pytest.approx(megaflops, rel=5e-4)
    g, m = config["dataset"]["graph"], config["train"]["model"]["gnn"]
    assert forward["bytes"] == m["num_layers"] * (
        2 * g["max_nodes"] * m["hidden"] * 2 + 12 * g["max_edges"])


def test_aggregate_roofline_reads_the_sage_aggregate_group_alone():
    config = _config("train-1024")
    peaks = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    run_ = {"config": config, "peaks": peaks, "counters": {},
            "trace": {"windows_in_trace": 96,
                      "scope_s": {"sage_aggregate": 0.0446, "gnn_layer": 9.9}}}
    # bytes-bound: 38.08 MB at 819 GB/s is 46.5 us a window
    assert run.read_metric("sage_aggregate_roofline.train", run_) == \
        pytest.approx(100 * 96 * 46.5e-6 / 0.0446, rel=1e-3)
    run_["trace"]["scope_s"] = {"gnn_layer": 9.9}
    assert run.read_metric("sage_aggregate_roofline.train", run_) is None
    run_["trace"] = None
    assert run.read_metric("sage_aggregate_roofline.train", run_) is None


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    toy = copy.deepcopy(rehearse.TOY)
    toy["cache_root"] = str(tmp_path_factory.mktemp("aot"))
    return toy


@pytest.mark.parametrize("trace", (1, 0))
def test_rehearsal_prints_the_span_metrics_in_the_traced_run_alone(
        toy, capsys, trace):
    rc = run.main(["--workload", "train-1024", "--seed", "2600000177",
                   "--seconds", "0.5", "--trace", str(trace)], rehearsal=toy)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    if not trace:
        assert not set(SPAN_METRICS) & set(got)
        return
    assert set(SPAN_METRICS) <= set(got)
    assert got["step_resolves_in_window.train"] == 0
    assert 0 < got["step_call_self_ms.train"] < got["step_call_ms.train"]
    # the program's clock inside the benchmark's: the same calls
    assert got["step_call_ms.train"] <= got["host_dispatch_ms.train"]
    # set-up's spans, as the timeline reads them
    setup_s = res["extras"]["end_to_end"]["setup_s"]
    data, resolve = (got[f"setup_timeline_{part}_s.train"]
                     for part in ("data", "resolve"))
    assert 0 < data < setup_s and 0 < resolve < setup_s
    assert data + resolve < setup_s
