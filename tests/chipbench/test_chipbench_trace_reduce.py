"""The reduction from a profiler trace to metrics, on a small recorded trace
(`tests/chipbench/data/recorded_trace.json`: the raw form `read_xplane`
returns, cut from a `--trace 1` run of `train-1024` on a v5e, with the
scopes of its instructions), and the
rule that a per-layer metric is a file found by its name."""

import json
import pathlib

import pytest

from chipbench import run, trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data" / "recorded_trace.json"


def test_short_name_keeps_what_tells_operations_apart():
    text = ("%fusion.3523 = bf16[8,2,128,1024]{3,2,0,1:T(8,128)(2,1)S(1)} "
            "fusion(bf16[100,8,2,128,1024]{4,3,0,1,2} %gte.6317, s32[] %x), "
            "kind=kOutput, calls=%fused_computation.39.clone.clone")
    assert tr.short_name(text) == "fusion.3523 bf16[8,2,128,1024] kOutput"
    assert tr.short_name("dot.178") == "dot.178"


def test_union_and_gaps():
    spans = [(0, 10), (5, 20), (30, 40), (32, 35)]
    assert tr.union_length(spans) == 30
    assert tr.gaps(spans, 0, 50) == [(20, 30), (40, 50)]
    assert tr.gaps([], 0, 5) == [(0, 5)]
    assert tr.union_length([]) == 0


def test_hand_made_trace_busy_idle_groups_and_gap_attribution():
    raw = {"device_planes": True, "host_spans": [
        ["dispatch", 0.0, 100.0], ["wait_inflight", 100.0, 900.0]],
        "devices": [{"name": "/device:TPU:0", "modules": [
            ["jit_flat_step(1)", 0.0, 400.0], ["jit_flat_step(1)", 500.0, 400.0]],
            "ops": [
                ["fusion.1", 0.0, 100.0],
                ["while.2", 100.0, 300.0],
                ["fusion.7", 100.0, 150.0],
                ["fusion.8", 250.0, 150.0],
                ["custom-call.4", 500.0, 300.0],
                ["fusion.9", 800.0, 50.0],
                ["fusion.10", 850.0, 50.0],
            ]}]}
    red = tr.reduce(raw)
    assert red["window_s"] == pytest.approx(900e-9)
    assert red["busy_s"] == pytest.approx(800e-9)
    assert red["idle_share"] == pytest.approx(1 / 9)
    # the `while` that only wraps its body's ops is not counted twice
    assert "while.2" not in dict(red["top_ops"])
    assert red["leaf_op_s"] == pytest.approx(red["busy_s"])
    assert red["step_s"] == pytest.approx([400e-9, 400e-9])
    # the one gap, 400..500, lies in the host's wait_inflight span
    assert red["idle_by_span_s"] == [("wait_inflight", pytest.approx(100e-9))]
    assert red["top_ops"][0] == ("custom-call.4", pytest.approx(300e-9))


def test_recorded_trace_reduces_to_what_was_read_by_hand():
    from chipbench.work import nerrfnet as work

    rec = json.loads(DATA.read_text())
    red = tr.reduce(rec["raw"], work.SCOPE_GROUPS)
    want = rec["by_hand"]
    # device time by scope group, as read with another protobuf reader
    assert red["scope_s"] == pytest.approx(want["scope_s"], rel=1e-9)
    assert sum(red["scope_s"].values()) == pytest.approx(red["leaf_op_s"])
    assert max(red["scope_s"], key=red["scope_s"].get) == "lstm"
    assert red["devices"] == 1 and red["device_planes"] is True
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert len(red["step_s"]) == want["steps"]
    # the leaf operations' time is the device's busy time less the loop
    # overhead between a `while`'s body operations: nothing counted twice
    assert red["leaf_op_s"] <= red["busy_s"] * (1 + 1e-9)
    assert red["leaf_op_s"] == pytest.approx(red["busy_s"], rel=want["leaf_rel"])
    assert all(len(name) <= 96 for name, _ in red["top_ops"])
    assert len(red["top_ops"]) <= 10 and len(red["idle_by_span_s"]) <= 10


def test_a_metric_file_dropped_in_is_found_by_name(tmp_path, monkeypatch):
    d = tmp_path / "layer_metrics"
    d.mkdir()
    (d / "new_thing.serve.py").write_text(
        "def read(run):\n    return 2.0 * run['counters']['x']\n")
    (d / "silent.py").write_text("def read(run):\n    return None\n")
    monkeypatch.setattr(run, "HERE", tmp_path)
    assert run.read_metric("new_thing.serve", {"counters": {"x": 21}}) == 42.0
    assert run.read_metric("silent", {}) is None
    with pytest.raises(run.BenchError, match="has no reader"):
        run.read_metric("absent", {})


def test_every_per_layer_metric_of_the_benchmark_has_its_reader():
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    empty = {"trace": None, "counters": {}, "peaks": {}}
    for m in bench["per_layer"]:
        # a reader that finds nothing to read returns nothing, never 0
        assert run.read_metric(m["name"], empty) is None, m["name"]
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
