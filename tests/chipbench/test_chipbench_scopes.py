"""From the compiled programs a trace keeps to the `jax.named_scope` of each
device operation (`chipbench.hlo_scopes`), the grouping of device time by
scope, and the kernels' shares of their rooflines."""

import json
import pathlib

import pytest

from chipbench import hlo_scopes, roofline, run, trace_reduce as tr
from chipbench.work import nerrfnet as work

ROOT = pathlib.Path(__file__).resolve().parents[2]


# a protobuf writer as small as the reader it checks
def varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def instruction(name, uid, op_name="", operands=(), calls=()):
    msg = field(1, name) + field(35, uid)
    if op_name:
        msg += field(7, field(2, op_name))
    if operands:                       # packed, as proto3 writes them
        msg += field(36, b"".join(varint(o) for o in operands))
    for c in calls:                    # one by one, as proto2 would
        msg += field(38, c)
    return field(2, msg)


def toy_hlo_proto():
    fused = field(1, "fused_computation.7") + field(5, 11) + instruction(
        "convolution.3", 1,
        "jit(step)/jvp(vmap(Net))/lstm/lstm_layer_0/dot_general") + instruction(
        "convert.4", 2)
    entry = field(1, "main") + field(5, 12) + b"".join([
        instruction("named.2", 20, "jit(step)/jvp(vmap(Net))/gnn/gnn_layer_3/"
                                   "sage_aggregate/pallas_call"),
        instruction("fusion.7", 21, calls=(11,)),
        instruction("broadcast.3.clone", 22),
        instruction("tuple.5", 23, operands=(22, 21)),
        instruction("while.6", 24, "jit(step)/jvp(vmap(Net))/lstm/"
                                   "lstm_layer_1/lstm_scan/while",
                    operands=(23,)),
        instruction("copy.9", 25, operands=(20,)),
        instruction("lonely.4", 26)])
    return field(1, field(1, "jit_step") + field(3, fused) + field(3, entry))


def toy_xplane(path):
    def plane(name, body=b""):
        return field(1, field(2, name) + body)

    stat_meta = field(5, field(1, 9) + field(2, field(1, 9)
                                             + field(2, "Hlo Proto")))
    event_meta = field(4, field(1, 3) + field(2, (
        field(1, 3) + field(2, "jit_step(77)")
        + field(5, field(1, 9) + field(6, toy_hlo_proto())))))
    path.write_bytes(plane("/device:TPU:0")
                     + plane("/host:metadata", stat_meta + event_meta)
                     + plane("/host:CPU"))
    return str(path)


def test_scopes_come_from_own_name_then_callees_then_neighbours(tmp_path):
    scopes = hlo_scopes.scopes_of_trace(toy_xplane(tmp_path / "t.xplane.pb"))
    assert list(scopes) == ["jit_step(77)"]
    table = scopes["jit_step(77)"]
    lstm0 = "jit(step)/jvp(vmap(Net))/lstm/lstm_layer_0/dot_general"
    assert table["convolution.3"] == [lstm0]
    # a fusion the compiler left unnamed: what it calls is named
    assert table["fusion.7"] == [lstm0]
    # the buffer a scan fills: two users away, the loop has a name
    assert table["broadcast.3.clone"] == [
        "jit(step)/jvp(vmap(Net))/lstm/lstm_layer_1/lstm_scan/while"]
    # no user at all: the operand's name
    assert table["copy.9"] == [
        "jit(step)/jvp(vmap(Net))/gnn/gnn_layer_3/sage_aggregate/pallas_call"]
    assert "lonely.4" not in table
    # a trace without a metadata plane gives nothing, and says so by {}
    bare = tmp_path / "bare.xplane.pb"
    bare.write_bytes(field(1, field(2, "/device:TPU:0")))
    assert hlo_scopes.scopes_of_trace(str(bare)) == {}


def test_group_of_votes_in_order():
    groups = work.SCOPE_GROUPS
    assert tr.group_of(["a/gnn/gnn_layer_3/sage_aggregate/pallas_call"],
                       groups) == "sage_aggregate"     # told apart first
    assert tr.group_of(["a/gnn/gnn_layer_3/block_3/w_msg/dot_general"],
                       groups) == "gnn_layer"
    assert tr.group_of(["a/lstm/lstm_layer_0/x", "a/lstm/lstm_layer_0/y",
                        "a/gnn/gnn_heads/z"], groups) == "lstm"
    assert tr.group_of(["jit(step)/adamw/mul"], groups) == "other"
    assert tr.group_of([], groups) == "unresolved"
    assert tr.instruction_of("%fusion.12 = bf16[8]{0} fusion(%p), kind=kLoop"
                             ) == "fusion.12"


def test_device_time_by_scope_group_adds_up_to_the_leaf_operations():
    step = "jit_step(77)"
    raw = {"device_planes": True, "host_spans": [],
           "op_scopes": {
               step: {"fusion.1": ["x/lstm/lstm_layer_0/dot_general"],
                      "while.2": ["x/lstm/lstm_layer_0/lstm_scan/while"],
                      "fusion.7": ["x/lstm/lstm_layer_0/lstm_scan/while/body/t"],
                      "call.4": ["x/gnn/gnn_layer_0/sage_aggregate/pallas_call"],
                      "fusion.9": ["x/adamw/mul"]},
               "jit_other(3)": {"fusion.1": ["x/gnn/gnn_heads/dot_general"]}},
           "devices": [{"name": "/device:TPU:0", "modules": [
               [step, 0.0, 400.0], ["jit_other(3)", 450.0, 50.0],
               [step, 500.0, 400.0]],
               "ops": [
                   ["%fusion.1 = f32[8] fusion()", 0.0, 100.0],
                   ["%while.2 = () while()", 100.0, 300.0],
                   ["%fusion.7 = f32[8] fusion()", 100.0, 150.0],
                   ["%fusion.8 = f32[8] fusion()", 250.0, 150.0],
                   ["%fusion.1 = f32[2] fusion()", 450.0, 50.0],
                   ["%call.4 = f32[8] custom-call()", 500.0, 300.0],
                   ["%fusion.9 = f32[8] fusion()", 800.0, 100.0]]}]}
    red = tr.reduce(raw, work.SCOPE_GROUPS)
    ns = 1e-9
    # fusion.1 means the LSTM inside the step and a head inside the other
    # program; the `while` that only wraps is not counted; fusion.8 has no
    # name anywhere
    assert red["scope_s"] == pytest.approx({
        "lstm": 250 * ns, "gnn_heads": 50 * ns, "sage_aggregate": 300 * ns,
        "other": 100 * ns, "unresolved": 150 * ns})
    assert sum(red["scope_s"].values()) == pytest.approx(red["leaf_op_s"])
    # no groups asked, or no program kept: no scope times, never zeros
    assert tr.reduce(raw)["scope_s"] is None
    assert tr.reduce(dict(raw, op_scopes={}), work.SCOPE_GROUPS)[
        "scope_s"] is None


def test_roofline_share_by_hand():
    peaks = {"flops_per_s_bf16": 100e12, "hbm_bytes_per_s": 1e12}
    assert roofline.least_seconds(2e12, 1e9, peaks) == (0.02, "flops")
    assert roofline.least_seconds(2e9, 5e9, peaks) == (0.005, "bytes")
    run_ = {"peaks": peaks,
            "counters": {"train_work_per_window": {"lstm": {
                "flops": 2e12, "bytes": 1e9, "groups": ["lstm"]}}},
            "trace": {"windows_in_trace": 10,
                      "scope_s": {"lstm": 0.8, "other": 0.1}}}
    # 10 windows x 0.02 s at the peak, over 0.8 s on the device
    assert roofline.share(run_, "lstm") == pytest.approx(25.0)
    assert run.read_metric("lstm_roofline.train", run_) == pytest.approx(25.0)
    # nothing traced under the group's scopes: nothing, never 0
    run_["trace"]["scope_s"] = {"other": 0.9}
    assert roofline.share(run_, "lstm") is None
    assert run.read_metric("gnn_layers_roofline.train", run_) is None


def test_required_bytes_and_roofline_work_against_hand_counts():
    # one SageBlock at N=1024, E=2048, H=160 in bf16: h in and out
    # 2*1024*160*2, edges 2048*12, edge embedding 2048*160*2
    assert work.sage_block_bytes(1024, 2048, 160, 2) == (
        655_360 + 24_576 + 655_360)
    # one BiLSTM layer at S=128, T=100, H=256 in bf16: seven passes over
    # an [S, T, H] tensor
    assert work.lstm_layer_bytes(128, 100, 256, 2) == 7 * 6_553_600
    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())[
        "TPU v5 lite"]
    for name, bound_gnn in (("joint-100h", "bytes"), ("joint-dense", "bytes")):
        config = json.loads(
            (ROOT / f"chipbench/configs/{name}.json").read_text())
        w = work.train_work(config)
        assert set(w) == set(work.ROOFLINES)
        g = config["dataset"]["graph"]
        assert w["gnn_layers"]["flops"] == 3 * 28 * work.sage_block_flops(
            g["max_nodes"], g["max_edges"], 160)
        assert w["gnn_layers"]["bytes"] == 3 * 28 * work.sage_block_bytes(
            g["max_nodes"], g["max_edges"], 160, 2)
        assert w["lstm"]["flops"] == 3 * 2 * work.lstm_layer_flops(
            128, 100, 256, 256)
        # the layers alone: under the whole LSTM's count, which has in_proj
        assert w["lstm"]["flops"] < work.train_flops(config)["lstm"]
        assert roofline.least_seconds(
            w["gnn_layers"]["flops"], w["gnn_layers"]["bytes"], peaks)[1] \
            == bound_gnn
        assert roofline.least_seconds(
            w["lstm"]["flops"], w["lstm"]["bytes"], peaks)[1] == "flops"
        groups = {g for g, _ in work.SCOPE_GROUPS}
        assert all(set(v["groups"]) <= groups for v in w.values())
