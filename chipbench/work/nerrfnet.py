"""Required work of one NerrfNet window, from shapes alone.

"Required" is what the model's equations need at the cell's padded shapes,
whatever implementation runs: matrix products count 2 FLOPs per
multiply-add, elementwise work is not counted, and `dense_adj`'s
``2 N^2 H`` adjacency product is NOT required work (the weighted
bidirectional aggregate needs ``2 * 2 E H``).  Training is 3 x forward
(forward, gradient w.r.t. activations, gradient w.r.t. weights); recomputed
operations do not count.

Required bytes (for the kernels' rooflines) are the least a layer has to
move between HBM and the chip if nothing but its inputs and outputs ever
left it: each activation tensor read once and written once in the compute
type (``dtype`` of the configuration: 2 bytes for bfloat16), the edge list
read once per layer, and, as with the FLOPs, training = 3 x forward (the
backward pass reads what the forward saved and writes one gradient per
input).  Weights are left out: shared by the windows of a batch, they are
under 3 % of a batch of 8's bytes.  Like the FLOPs this is a floor, not a
description of any implementation: `dense_adj` moves an ``N x N`` adjacency
per layer on top of it.

`SCOPE_GROUPS` says which `jax.named_scope` paths of the program belong to
which group of the trace reduction (`chipbench.trace_reduce.group_of`), in
order: the fused aggregate sits inside a layer's scope and is told apart
first.  `ROOFLINES` says which groups' device time is held against which
required work.
"""

from __future__ import annotations

SCOPE_GROUPS = [["sage_aggregate", ["sage_aggregate"]],
                ["gnn_layer", ["gnn_layer_"]],
                ["lstm", ["lstm_layer_", "lstm_scan"]],
                ["gnn_heads", ["gnn_heads"]]]
ROOFLINES = {"gnn_layers": ["sage_aggregate", "gnn_layer"],
             "lstm": ["lstm"]}
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def shapes_of(config: dict) -> dict:
    g = config["dataset"]["graph"]
    m = config["train"]["model"]
    s = config["shapes"]
    return {"N": g["max_nodes"], "E": g["max_edges"],
            "S": config["dataset"]["max_seqs"], "T": config["dataset"]["seq_len"],
            "H": m["gnn"]["hidden"], "L": m["gnn"]["num_layers"],
            "Hl": m["lstm"]["hidden"], "Ll": m["lstm"]["num_layers"],
            "Fn": s["node_feature_dim"], "Fe": s["edge_feature_dim"],
            "Fs": s["seq_feature_dim"], "fuse": m["fuse"]}


def sage_block_flops(N, E, H) -> int:
    """One SageBlock forward: w_msg, w_self over [hn, agg], and the
    bidirectional weighted aggregate."""
    return 2 * N * H * H + 2 * N * 2 * H * H + 2 * 2 * E * H


def lstm_layer_flops(S, T, H_in, H) -> int:
    """One BiLSTM layer forward: per direction the input and the recurrent
    gate products over S*T steps, then the merge of the two directions."""
    per_direction = 2 * S * T * H_in * 4 * H + 2 * S * T * H * 4 * H
    return 2 * per_direction + 2 * S * T * 2 * H * H


def forward_flops(config: dict) -> dict:
    """Forward FLOPs of one window by group: gnn_layers, lstm, other."""
    d = shapes_of(config)
    gnn_layers = d["L"] * sage_block_flops(d["N"], d["E"], d["H"])
    lstm = (2 * d["S"] * d["T"] * d["Fs"] * d["Hl"]
            + d["Ll"] * lstm_layer_flops(d["S"], d["T"], d["Hl"], d["Hl"])
            + 2 * d["S"] * d["Hl"])
    other = (2 * d["N"] * d["Fn"] * d["H"] + 2 * d["E"] * d["Fe"] * d["H"]
             + 2 * d["N"] * d["H"]
             + 2 * d["E"] * 4 * d["H"] * d["H"] + 2 * d["E"] * d["H"])
    if d["fuse"]:
        other += 2 * d["S"] * d["Hl"] * d["Fn"]
    return {"gnn_layers": gnn_layers, "lstm": lstm, "other": other}


def sage_block_bytes(N, E, H, a) -> int:
    """One SageBlock forward: ``h`` in and out, the edge list (source,
    destination, weight: 12 bytes an edge) and the edge embedding."""
    return 2 * N * H * a + E * (12 + H * a)


def lstm_layer_bytes(S, T, H, a) -> int:
    """One BiLSTM layer forward: the input read by both directions, each
    direction's hidden states written, read by the merge, the merged
    output written."""
    return (2 + 2 + 2 + 1) * S * T * H * a


def forward_bytes(config: dict) -> dict:
    """Required bytes of one window's forward pass for the groups that
    have a roofline."""
    d = shapes_of(config)
    m = config["train"]["model"]
    return {"gnn_layers": d["L"] * sage_block_bytes(
                d["N"], d["E"], d["H"], _BYTES[m["gnn"]["dtype"]]),
            "lstm": d["Ll"] * lstm_layer_bytes(
                d["S"], d["T"], d["Hl"], _BYTES[m["lstm"]["dtype"]])}


def train_work(config: dict) -> dict:
    """{roofline: {"flops", "bytes", "groups"}}: the required training work
    of one window inside the scopes of ``groups`` (the layers alone: the
    encoders, `in_proj` and the heads are traced outside them)."""
    d = shapes_of(config)
    flops = {"gnn_layers": forward_flops(config)["gnn_layers"],
             "lstm": d["Ll"] * lstm_layer_flops(d["S"], d["T"], d["Hl"],
                                                d["Hl"])}
    moved = forward_bytes(config)
    return {name: {"flops": 3 * flops[name], "bytes": 3 * moved[name],
                   "groups": groups} for name, groups in ROOFLINES.items()}


def train_flops(config: dict) -> dict:
    """Required training FLOPs of one window by group, and ``total``."""
    out = {k: 3 * v for k, v in forward_flops(config).items()}
    out["total"] = sum(out.values())
    return out
