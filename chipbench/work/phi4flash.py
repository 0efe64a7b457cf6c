"""Required work of one packed sequence (one "window" of the cell) of the
decoder-hybrid-decoder stream encoder, from shapes and the packing alone.

"Required" as in `chipbench/work/nerrfnet.py`: what the equations need,
whatever implementation runs.  Matrix products count 2 FLOPs per
multiply-add, the scan 6 per state element and step (``exp(dt A)``, the
two products and the sum of the update, the product and the sum of the
read-out), other elementwise work nothing.  Only real tokens count (the
padded tail of a packed sequence is the packer's waste, not work), and in
attention only the pairs the masks let attend: a query's own document,
causally, within the window where there is one.  Differential attention
needs ``Q K^T`` for both softmaxes of a pair and ONE product of their
difference with the value pair (the program multiplies each softmax by the
value pair and subtracts after: that second product is not required).
Training is 3 x forward; recomputed operations do not count.

Required bytes are the least a kernel has to move if only its inputs and
outputs ever left the chip, in the compute type (2 bytes): the scan reads
``x``, ``dt`` [d_inner] and ``B``, ``C`` [d_state] a token and writes ``y``
[d_inner]; attention reads ``Q, K, V`` and writes ``O``; the MLP and the
head read and write their activations and read their weights once a pass
(at batch 1 the weights are most of the MLP's bytes).  Training 3 x, as
the FLOPs.

`packing_of(segments, window)` counts, over the resident sequences, the
real tokens and the attending pairs a sequence has on average: every seed
trains the same sequences equally often (epochs), so the mean is the
cell's.
"""

from __future__ import annotations

SCOPE_GROUPS = [["ssm_scan", ["ssm_scan"]],
                ["ssm_conv", ["ssm_conv"]],
                ["stream_attention", ["swa_attention", "full_attention",
                                      "cross_attention"]],
                ["stream_mlp", ["stream_mlp"]],
                ["lm_head", ["lm_head_loss"]],
                ["gmu", ["gmu"]],
                ["stream_layer", ["stream_layer_"]],
                ["optimizer", ["optimizer_update"]]]
ROOFLINES = {"ssm_scan": ["ssm_scan"],
             "stream_attention": ["stream_attention"],
             "stream_mlp": ["stream_mlp"],
             "lm_head": ["lm_head"]}
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def shapes_of(config: dict) -> dict:
    a = {k: v["value"] for k, v in config["assumed"].items() if "value" in v}
    h = config["hidden_size"]
    kinds = list(config["kinds"])
    return {"H": h, "Hq": config["num_attention_heads"],
            "Hk": config["num_key_value_heads"],
            "d": h // config["num_attention_heads"],
            "F": config["intermediate_size"], "V": config["vocab_size"],
            "W": config["sliding_window"], "Di": a["expand"] * h,
            "N": a["d_state"], "K": a["d_conv"], "R": a["dt_rank"],
            "kinds": kinds, "a": _BYTES[config["dtype"]]}


def packing_of(segments, window: int) -> dict:
    """``segments`` [S, T] int (0 = padding) -> per sequence, on average:
    ``tokens`` (real), ``pairs_full`` (query-key pairs of one document,
    causal), ``pairs_window`` (those no further apart than the window)."""
    import numpy as np

    seg = np.asarray(segments)
    tokens = pairs_full = pairs_window = 0
    for row in seg:
        real = row[row > 0]
        # documents are contiguous: their lengths are the run lengths
        cuts = np.flatnonzero(np.diff(real)) + 1
        for n in np.diff(np.concatenate([[0], cuts, [len(real)]])):
            n = int(n)
            tokens += n
            pairs_full += n * (n + 1) // 2
            w = min(n, window)
            pairs_window += w * (w + 1) // 2 + (n - w) * window
    s = len(seg)
    return {"tokens": tokens / s, "pairs_full": pairs_full / s,
            "pairs_window": pairs_window / s}


def mixer_flops_per_token(kind: str, d: dict) -> int:
    """Forward FLOPs a token of a mixer's products and scan (attention's
    pair products are counted by the pair: `attention_flops_per_pair`)."""
    h, di = d["H"], d["Di"]
    if kind == "mamba":
        return (2 * h * 2 * di + 2 * d["K"] * di
                + 2 * di * (d["R"] + 2 * d["N"]) + 2 * d["R"] * di
                + scan_flops_per_token(d) + 2 * di * h)
    if kind in ("swa", "full"):
        return 2 * h * (2 * d["Hq"] + 2 * d["Hk"]) * d["d"]
    if kind == "cross":
        return 2 * h * 2 * d["Hq"] * d["d"]
    if kind == "gmu":
        return 2 * h * di + 2 * di * h
    raise ValueError(f"unknown layer kind {kind!r}")


def scan_flops_per_token(d: dict) -> int:
    return 6 * d["Di"] * d["N"]


def attention_flops_per_pair(d: dict) -> int:
    """One attending (query, key) pair of one layer: ``Q K^T`` over every
    query head, and the pairs' differences times the value pair."""
    return 2 * d["Hq"] * d["d"] + 2 * (d["Hq"] // 2) * 2 * d["d"]


def mlp_flops_per_token(d: dict) -> int:
    return 3 * 2 * d["H"] * d["F"]


def head_flops_per_token(d: dict) -> int:
    return 2 * d["H"] * d["V"]


def _pairs(kind: str, packing: dict) -> float:
    return packing["pairs_window"] if kind == "swa" else packing["pairs_full"]


def forward_flops(config: dict, packing: dict) -> dict:
    """Forward FLOPs of one packed sequence by group."""
    d = shapes_of(config)
    t = packing["tokens"]
    attn = [k for k in d["kinds"] if k in ("swa", "full", "cross")]
    out = {
        "ssm_scan": t * scan_flops_per_token(d) * d["kinds"].count("mamba"),
        "stream_attention": attention_flops_per_pair(d) * sum(
            _pairs(k, packing) for k in attn),
        "stream_mlp": t * mlp_flops_per_token(d) * len(d["kinds"]),
        "lm_head": t * head_flops_per_token(d),
    }
    out["mixers"] = t * sum(mixer_flops_per_token(k, d)
                            for k in d["kinds"]) - out["ssm_scan"]
    return out


def forward_bytes(config: dict, packing: dict) -> dict:
    """Required bytes of one packed sequence's forward pass for the groups
    that have a roofline."""
    d = shapes_of(config)
    t, a = packing["tokens"], d["a"]
    attn = [k for k in d["kinds"] if k in ("swa", "full", "cross")]
    return {
        "ssm_scan": t * (3 * d["Di"] + 2 * d["N"]) * a
        * d["kinds"].count("mamba"),
        "stream_attention": t * (2 * d["Hq"] + 2 * d["Hk"]) * d["d"] * a
        * len(attn),
        "stream_mlp": (t * 2 * d["H"] + 3 * d["H"] * d["F"]) * a
        * len(d["kinds"]),
        "lm_head": (t * d["H"] + d["V"] * d["H"]) * a,
    }


def train_work(config: dict, packing: dict) -> dict:
    """{roofline: {"flops", "bytes", "groups"}}: the required training work
    of one packed sequence inside the scopes of ``groups``."""
    flops, moved = forward_flops(config, packing), forward_bytes(config,
                                                                 packing)
    return {name: {"flops": 3 * flops[name], "bytes": 3 * moved[name],
                   "groups": groups} for name, groups in ROOFLINES.items()}


def train_flops(config: dict, packing: dict) -> dict:
    """Required training FLOPs of one packed sequence by group, and
    ``total``."""
    out = {k: 3 * v for k, v in forward_flops(config, packing).items()}
    out["total"] = sum(out.values())
    return out


def count_params(config: dict) -> int:
    """Parameters held on the chip (the cut), from shapes."""
    d = shapes_of(config)
    h, di = d["H"], d["Di"]
    per = {"mamba": (h * 2 * di + d["K"] * di + di
                     + di * (d["R"] + 2 * d["N"]) + d["R"] * di + di
                     + di * d["N"] + di + di * h),
           "gmu": 2 * h * di}
    qo, kv = 2 * h * d["Hq"] * d["d"], 2 * h * d["Hk"] * d["d"]
    lam = 4 * d["d"] + 2 * d["d"]
    per.update({"swa": qo + kv + lam, "full": qo + kv + lam,
                "cross": qo + lam})
    layer = 3 * h * d["F"] + 2 * 2 * h        # the MLP and the two norms
    return (sum(per[k] + layer for k in d["kinds"]) + d["V"] * h + 2 * h)
