"""Required work of one packed sequence (one "window" of the cell) of the
stream encoder's grouped-query stack (Laguna-S-2.1's decoder, one chip's
share: a dense layer, then three window layers and a full one, each with
routed experts beside a shared one), from shapes, the packing and the
counted expert assignments.

"Required" as in `chipbench/work/phi4flash.py`, `keyevl2.py` and
`glm47flash.py`: what the equations need, whatever implementation runs.
Matrix products count 2 FLOPs per multiply-add, elementwise work nothing;
only real tokens count, and only the attending pairs (a query's own
document, causally, and on a window layer no more than 511 positions back);
recomputation does not count.  By layer:

* the projections (``W_q``, ``W_k``, ``W_v``, ``W_o`` and the head gate's
  ``W_g``) by the real token: 2 x 3072 x (Hq x 128 + 2 x 8 x 128 + Hq + Hq x
  128) FLOPs, Hq 48 on a full layer and 72 on a window layer (scope
  ``gqa_proj``, with the rotary passes and the gate's product, which count
  nothing);
* the core at its attending pairs: ``Q K^T`` and ``P V`` over 128-wide heads,
  ``2 x Hq x (128 + 128)`` FLOPs a pair (scopes ``gqa_window_attention``,
  ``gqa_full_attention``);
* the dense layer's SwiGLU (``3 x 2 x 3072 x 12288`` a token), the shared
  expert (``3 x 2 x 3072 x 1024`` a token, every expert layer), the router
  (``2 x 3072 x 256``), the experts held here by the assignments the router
  sent them (``3 x 2 x 3072 x 1024`` each: the run's own count,
  ``packing["assignments"]``, per routed layer and sequence, else the even
  split ``tokens x 10 x 8 / 256``), the head (``2 x 3072 x 12544`` a token).

Training is 3 x forward throughout.  Required bytes are the least a kernel
has to move if only its inputs and outputs ever left the chip, in the
compute type, once a pass: a core reads ``Q, K, V`` and writes ``O``; the
projections read and write their rows and read their matrices; the shared
and held experts and the head likewise.  FLOPs bound every roofline here.

`packing_of(segments, window)` counts, over the resident sequences, the real
tokens and each kind's attending pairs a sequence has on average: every seed
trains the same sequences equally often (epochs).  The program counts the
same pairs itself (``aux``'s ``window_pairs`` / ``full_pairs``, the counter
``attention_pairs_total{kind}``); the generator holds the two equal on the
warm-up's sequences.
"""

from __future__ import annotations

SCOPE_GROUPS = [["moe_router", ["moe_router"]],
                ["moe_dispatch", ["moe_dispatch"]],
                ["moe_combine", ["moe_combine"]],
                ["moe_experts", ["moe_experts"]],
                ["moe_shared", ["moe_shared"]],
                ["dense_mlp", ["dense_mlp"]],
                ["gqa_window_attention", ["gqa_window_attention"]],
                ["gqa_full_attention", ["gqa_full_attention"]],
                ["gqa_proj", ["gqa_proj"]],
                ["lm_head", ["lm_head_loss"]],
                ["stream_layer", ["stream_layer_"]],
                ["optimizer", ["optimizer_update"]]]
ROOFLINES = {"gqa_window_attention": ["gqa_window_attention"],
             "gqa_full_attention": ["gqa_full_attention"],
             "gqa_proj": ["gqa_proj"],
             "moe_shared": ["moe_shared"],
             "moe_experts": ["moe_experts"],
             "lm_head": ["lm_head"]}
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def shapes_of(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    return {"H": config["hidden_size"], "d": config["head_dim"],
            "Hk": config["num_key_value_heads"],
            "heads": list(config["num_attention_heads_per_layer"][:layers]),
            "window": [t == "sliding_attention"
                       for t in config["layer_types"][:layers]],
            "dense": [t == "dense"
                      for t in config["mlp_layer_types"][:layers]],
            "reach": config["sliding_window"],
            "I": config["intermediate_size"],
            "F": config["moe_intermediate_size"],
            "S": config["shared_expert_intermediate_size"],
            "V": config["vocab_size"], "L": layers,
            "E": config["router_experts"], "held": config["num_experts"],
            "K": config["num_experts_per_tok"], "a": _BYTES[config["dtype"]]}


def packing_of(segments, window: int) -> dict:
    """``segments`` [S, T] int (0 = padding) -> per sequence, on average:
    ``tokens`` (real), ``full_pairs`` (one document, causal) and
    ``window_pairs`` (of those, no more than ``window`` - 1 back)."""
    import numpy as np

    seg = np.asarray(segments)
    tokens = full = near = 0
    for row in seg:
        real = row[row > 0]
        cuts = np.flatnonzero(np.diff(real)) + 1
        for n in np.diff(np.concatenate([[0], cuts, [len(real)]])):
            n = int(n)
            tokens += n
            full += n * (n + 1) // 2
            w = min(n, window)
            near += w * (w + 1) // 2 + (n - w) * window
    return {"tokens": tokens / len(seg), "full_pairs": full / len(seg),
            "window_pairs": near / len(seg)}


def proj_params(d: dict, heads: int) -> int:
    """A layer's ``W_q``, ``W_k``, ``W_v``, ``W_o`` and ``W_g``."""
    return d["H"] * (2 * heads * d["d"] + 2 * d["Hk"] * d["d"] + heads)


def attention_flops_per_pair(d: dict, heads: int) -> int:
    return 2 * heads * (d["d"] + d["d"])


def assignments_of(d: dict, packing: dict) -> float:
    """Assignments to held experts of one sequence and routed layer."""
    got = packing.get("assignments")
    return got if got is not None else (
        packing["tokens"] * d["K"] * d["held"] / d["E"])


def train_flops(config: dict, packing: dict) -> dict:
    """Required training FLOPs of one packed sequence by group, and
    ``total``."""
    d = shapes_of(config)
    t, h = packing["tokens"], d["H"]
    routed = d["L"] - sum(d["dense"])
    out = dict.fromkeys(("gqa_proj", "gqa_window_attention",
                         "gqa_full_attention"), 0.0)
    for heads, window in zip(d["heads"], d["window"]):
        out["gqa_proj"] += 3 * t * 2 * proj_params(d, heads)
        core = "gqa_window_attention" if window else "gqa_full_attention"
        pairs = packing["window_pairs" if window else "full_pairs"]
        out[core] += 3 * pairs * attention_flops_per_pair(d, heads)
    out["dense_mlp"] = 3 * sum(d["dense"]) * t * 3 * 2 * h * d["I"]
    out["moe_shared"] = 3 * routed * t * 3 * 2 * h * d["S"]
    out["moe_router"] = 3 * routed * t * 2 * h * d["E"]
    out["moe_experts"] = (3 * routed * assignments_of(d, packing)
                          * 3 * 2 * h * d["F"])
    out["lm_head"] = 3 * t * 2 * h * d["V"]
    out["total"] = sum(out.values())
    return out


def train_bytes(config: dict, packing: dict) -> dict:
    d = shapes_of(config)
    t, a, h, w = packing["tokens"], d["a"], d["H"], d["d"]
    routed = d["L"] - sum(d["dense"])
    out = dict.fromkeys(("gqa_proj", "gqa_window_attention",
                         "gqa_full_attention"), 0.0)
    for heads, window in zip(d["heads"], d["window"]):
        core = "gqa_window_attention" if window else "gqa_full_attention"
        out[core] += 3 * a * t * (2 * heads * w + 2 * d["Hk"] * w)
        # u in; q, k, v and the gate's logits out; o in, the result out;
        # the weights
        out["gqa_proj"] += 3 * a * (
            t * (2 * h + 2 * heads * w + 2 * d["Hk"] * w + heads)
            + proj_params(d, heads))
    out["moe_shared"] = 3 * routed * a * (2 * t * h + 3 * h * d["S"])
    out["moe_experts"] = 3 * routed * a * (
        2 * assignments_of(d, packing) * h + d["held"] * 3 * h * d["F"])
    out["lm_head"] = 3 * a * (t * h + d["V"] * h)
    return out


def train_work(config: dict, packing: dict) -> dict:
    """{roofline: {"flops", "bytes", "groups"}}: the required training work
    of one packed sequence inside the scopes of ``groups``."""
    flops, moved = train_flops(config, packing), train_bytes(config, packing)
    return {name: {"flops": flops[name], "bytes": moved[name],
                   "groups": groups} for name, groups in ROOFLINES.items()}


def count_params(config: dict) -> int:
    """Parameters held on the chip (the cut), from shapes."""
    d = shapes_of(config)
    h = d["H"]
    total = 2 * d["V"] * h + h
    for heads, dense in zip(d["heads"], d["dense"]):
        total += proj_params(d, heads) + 2 * h
        total += (3 * h * d["I"] if dense else
                  h * d["E"] + 3 * h * d["S"] + d["held"] * 3 * h * d["F"])
    return total
