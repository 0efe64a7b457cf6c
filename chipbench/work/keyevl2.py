"""Required work of one packed sequence (one "window" of the cell) of the
stream encoder's ``dsa_moe`` stack (Keye-VL-2.0-30B-A3B's decoder, one chip's
share), from shapes, the packing and the counted expert assignments.

"Required" as in `chipbench/work/phi4flash.py`: what the equations need,
whatever implementation runs.  Matrix products count 2 FLOPs per
multiply-add, elementwise work nothing; only real tokens count, and only
the query-key pairs that matter:

* the indexer scores every ALLOWED pair (a query's own document, causally):
  ``2 x 16 x 64`` FLOPs a pair forward.  Its backward pass needs the two
  products only at the SELECTED pairs (the loss is over ``S_t``), so
  training is ``allowed + 2 x selected`` pairs, not 3 x;
* attention needs ``Q K^T`` and ``P V`` at the selected pairs alone:
  ``2 x 2 x 32 x 128 = 16,384`` FLOPs a pair, training 3 x.  (The program
  computes every allowed pair and masks: the difference is its to answer
  for in `sparse_attention_roofline.train`);
* the experts held here work on the assignments the router sent them,
  ``3 x 2 x 2048 x 768`` FLOPs each, training 3 x.  The count comes from the
  run (``packing["assignments"]``: the program's own counter, per layer and
  sequence) and, where no run gave one, is the even split ``tokens x 8 x 16
  / 128``;
* projections (q, k, v, o; the indexer's three; the router) and the head by
  the token, training 3 x.  Recomputed operations do not count.

Required bytes are the least a kernel has to move if only its inputs and
outputs ever left the chip: the experts read and write their rows in the
compute type (2 bytes) and read their three matrices once a pass (at 512
rows an expert the weights are most of it); attention reads ``Q, K, V`` and
writes ``O``; the indexer reads its float32 ``qI, kI, w``.  Training 3 x.
FLOPs bound all three, measured against the chip's bf16 peak (the index
scores are float32 at ``highest`` precision in the program, so their share
reads the lower for it).

`packing_of(segments, topk)` counts, over the resident sequences, the real
tokens and the allowed and selected pairs a sequence has on average: every
seed trains the same sequences equally often (epochs).
"""

from __future__ import annotations

SCOPE_GROUPS = [["moe_router", ["moe_router"]],
                ["moe_dispatch", ["moe_dispatch"]],
                ["moe_combine", ["moe_combine"]],
                ["moe_experts", ["moe_experts"]],
                ["dsa_indexer_loss", ["dsa_indexer_loss"]],
                ["dsa_topk", ["dsa_topk"]],
                ["dsa_indexer", ["dsa_indexer"]],
                ["dsa_attention", ["dsa_attention"]],
                ["lm_head", ["lm_head_loss"]],
                ["stream_layer", ["stream_layer_"]],
                ["optimizer", ["optimizer_update"]]]
ROOFLINES = {"moe_experts": ["moe_experts"],
             "sparse_attention": ["dsa_attention"],
             "dsa_indexer": ["dsa_indexer"]}
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def shapes_of(config: dict) -> dict:
    sa = config["sa_config"]
    return {"H": config["hidden_size"], "Hq": config["num_attention_heads"],
            "Hk": config["num_key_value_heads"], "d": config["head_dim"],
            "F": config["moe_intermediate_size"], "V": config["vocab_size"],
            "L": config["num_hidden_layers"], "E": config["num_local_experts"],
            "held": config["num_experts"], "K": config["num_experts_per_tok"],
            "J": sa["indexer_num_heads"], "e": sa["indexer_head_dim"],
            "topk": sa["topk"], "a": _BYTES[config["dtype"]]}


def packing_of(segments, topk: int) -> dict:
    """``segments`` [S, T] int (0 = padding) -> per sequence, on average:
    ``tokens`` (real), ``pairs_full`` (allowed: one document, causal),
    ``pairs_selected`` (a query keeps ``min(t + 1, topk)`` of its keys)."""
    import numpy as np

    seg = np.asarray(segments)
    tokens = pairs_full = pairs_selected = 0
    for row in seg:
        real = row[row > 0]
        cuts = np.flatnonzero(np.diff(real)) + 1
        for n in np.diff(np.concatenate([[0], cuts, [len(real)]])):
            n = int(n)
            tokens += n
            pairs_full += n * (n + 1) // 2
            k = min(n, topk)
            pairs_selected += k * (k + 1) // 2 + (n - k) * topk
    s = len(seg)
    return {"tokens": tokens / s, "pairs_full": pairs_full / s,
            "pairs_selected": pairs_selected / s}


def projection_flops_per_token(d: dict) -> int:
    """One layer, forward: q, k, v, o; the indexer's q, k, w; the router."""
    return 2 * d["H"] * (2 * d["Hq"] * d["d"] + 2 * d["Hk"] * d["d"]
                         + d["J"] * d["e"] + d["e"] + d["J"] + d["E"])


def index_flops_per_pair(d: dict) -> int:
    return 2 * d["J"] * d["e"]


def attention_flops_per_pair(d: dict) -> int:
    return 2 * 2 * d["Hq"] * d["d"]


def expert_flops_per_assignment(d: dict) -> int:
    return 3 * 2 * d["H"] * d["F"]


def head_flops_per_token(d: dict) -> int:
    return 2 * d["H"] * d["V"]


def assignments_of(d: dict, packing: dict) -> float:
    """Assignments to held experts of one sequence and layer."""
    got = packing.get("assignments")
    return got if got is not None else (
        packing["tokens"] * d["K"] * d["held"] / d["E"])


def train_flops(config: dict, packing: dict) -> dict:
    """Required training FLOPs of one packed sequence by group, and
    ``total``."""
    d = shapes_of(config)
    t, layers = packing["tokens"], d["L"]
    out = {
        "projections": 3 * t * projection_flops_per_token(d) * layers,
        "dsa_indexer": index_flops_per_pair(d) * layers * (
            packing["pairs_full"] + 2 * packing["pairs_selected"]),
        "sparse_attention": 3 * attention_flops_per_pair(d) * layers
        * packing["pairs_selected"],
        "moe_experts": 3 * expert_flops_per_assignment(d) * layers
        * assignments_of(d, packing),
        "lm_head": 3 * t * head_flops_per_token(d),
    }
    out["total"] = sum(out.values())
    return out


def train_bytes(config: dict, packing: dict) -> dict:
    d = shapes_of(config)
    t, a, layers = packing["tokens"], d["a"], d["L"]
    return {
        "moe_experts": 3 * layers * a * (
            2 * assignments_of(d, packing) * d["H"]
            + d["held"] * 3 * d["H"] * d["F"]),
        "sparse_attention": 3 * layers * a * t * (2 * d["Hq"] + 2 * d["Hk"])
        * d["d"],
        "dsa_indexer": 3 * layers * 4 * t * (d["J"] * d["e"] + d["e"]
                                             + d["J"]),
    }


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
    attention = h * (2 * d["Hq"] + 2 * d["Hk"]) * d["d"] + 2 * d["d"]
    indexer = h * (d["J"] * d["e"] + d["e"] + d["J"]) + 2 * d["e"]
    experts = d["held"] * 3 * h * d["F"]
    layer = attention + indexer + h * d["E"] + experts + 2 * h
    return d["L"] * layer + 2 * d["V"] * h + h
