"""Required work of one packed sequence (one "window" of the cell) of the
stream encoder's latent-attention stack (GLM-4.7-Flash's decoder, one chip's
share: a dense layer, expert layers, the multi-token-prediction module), from
shapes, the packing and the counted expert assignments.

"Required" as in `chipbench/work/phi4flash.py` and `keyevl2.py`: what the
equations need, whatever implementation runs.  Matrix products count 2 FLOPs
per multiply-add, elementwise work nothing; only real tokens count, and only
the attending pairs (a query's own document, causally); recomputation does
not count.  A "block" is a stack layer or the MTP module's layer:

* latent attention's five projections (``W_qa``, ``W_qb``, ``W_kva``,
  ``W_kvb``, ``W_o``) by the real token, in every block;
* its core at the attending pairs: ``Q K^T`` over the 256-wide assembled
  keys and ``P V`` over the 256-wide values, ``2 x 20 x (256 + 256)`` FLOPs a
  pair, in every block;
* the dense layer's SwiGLU (``3 x 2 x 2048 x 10240`` FLOPs a token), the
  shared expert (``3 x 2 x 2048 x 1536`` a token, every routed block), the
  router (``2 x 2048 x 64``);
* the experts held here by the assignments the router sent them, ``3 x 2 x
  2048 x 1536`` FLOPs each.  The count comes from the run
  (``packing["assignments"]``: the program's own counter, per routed block
  and sequence) and, where no run gave one, is the even split ``tokens x 4 x
  8 / 64``;
* the MTP module's joining projection (``2 x 4096 x 2048`` a token) and
  both passes of the head (``2 x 2048 x 19360`` a token each).

Training is 3 x forward throughout.  Required bytes are the least a kernel
has to move if only its inputs and outputs ever left the chip, in the
compute type, once a pass: attention reads ``Q, K, V`` and writes ``O``; the
projections, the shared expert and the held experts read and write their
rows and read their matrices; the head reads its rows and its matrix.  FLOPs
bound all five rooflines.

The five rooflines count the STACK's layers only, as their scopes do: in a
trace the MTP module's layer is one group (``mtp_block``, told apart before
its inner scopes, its experts' walk among them) beside ``mtp_embed_proj`` and
``mtp_head`` (its head pass, scope ``mtp_head_loss``), so that
`mtp_step_share.train` can read the module's whole time; its required work
is in `train_flops` under ``mtp`` and in the step's total.  So ``lm_head``
(scope ``lm_head_loss``) is the next-token pass alone, one pass of the head,
and ``moe_experts`` the stack's routed layers' walks.

`packing_of(segments)` counts, over the resident sequences, the real tokens
and the attending pairs a sequence has on average: every seed trains the
same sequences equally often (epochs).
"""

from __future__ import annotations

SCOPE_GROUPS = [["mtp_embed_proj", ["mtp_embed_proj"]],
                ["mtp_head", ["mtp_head_loss"]],
                ["mtp_block", ["mtp_block"]],
                ["moe_router", ["moe_router"]],
                ["moe_dispatch", ["moe_dispatch"]],
                ["moe_combine", ["moe_combine"]],
                ["moe_experts", ["moe_experts"]],
                ["moe_shared", ["moe_shared"]],
                ["dense_mlp", ["dense_mlp"]],
                ["mla_attention", ["mla_attention"]],
                ["mla_latent", ["mla_latent"]],
                ["lm_head", ["lm_head_loss"]],
                ["stream_layer", ["stream_layer_"]],
                ["optimizer", ["optimizer_update"]]]
ROOFLINES = {"mla_attention": ["mla_attention"],
             "mla_latent": ["mla_latent"],
             "moe_shared": ["moe_shared"],
             "moe_experts": ["moe_experts"],
             "lm_head": ["lm_head"]}
MTP_GROUPS = ("mtp_embed_proj", "mtp_block", "mtp_head")
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def shapes_of(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    return {"H": config["hidden_size"], "heads": config["num_attention_heads"],
            "rq": config["q_lora_rank"], "rkv": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "I": config["intermediate_size"],
            "F": config["moe_intermediate_size"],
            "S": config["moe_intermediate_size"] * config["n_shared_experts"],
            "V": config["vocab_size"], "L": layers, "dense": dense,
            "routed": layers - dense,
            "mtp": config["num_nextn_predict_layers"],
            "E": config["router_experts"], "held": config["n_routed_experts"],
            "K": config["num_experts_per_tok"], "a": _BYTES[config["dtype"]]}


def packing_of(segments) -> dict:
    """``segments`` [S, T] int (0 = padding) -> per sequence, on average:
    ``tokens`` (real), ``pairs`` (attending: one document, causal)."""
    import numpy as np

    seg = np.asarray(segments)
    tokens = pairs = 0
    for row in seg:
        real = row[row > 0]
        cuts = np.flatnonzero(np.diff(real)) + 1
        for n in np.diff(np.concatenate([[0], cuts, [len(real)]])):
            tokens += int(n)
            pairs += int(n) * (int(n) + 1) // 2
    return {"tokens": tokens / len(seg), "pairs": pairs / len(seg)}


def latent_params(d: dict) -> int:
    """The five projections' weights of one block."""
    return (d["H"] * d["rq"] + d["rq"] * d["heads"] * (d["nope"] + d["rope"])
            + d["H"] * (d["rkv"] + d["rope"])
            + d["rkv"] * d["heads"] * (d["nope"] + d["dv"])
            + d["heads"] * d["dv"] * d["H"])


def latent_flops_per_token(d: dict) -> int:
    return 2 * latent_params(d)


def attention_flops_per_pair(d: dict) -> int:
    return 2 * d["heads"] * (d["nope"] + d["rope"] + d["dv"])


def dense_flops_per_token(d: dict) -> int:
    return 3 * 2 * d["H"] * d["I"]


def shared_flops_per_token(d: dict) -> int:
    return 3 * 2 * d["H"] * d["S"]


def router_flops_per_token(d: dict) -> int:
    return 2 * d["H"] * d["E"]


def expert_flops_per_assignment(d: dict) -> int:
    return 3 * 2 * d["H"] * d["F"]


def head_flops_per_token(d: dict) -> int:
    return 2 * d["H"] * d["V"]


def assignments_of(d: dict, packing: dict) -> float:
    """Assignments to held experts of one sequence and routed block."""
    got = packing.get("assignments")
    return got if got is not None else (
        packing["tokens"] * d["K"] * d["held"] / d["E"])


def block_flops(d: dict, packing: dict) -> dict:
    """Forward FLOPs of one routed block by part."""
    t = packing["tokens"]
    return {"mla_latent": t * latent_flops_per_token(d),
            "mla_attention": packing["pairs"] * attention_flops_per_pair(d),
            "moe_shared": t * shared_flops_per_token(d),
            "moe_router": t * router_flops_per_token(d),
            "moe_experts": assignments_of(d, packing)
            * expert_flops_per_assignment(d)}


def train_flops(config: dict, packing: dict) -> dict:
    """Required training FLOPs of one packed sequence by group, and
    ``total``.  The stack's groups count its ``L`` layers; ``mtp`` holds the
    whole module: its block, its joining projection, its head pass."""
    d = shapes_of(config)
    t = packing["tokens"]
    block = block_flops(d, packing)
    out = {name: 3 * value * (d["L"] if name.startswith("mla_")
                              else d["routed"])
           for name, value in block.items()}
    out["dense_mlp"] = 3 * t * dense_flops_per_token(d) * d["dense"]
    out["lm_head"] = 3 * t * head_flops_per_token(d)
    out["mtp"] = 3 * d["mtp"] * (sum(block.values()) + t * (
        2 * 2 * d["H"] * d["H"] + head_flops_per_token(d)))
    out["total"] = sum(out.values())
    return out


def train_bytes(config: dict, packing: dict) -> dict:
    d = shapes_of(config)
    t, a = packing["tokens"], d["a"]
    heads = d["heads"]
    q_wide = heads * (d["nope"] + d["rope"])
    return {
        "mla_attention": 3 * d["L"] * a * t * (2 * q_wide
                                               + 2 * heads * d["dv"]),
        # u in, q, the latent and kv out, o in, the result out; the weights
        "mla_latent": 3 * d["L"] * a * (
            t * (2 * d["H"] + d["rq"] + q_wide + d["rkv"] + d["rope"]
                 + heads * (d["nope"] + d["dv"]) + heads * d["dv"])
            + latent_params(d)),
        "moe_shared": 3 * d["routed"] * a * (2 * t * d["H"]
                                             + 3 * d["H"] * d["S"]),
        "moe_experts": 3 * d["routed"] * a * (
            2 * assignments_of(d, packing) * d["H"]
            + d["held"] * 3 * d["H"] * d["F"]),
        "lm_head": 3 * a * (t * d["H"] + d["V"] * d["H"]),
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
    attention = latent_params(d) + d["rq"] + d["rkv"]
    dense = attention + 2 * h + 3 * h * d["I"]
    routed = (attention + 2 * h + h * d["E"] + d["E"] + 3 * h * d["S"]
              + d["held"] * 3 * h * d["F"])
    mtp = d["mtp"] * (routed + 2 * h * h + 3 * h)
    return (d["dense"] * dense + d["routed"] * routed + mtp
            + 2 * d["V"] * h + h)
