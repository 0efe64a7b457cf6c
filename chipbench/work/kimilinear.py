"""Required work of one packed sequence (one "window" of the cell) of the
stream encoder's delta-rule stack (Kimi-Linear-48B-A3B's decoder, one chip's
share: published layers 1-5, a dense KDA layer, then KDA, KDA, MLA, KDA, each
with routed experts beside a shared one), from shapes, the packing and the
counted expert assignments.

"Required" as in `chipbench/work/phi4flash.py` and its siblings: what the
equations need, whatever implementation runs.  Matrix products count 2 FLOPs
per multiply-add, other elementwise work nothing; only real tokens count, and
only the MLA core's attending pairs (a query's own document, causally);
recomputation does not count.  By layer:

* the KDA blocks' projections, convolutions and gates (scope ``kda_proj``),
  by the real token: ``W_q``, ``W_k``, ``W_v`` (2304 -> 4096 each), the
  decay's and the output gate's two factors (2304 -> 128 -> 4096 each),
  ``W_b`` (2304 -> 32), ``W_o`` (4096 -> 2304) at 2 FLOPs a weight, and the
  three short convolutions' multiply-adds (``2 x 4 x 4096`` each);
* the delta rule (scope ``kda_core``), the same whatever implements it: per
  real token and head ``7 x d_k x d_v`` FLOPs (the decay of the state
  ``d_k d_v``, ``S^T k``, the rank-one update and ``S^T q`` at ``2 d_k d_v``
  each); its bytes: ``q``, ``k``, ``v``, ``o`` in the compute type and ``g``
  in float32, once a pass;
* the MLA block's projections (``W_q``, ``W_kva``, ``W_kvb``, ``W_o``; scope
  ``mla_latent``) by the real token, its core at its attending pairs, ``2 x
  32 x (192 + 128)`` FLOPs a pair (scope ``mla_attention``);
* the dense layer's SwiGLU (``3 x 2 x 2304 x 9216`` a token), the shared
  expert (``3 x 2 x 2304 x 1024`` a token, every expert layer), the router
  (``2 x 2304 x 256``), the experts held here by the assignments the router
  sent them (``3 x 2 x 2304 x 1024`` each: the run's own count,
  ``packing["assignments"]``, per routed layer and sequence, else the even
  split ``tokens x 8 x 8 / 256``), the head (``2 x 2304 x 20480`` a token).

Training is 3 x forward throughout.  Required bytes elsewhere are the least a
kernel has to move if only its inputs and outputs ever left the chip, in the
compute type, once a pass.

`packing_of(segments)` counts, over the resident sequences, the real tokens,
the documents (each a start at which the delta rule's state is zeroed) and
the MLA core's attending pairs a sequence has on average: every seed trains
the same sequences equally often (epochs).  The program counts the tokens and
the resets itself (``aux``'s ``delta_tokens`` / ``delta_resets``, the
counters ``kda_tokens_total`` / ``kda_resets_total``); the generator holds
them equal to this count on the warm-up's sequences.
"""

from __future__ import annotations

SCOPE_GROUPS = [["moe_router", ["moe_router"]],
                ["moe_dispatch", ["moe_dispatch"]],
                ["moe_combine", ["moe_combine"]],
                ["moe_experts", ["moe_experts"]],
                ["moe_shared", ["moe_shared"]],
                ["dense_mlp", ["dense_mlp"]],
                ["kda_core", ["kda_core"]],
                ["kda_proj", ["kda_proj"]],
                ["mla_attention", ["mla_attention"]],
                ["mla_latent", ["mla_latent"]],
                ["lm_head", ["lm_head_loss"]],
                ["stream_layer", ["stream_layer_"]],
                ["optimizer", ["optimizer_update"]]]
ROOFLINES = {"kda_core": ["kda_core"],
             "kda_proj": ["kda_proj"],
             "mla_attention": ["mla_attention"],
             "mla_latent": ["mla_latent"],
             "moe_shared": ["moe_shared"],
             "moe_experts": ["moe_experts"],
             "lm_head": ["lm_head"]}
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def shapes_of(config: dict) -> dict:
    lin = config["linear_attn_config"]
    published = config["published_layers"]
    return {"H": config["hidden_size"], "heads": config["num_attention_heads"],
            "kh": lin["num_heads"], "dk": lin["head_dim"],
            "conv": lin["short_conv_kernel_size"],
            "rank": config["assumed"]["gate_rank"]["value"],
            "delta": [n in lin["kda_layers"] for n in published],
            "dense": [n <= config["first_k_dense_replace"]
                      for n in published],
            "rkv": config["kv_lora_rank"], "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "I": config["intermediate_size"],
            "F": config["moe_intermediate_size"],
            "S": config["moe_intermediate_size"]
            * config["num_shared_experts"],
            "V": config["vocab_size"], "L": config["num_hidden_layers"],
            "E": config["router_experts"], "held": config["num_experts"],
            "K": config["num_experts_per_token"],
            "a": _BYTES[config["dtype"]]}


def packing_of(segments) -> dict:
    """``segments`` [S, T] int (0 = padding) -> per sequence, on average:
    ``tokens`` (real), ``docs`` (real documents: the starts at which the
    delta rule's state is zeroed) and ``pairs`` (one document, causal)."""
    import numpy as np

    seg = np.asarray(segments)
    tokens = docs = pairs = 0
    for row in seg:
        real = row[row > 0]
        cuts = np.flatnonzero(np.diff(real)) + 1
        for n in np.diff(np.concatenate([[0], cuts, [len(real)]])):
            n = int(n)
            tokens += n
            docs += n > 0
            pairs += n * (n + 1) // 2
    return {"tokens": tokens / len(seg), "docs": docs / len(seg),
            "pairs": pairs / len(seg)}


def kda_proj_params(d: dict) -> int:
    """One KDA block's matrices: ``W_q``, ``W_k``, ``W_v``, the two gates'
    factors, ``W_b``, ``W_o``."""
    width = d["kh"] * d["dk"]
    return (3 * d["H"] * width + 2 * (d["H"] * d["rank"] + d["rank"] * width)
            + d["H"] * d["kh"] + width * d["H"])


def kda_proj_flops(d: dict) -> int:
    """One KDA block's products by the real token, forward: its matrices
    and the three short convolutions."""
    return 2 * kda_proj_params(d) + 3 * 2 * d["conv"] * d["kh"] * d["dk"]


def kda_core_flops(d: dict) -> int:
    """The delta rule's FLOPs by the real token, forward."""
    return 7 * d["kh"] * d["dk"] * d["dk"]


def mla_proj_params(d: dict) -> int:
    heads = d["heads"]
    return (d["H"] * heads * (d["nope"] + d["rope"])
            + d["H"] * (d["rkv"] + d["rope"])
            + d["rkv"] * heads * (d["nope"] + d["dv"])
            + heads * d["dv"] * d["H"])


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
    delta, mla = sum(d["delta"]), d["L"] - sum(d["delta"])
    routed = d["L"] - sum(d["dense"])
    out = {"kda_proj": 3 * delta * t * kda_proj_flops(d),
           "kda_core": 3 * delta * t * kda_core_flops(d),
           "mla_latent": 3 * mla * t * 2 * mla_proj_params(d),
           "mla_attention": 3 * mla * packing["pairs"] * 2 * d["heads"] * (
               d["nope"] + d["rope"] + d["dv"]),
           "dense_mlp": 3 * sum(d["dense"]) * t * 3 * 2 * h * d["I"],
           "moe_shared": 3 * routed * t * 3 * 2 * h * d["S"],
           "moe_router": 3 * routed * t * 2 * h * d["E"],
           "moe_experts": (3 * routed * assignments_of(d, packing)
                           * 3 * 2 * h * d["F"]),
           "lm_head": 3 * t * 2 * h * d["V"]}
    out["total"] = sum(out.values())
    return out


def train_bytes(config: dict, packing: dict) -> dict:
    d = shapes_of(config)
    t, a, h = packing["tokens"], d["a"], d["H"]
    delta, mla = sum(d["delta"]), d["L"] - sum(d["delta"])
    routed = d["L"] - sum(d["dense"])
    width = d["kh"] * d["dk"]
    heads = d["heads"]
    out = {
        # q, k, v, o in the compute type, g in float32
        "kda_core": 3 * delta * t * d["kh"] * d["dk"] * (4 * a + 4),
        # u in; q, k, v, the gates and beta out (and back in after the
        # core with o); the result out; the weights
        "kda_proj": 3 * delta * a * (
            t * (2 * h + 3 * width + 2 * width + 2 * d["kh"] + 2 * width)
            + kda_proj_params(d) + 3 * d["conv"] * width),
        "mla_attention": 3 * mla * a * t * heads * (
            2 * (d["nope"] + d["rope"]) + 2 * d["dv"]),
        "mla_latent": 3 * mla * a * (
            t * (2 * h + 2 * heads * (d["nope"] + d["rope"]) + d["rkv"]
                 + d["rope"] + heads * (d["nope"] + d["dv"]))
            + mla_proj_params(d)),
        "moe_shared": 3 * routed * a * (2 * t * h + 3 * h * d["S"]),
        "moe_experts": 3 * routed * a * (
            2 * assignments_of(d, packing) * h + d["held"] * 3 * h * d["F"]),
        "lm_head": 3 * a * (t * h + d["V"] * h)}
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
    width = d["kh"] * d["dk"]
    total = 2 * d["V"] * h + h
    for delta, dense in zip(d["delta"], d["dense"]):
        if delta:
            # the convolutions, dt_bias, A_log and the head norm's scale
            total += (kda_proj_params(d) + 3 * d["conv"] * width + width
                      + d["kh"] + d["dk"])
        else:
            total += mla_proj_params(d) + d["rkv"]
        total += 2 * h
        total += (3 * h * d["I"] if dense else
                  h * d["E"] + d["E"] + 3 * h * d["S"]
                  + d["held"] * 3 * h * d["F"])
    return total
