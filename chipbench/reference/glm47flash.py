"""GLM-4.7-Flash's decoder, as the stream encoder's ``mla_dense`` / ``mla_moe``
kinds and its multi-token-prediction module carry it, in plain ``jax.numpy``:
forward, the two losses, gradients, and the benchmark's own weights.

The plain reference of the configuration `glm-4.7-flash`
(https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json,
``model_type`` ``glm4_moe_lite``; its keys mirror the DeepSeek-V3 body key
for key): float32 throughout, every matrix product at ``highest`` precision,
attention against the explicit mask, the experts as a loop over those held;
no kernel, no remat policy, no flax module, nothing of ``nerrf_tpu``.  The
weights are made here from the seed (`make_params`) under the program's
parameter names.

The equations (``x`` the residual, ``t`` a query, ``s <= t`` a key of the
same packed document; ``u = RMSNorm(x)``, eps 1e-5, learned scale, no bias
anywhere).

* **Latent attention** (every layer, the MTP block too).  ``c_q =
  RMSNorm_768(u W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` as 20 heads of 192
  + 64.  ``[c_kv | k_r] = u W_kva`` (512 + 64); ``c = RMSNorm_512(c_kv)``;
  ``[k_nope | v] = c W_kvb`` as 20 heads of 192 + 256.  ``q_rope`` and ``k_r``
  are rotated over all 64 dimensions (theta 1e6, rotate-half), the position
  counted inside the document; the one rotated ``k_r`` serves all 20 heads.
  Scores ``(q_nope . k_nope + q_rope . k_r) / sqrt(192 + 64)``, softmax over
  the allowed keys, ``o = P v`` (20 x 256); ``h = x + o W_o``.
* **Dense layer** (index 0): ``y = h + W_down (silu(W_gate z) * W_up z)``,
  ``z = RMSNorm(h)``, width 10240.
* **Expert layer** (the rest): ``z = RMSNorm(h)``; ``s = sigmoid(z W_r)``
  over all 64 experts; ``E_t`` = the 4 experts of largest ``s + b`` (``b``
  the correction bias, a constant; of equal ones the lower index); ``g_e =
  1.8 s_e / (sum_{e' in E_t} s_e' + 1e-20)``: ``b`` never enters a weight;
  ``y = h + sum_{e in E_t, e held} g_e E_e(z) + Shared(z)``, each ``E_e`` and
  ``Shared`` a SwiGLU of width 1536.  The normalisation is over all 4
  chosen, held or not; what the absent experts would add is left out; the
  shared expert is computed whole.
* **MTP module**: with ``H_t`` the stack's output before the final norm,
  ``h'_t = W_eh [RMSNorm_e(Emb(token_{t+1})) ; RMSNorm_h(H_t)]`` (4096 ->
  2048), one expert layer over ``h'`` under the same masks and positions,
  ``RMSNorm_s``, the main model's head: logits for ``token_{t+2}``.  ``Emb``
  is the main model's embedding.

After the last layer RMSNorm, then ``logits = x W_head^T`` over the held rows
of the untied head.  ``L = L_next + 0.3 L_mtp``: ``L_next`` the mean
cross-entropy over the positions whose next token is a real token of their
document, ``L_mtp`` over the positions whose next TWO tokens are.  No
auxiliary or balance loss; no gradient reaches ``b`` and `clip_and_update`
leaves it as it was.

What the published ``config.json`` leaves open is listed in the configuration
file under ``assumed`` (the MTP weight, the order of ``W_eh``'s halves and
that ``H`` is taken before the final norm, the rotary convention, the fixed
bias, no auxiliary loss, tie-breaks); the vocabulary is the 19,360 rows one
of eight chips holds of each matrix, the experts 0-7 of 64, the depth the
dense layer and four of 46 expert layers (``reduced``).  Departures of this
file from a naive transcription, none of which changes a number: ``token_{t
+ 1}`` at a sequence's last position is its first token (the position carries
no target and no later query attends to it); attention is computed a block
of queries at a time, the experts one at a time over all tokens under the
routing weight (zero where the expert was not chosen), the dense layer's MLP
a block of tokens at a time, the loss a block of positions at a time, each
behind `jax.checkpoint`, and the gradient is taken one layer at a time by
hand (`make_loss_and_grad`; a test holds it equal to `jax.grad` of the
whole).

``precision`` chooses how matrix products are computed (`f32`: the
reference; `bf16`, `fp8`: the lower-precision controls, both operands of
every product rounded first).  ``fault`` plants one fault of each new
mechanism, which the comparison has to catch: ``scale_from_nope`` takes the
softmax scale from the un-rotated width alone (``1 / sqrt(192)``),
``bias_in_weights`` lets the correction bias into the routing weights (``g``
from ``s + b``), ``mtp_shift_one`` gives the MTP module the target one
ahead, not two.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.reference import phi4flash
from chipbench.reference.keyevl2 import (allowed_keys, experts, head_nll,
                                         positions_in_document, rms_norm,
                                         rope, top_by_sort)
from chipbench.reference.phi4flash import (_is_leaf, _w, ein, init_opt, silu,
                                           targets_of)

__all__ = ["make_params", "make_loss_and_grad", "init_opt",
           "clip_and_update", "count_params", "selections"]

QUERY_BLOCK = 128       # queries a block in the attention
TOKEN_BLOCK = 1024      # tokens a block in the dense layer's MLP
LOSS_BLOCK = 1024       # positions a block in the loss
FAULTS = (None, "scale_from_nope", "bias_in_weights", "mtp_shift_one")
BIAS_STD = 0.1          # the seeded correction bias: N(0, 0.1^2)


def dims(config: dict) -> dict:
    return {"H": config["hidden_size"], "heads": config["num_attention_heads"],
            "rq": config["q_lora_rank"], "rkv": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "I": config["intermediate_size"],
            "F": config["moe_intermediate_size"],
            "S": config["moe_intermediate_size"] * config["n_shared_experts"],
            "V": config["vocab_size"], "L": config["num_hidden_layers"],
            "dense": config["first_k_dense_replace"],
            "mtp": config["num_nextn_predict_layers"],
            "E": config["router_experts"], "held": config["n_routed_experts"],
            "first": config["first_expert"],
            "K": config["num_experts_per_tok"],
            "scale": config["routed_scaling_factor"],
            "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"]),
            "mtp_weight": config["assumed"]["mtp_loss_weight"]["value"]}


# --------------------------------------------------------------------------
# the benchmark's own weights
# --------------------------------------------------------------------------

def _scale(width):
    return {"scale": ("ones", (width,), 0.0)}


def layer_spec(c: dict, dense: bool) -> dict:
    h, heads = c["H"], c["heads"]
    out = {"attn_norm": _scale(h), "mlp_norm": _scale(h),
           "q_a_norm": _scale(c["rq"]), "kv_a_norm": _scale(c["rkv"]),
           "wq_a": _w(h, c["rq"]),
           "wq_b": _w(c["rq"], heads * (c["nope"] + c["rope"])),
           "wkv_a": _w(h, c["rkv"] + c["rope"]),
           "wkv_b": _w(c["rkv"], heads * (c["nope"] + c["dv"])),
           "wo": _w(heads * c["dv"], h)}
    if dense:
        return {**out, "gate": _w(h, c["I"]), "up": _w(h, c["I"]),
                "down": _w(c["I"], h)}
    expert = lambda a, b: ("normal", (c["held"], a, b), 1.0 / math.sqrt(a))
    return {**out, "router": _w(h, c["E"]),
            "router_bias": ("normal", (c["E"],), BIAS_STD),
            "shared_gate": _w(h, c["S"]), "shared_up": _w(h, c["S"]),
            "shared_down": _w(c["S"], h),
            "w_gate": expert(h, c["F"]), "w_up": expert(h, c["F"]),
            "w_down": expert(c["F"], h)}


def param_spec(config: dict) -> dict:
    """Nested dict of ``(init kind, shape, scale)`` leaves under the
    program's parameter names.  Kernels, the experts and both vocabulary
    matrices N(0, 1/fan_in) (fan_in: the hidden size for the embedding),
    scales one, the correction bias N(0, 0.1^2)."""
    c = dims(config)
    h = c["H"]
    vocab = ("normal", (c["V"], h), 1.0 / math.sqrt(h))
    out = {"tok_embed": {"embedding": vocab}, "lm_head": vocab,
           "final_norm": _scale(h)}
    for i in range(c["L"]):
        out[f"layer_{i}"] = layer_spec(c, i < c["dense"])
    if c["mtp"]:
        out.update(mtp_block=layer_spec(c, False), mtp_enorm=_scale(h),
                   mtp_hnorm=_scale(h), mtp_norm=_scale(h),
                   mtp_eh_proj=_w(2 * h, h))
    return out


def count_params(config: dict) -> int:
    return sum(math.prod(s[1]) for s in jax.tree_util.tree_leaves(
        param_spec(config), is_leaf=_is_leaf))


def make_params(config: dict, key):
    """All weights in one jitted call from ``key``, float32."""
    leaves, treedef = jax.tree_util.tree_flatten(param_spec(config),
                                                 is_leaf=_is_leaf)

    @jax.jit
    def build(key):
        return [scale * jax.random.normal(k, shape, jnp.float32)
                if kind == "normal"
                else jnp.full(shape, float(kind == "ones"), jnp.float32)
                for k, (kind, shape, scale) in zip(
                    jax.random.split(key, len(leaves)), leaves)]

    return jax.tree_util.tree_unflatten(treedef, build(key))


# --------------------------------------------------------------------------
# one layer, one sequence [T]
# --------------------------------------------------------------------------

def latent_qkv(p, u, seg, c, precision):
    """-> (q [T, heads, nope + rope], k [T, heads, nope + rope], v [T,
    heads, dv]): the rotary parts rotated, the one rotary key behind every
    head's un-rotated key."""
    t, heads, nope = u.shape[0], c["heads"], c["nope"]
    proj = lambda name, x: ein("th,he->te", x, p[name]["kernel"], precision)
    pos = positions_in_document(seg)
    q = proj("wq_b", rms_norm(p["q_a_norm"], proj("wq_a", u), c["eps"])
             ).reshape(t, heads, nope + c["rope"])
    latent = proj("wkv_a", u)
    kv = proj("wkv_b", rms_norm(p["kv_a_norm"], latent[:, :c["rkv"]],
                                c["eps"])).reshape(t, heads, nope + c["dv"])
    k_r = rope(latent[:, None, c["rkv"]:], pos, c["theta"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, c["theta"])],
                        axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r, (t, heads, c["rope"]))], axis=-1)
    return q, k, kv[..., nope:]


def attention(p, u, seg, c, precision, fault=None):
    """-> o [T, heads * dv]."""
    t = u.shape[0]
    q, k, v = latent_qkv(p, u, seg, c, precision)
    width = c["nope"] if fault == "scale_from_nope" else c["nope"] + c["rope"]

    @partial(jax.checkpoint, prevent_cse=False)
    def queries(args):
        q_b, pos_b, seg_b = args
        logits = ein("thd,shd->hts", q_b, k, precision) / math.sqrt(width)
        soft = jax.nn.softmax(jnp.where(allowed_keys(pos_b, seg_b, seg),
                                        logits, -1e30), axis=-1)
        return ein("hts,shd->thd", soft, v, precision)

    blk = min(QUERY_BLOCK, t)
    cut = lambda x: x.reshape((t // blk, blk) + x.shape[1:])
    o = jax.lax.map(queries, (cut(q), cut(jnp.arange(t)), cut(seg)))
    return o.reshape(t, c["heads"] * c["dv"])


def swiglu(z, w_gate, w_up, w_down, precision):
    return ein("tf,fh->th", silu(ein("th,hf->tf", z, w_gate, precision))
               * ein("th,hf->tf", z, w_up, precision), w_down, precision)


def dense_mlp(p, z, precision):
    @partial(jax.checkpoint, prevent_cse=False)
    def tokens(z):
        return swiglu(z, p["gate"]["kernel"], p["up"]["kernel"],
                      p["down"]["kernel"], precision)

    t = z.shape[0]
    blk = min(TOKEN_BLOCK, t)
    return jax.lax.map(tokens, z.reshape(t // blk, blk, -1)).reshape(z.shape)


def routing(p, z, c, precision, fault=None):
    """-> (weights [T, E]: ``g_e`` where expert e is among the token's K
    chosen, else 0; the chosen experts [T, E] bool)."""
    scores = jax.nn.sigmoid(ein("th,he->te", z, p["router"]["kernel"],
                                precision))
    biased = scores + jax.lax.stop_gradient(p["router_bias"])
    chosen = top_by_sort(biased, jnp.ones_like(scores, bool), c["K"])
    weigh = biased if fault == "bias_in_weights" else scores
    total = jnp.sum(jnp.where(chosen, weigh, 0.0), axis=-1, keepdims=True)
    return (jnp.where(chosen, c["scale"] * weigh / (total + 1e-20), 0.0),
            chosen)


def layer(p, x, seg, c, precision="f32", fault=None):
    """One layer (dense where its parameters hold no router) -> (its output
    [T, H], the experts each token chose [T, E] bool, or [T, 0] where
    dense)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    u = rms_norm(p["attn_norm"], x, c["eps"])
    h = x + ein("te,eh->th", attention(p, u, seg, c, precision, fault),
                p["wo"]["kernel"], precision)
    z = rms_norm(p["mlp_norm"], h, c["eps"])
    if "router" not in p:
        return (h + dense_mlp(p, z, precision),
                jnp.zeros((x.shape[0], 0), bool))
    weights, chosen = routing(p, z, c, precision, fault)
    shared = swiglu(z, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                    p["shared_down"]["kernel"], precision)
    return h + experts(p, z, weights, c, precision) + shared, chosen


def mtp_input(p_enorm, p_hnorm, w_eh, e_next, hidden, c, precision):
    """``h' = W_eh [RMSNorm_e(Emb(token_{t+1})) ; RMSNorm_h(H)]``."""
    return ein("te,eh->th", jnp.concatenate(
        [rms_norm(p_enorm, e_next, c["eps"]),
         rms_norm(p_hnorm, hidden, c["eps"])], axis=-1), w_eh, precision)


def next_of(tokens):
    """The next token at every position; the first at the last."""
    return jnp.concatenate([tokens[1:], tokens[:1]])


def mtp_targets_of(tokens, seg, fault=None):
    """-> (targets [T], weights [T]): position t predicts token t + 2 where
    tokens t + 1 and t + 2 are real tokens of t's document."""
    ok = jnp.concatenate([(seg[1:-1] == seg[:-2]) & (seg[2:] == seg[:-2])
                          & (seg[:-2] > 0), jnp.zeros((2,), bool)])
    shift = 1 if fault == "mtp_shift_one" else 2
    return jnp.concatenate([tokens[shift:], tokens[:shift]]), \
        ok.astype(jnp.float32)


def batch_loss(params, tokens, seg, c, precision="f32", fault=None):
    """``tokens``, ``seg`` [B, T] -> ``L_next + 0.3 L_mtp`` as one
    differentiable function (tests; `make_loss_and_grad` computes the same a
    layer at a time)."""
    nll = extra = count = count2 = 0.0
    emb = params["tok_embed"]["embedding"]
    for b in range(tokens.shape[0]):
        x = emb[tokens[b]]
        for i in range(c["L"]):
            x, _ = layer(params[f"layer_{i}"], x, seg[b], c, precision, fault)
        y, w = targets_of(tokens[b], seg[b])
        nll = nll + head_nll(params["final_norm"], params["lm_head"], x, y, w,
                             c["eps"], precision)
        count = count + jnp.sum(w)
        if c["mtp"]:
            h = mtp_input(params["mtp_enorm"], params["mtp_hnorm"],
                          params["mtp_eh_proj"]["kernel"],
                          emb[next_of(tokens[b])], x, c, precision)
            h, _ = layer(params["mtp_block"], h, seg[b], c, precision, fault)
            y, w = mtp_targets_of(tokens[b], seg[b], fault)
            extra = extra + head_nll(params["mtp_norm"], params["lm_head"], h,
                                     y, w, c["eps"], precision)
            count2 = count2 + jnp.sum(w)
    return nll / jnp.maximum(count, 1.0) \
        + c["mtp_weight"] * extra / jnp.maximum(count2, 1.0)


def make_loss_and_grad(config: dict, precision: str = "f32", fault=None):
    """-> ``fn(params, tokens, seg) -> (loss, grads)``; ``fn.stats`` holds
    the last call's held assignments per routed layer and both loss terms.
    Reverse mode by hand over the stack: the forward pass keeps each layer's
    input, the backward pass calls `jax.vjp` of one layer (which recomputes
    it) from the MTP block back to the first layer, so the device holds one
    layer's intermediates at a time beside the parameters, their gradients
    and the optimizer's moments (11.3 GB at the published widths).  The
    embedding and the head are used twice and their gradients are sums."""
    c = dims(config)
    one = partial(layer, c=c, precision=precision, fault=fault)
    fwd = jax.jit(one)

    @jax.jit
    def bwd(p, x, seg, dy):
        _, pull = jax.vjp(lambda p, x: one(p, x, seg)[0], p, x)
        return pull(dy)

    head = jax.jit(jax.value_and_grad(
        lambda norm, w_head, x, y, w: head_nll(norm, w_head, x, y, w,
                                               c["eps"], precision),
        argnums=(0, 1, 2)))
    join = partial(mtp_input, c=c, precision=precision)
    join_fwd = jax.jit(join)

    @jax.jit
    def join_bwd(enorm, hnorm, w_eh, e_next, hidden, dy):
        return jax.vjp(join, enorm, hnorm, w_eh, e_next, hidden)[1](dy)

    embed = jax.jit(lambda emb, tokens: emb[tokens])
    scatter = jax.jit(lambda emb, tokens, dx: jnp.zeros_like(emb).at[
        tokens].add(dx))
    add = jax.jit(lambda a, b, scale: jax.tree_util.tree_map(
        lambda x, y: x + y * scale, a, b), donate_argnums=(0,))

    def fn(params, tokens, seg):
        rows = range(tokens.shape[0])
        emb = params["tok_embed"]["embedding"]
        targets = [targets_of(tokens[b], seg[b]) for b in rows]
        targets2 = [mtp_targets_of(tokens[b], seg[b], fault) for b in rows]
        scale = 1.0 / jnp.maximum(sum(jnp.sum(w) for _, w in targets), 1.0)
        scale2 = c["mtp_weight"] / jnp.maximum(
            sum(jnp.sum(w) for _, w in targets2), 1.0)
        grads, loss = {}, 0.0
        fn.stats = {"held_assignments": [], "token_loss": 0.0,
                    "mtp_loss": 0.0}
        held = lambda chosen: jnp.sum(
            chosen[:, c["first"]:c["first"] + c["held"]], axis=0)

        def accumulate(name, g, by=1.0):
            grads[name] = (add(grads[name], g, by) if name in grads
                           else jax.tree_util.tree_map(lambda x: x * by, g))

        for b in rows:
            xs = [embed(emb, tokens[b])]
            for i in range(c["L"]):
                x, chosen = fwd(params[f"layer_{i}"], xs[-1], seg[b])
                xs.append(x)
                if chosen.size:
                    fn.stats["held_assignments"].append(held(chosen))
            hidden = xs.pop()
            y, w = targets[b]
            nll, (g_norm, g_head, dx) = head(
                params["final_norm"], params["lm_head"], hidden, y, w)
            loss = loss + nll * scale
            fn.stats["token_loss"] += nll * scale
            accumulate("final_norm", g_norm, scale)
            accumulate("lm_head", g_head, scale)
            dx = dx * scale
            if c["mtp"]:
                nxt = next_of(tokens[b])
                args = (params["mtp_enorm"], params["mtp_hnorm"],
                        params["mtp_eh_proj"]["kernel"], embed(emb, nxt),
                        hidden)
                joined = join_fwd(*args)
                out, chosen = fwd(params["mtp_block"], joined, seg[b])
                fn.stats["held_assignments"].append(held(chosen))
                y, w = targets2[b]
                nll, (g_norm, g_head, d_out) = head(
                    params["mtp_norm"], params["lm_head"], out, y, w)
                loss = loss + nll * scale2
                fn.stats["mtp_loss"] += nll * scale2 / c["mtp_weight"]
                accumulate("mtp_norm", g_norm, scale2)
                accumulate("lm_head", g_head, scale2)
                dp, d_joined = bwd(params["mtp_block"], joined, seg[b],
                                   d_out * scale2)
                accumulate("mtp_block", dp)
                g_e, g_h, g_w, d_e, d_hidden = join_bwd(*args, d_joined)
                accumulate("mtp_enorm", g_e)
                accumulate("mtp_hnorm", g_h)
                accumulate("mtp_eh_proj", {"kernel": g_w})
                accumulate("tok_embed", {"embedding": scatter(emb, nxt, d_e)})
                dx = dx + d_hidden
            for i in reversed(range(c["L"])):
                dp, dx = bwd(params[f"layer_{i}"], xs.pop(), seg[b], dx)
                accumulate(f"layer_{i}", dp)
            accumulate("tok_embed", {"embedding": scatter(emb, tokens[b], dx)})
        return loss, grads

    return fn


def clip_and_update(params, grads, state, opt: dict, fetch: bool = False):
    """`phi4flash.clip_and_update` (the global-norm clip, then AdamW a leaf
    at a time), after which every correction bias is put back as it was: it
    is a buffer, and weight decay would otherwise move it."""
    held = {name: p["router_bias"] for name, p in params.items()
            if isinstance(p, dict) and "router_bias" in p}
    params, state, clipped = phi4flash.clip_and_update(params, grads, state,
                                                       opt, fetch=fetch)
    for name, bias in held.items():
        params[name]["router_bias"] = bias
    return params, state, clipped


def selections(params, tokens, seg, config: dict, precision: str = "f32"):
    """One sequence ``tokens``, ``seg`` [T] -> per routed layer of the stack
    and then the MTP block, the chosen experts [T, E] bool: what the
    diagnostic that counts how often program and reference choose alike
    reads."""
    c = dims(config)
    one = jax.jit(partial(layer, c=c, precision=precision))
    emb = params["tok_embed"]["embedding"]
    x = emb[tokens]
    out = []
    for i in range(c["L"]):
        x, chosen = one(params[f"layer_{i}"], x, seg)
        if chosen.size:
            out.append(chosen)
    if c["mtp"]:
        joined = jax.jit(partial(mtp_input, c=c, precision=precision))(
            params["mtp_enorm"], params["mtp_hnorm"],
            params["mtp_eh_proj"]["kernel"], emb[next_of(tokens)], x)
        out.append(one(params["mtp_block"], joined, seg)[1])
    return out
