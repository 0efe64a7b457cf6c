"""Laguna-S-2.1's decoder, as the stream encoder's ``gqa_full_dense`` /
``gqa_swa_moe`` / ``gqa_full_moe`` kinds carry it, in plain ``jax.numpy``:
forward, loss, gradients, and the benchmark's own weights.

The plain reference of the configuration `laguna-s-2.1`
(https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json,
``model_type`` ``laguna``): float32 throughout, every matrix product at
``highest`` precision, attention against the explicit mask, the experts as a
loop over those held; no kernel, no remat policy, no flax module, nothing of
``nerrf_tpu``.  The weights are made here from the seed (`make_params`) under
the program's parameter names.

The equations (``x`` the residual, ``t`` a query, ``s <= t`` a key of the
same packed document; ``u = RMSNorm(x)``, eps 1e-6, learned scale, no bias
anywhere).  Layer ``i``'s attention kind is ``layer_types[i]``, its query
heads ``num_attention_heads_per_layer[i]``, its feed-forward
``mlp_layer_types[i]``.

* **Attention** (every layer).  ``q = u W_q`` as ``Hq`` heads of 128 (48 on
  a full layer, 72 on a window layer), ``k = u W_k``, ``v = u W_v`` as 8
  heads of 128; query head ``h`` reads key-value head ``h // (Hq / 8)``.
  Positions are counted inside the document.  A window layer rotates all 128
  dimensions of ``q`` and ``k`` (rotate-half, theta 1e4); a full layer
  rotates the first 64 (pairs ``i, i + 32``; the last 64 pass) with YaRN's
  frequencies (`yarn_inv_freq`: theta 5e5, factor 128 over 8192 original
  positions, beta_fast 32, beta_slow 1) and its cosines and sines times
  1.4852 (``attention_factor``).  Scores ``q . k / sqrt(128)``; a full layer
  allows every key ``s <= t`` of the document, a window layer those with
  ``t - s < 512``; softmax over the allowed keys, ``o_h = P v``.
* **Head gate**: ``o_h <- sigmoid(u . w_g,h) o_h`` (``W_g``: 3072 x Hq); ``h
  = x + [o_1 .. o_Hq] W_o``.
* **Dense layer** (layer 0): ``y = h + W_down (silu(W_gate z) * W_up z)``,
  ``z = RMSNorm(h)``, width 12,288.
* **Expert layer** (the rest): ``z = RMSNorm(h)``; ``p = softmax(z W_r)``
  over all 256 experts; ``E_t`` = the 10 of largest ``p`` (of equal ones the
  lower index); ``g_e = 2.5 p_e / sum_{e' in E_t} p_e'``; ``y = h + sum_{e
  in E_t, e held} g_e E_e(z) + Shared(z)``, each ``E_e`` and ``Shared`` a
  SwiGLU of width 1024.  The normalisation is over all 10 chosen, held or
  not; what the absent experts would add is left out; the shared expert is
  computed whole and ungated.

After the last layer RMSNorm, then ``logits = x W_head^T`` over the held rows
of the untied head; ``L`` the mean cross-entropy over the positions whose
next token is a real token of their document.  No auxiliary loss.

What the published ``config.json`` leaves open is listed in the configuration
file under ``assumed`` (the gate's form and input, no q/k norm, no gate on the
shared expert, HF's YaRN blend and where its factor applies, which dimensions
rotate, the window's edge, the scale, tie-breaks, positions inside a
document); the vocabulary is the 12,544 rows one of eight chips holds of each
matrix, the experts 0-7 of 256, the depth the dense layer and one period of
four (``reduced``).  Departures of this file from a naive transcription, none
of which changes a number: attention is computed a block of queries at a
time against every key (the mask does the rest), the experts one at a time
over all tokens under the routing weight (zero where the expert was not
chosen), the dense layer's MLP a block of tokens at a time, the loss a block
of positions at a time, each behind `jax.checkpoint`, and the gradient is
taken one layer at a time by hand (`make_loss_and_grad`; a test holds it
equal to `jax.grad` of the whole).

``precision`` chooses how matrix products are computed (`f32`: the
reference; `bf16`, `fp8`: the lower-precision controls, both operands of
every product rounded first).  ``fault`` plants one fault, which the
comparison has to catch: ``half_batch`` leaves the second half of each
sequence's targets out of the loss; ``no_window`` lets the window layers
attend to their whole document; ``rope_unscaled`` gives the full layers
theta's plain frequencies and no attention factor (YaRN left out);
``router_unscaled`` leaves the routed part's 2.5 out.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.reference.keyevl2 import (experts, head_nll, rms_norm,
                                         positions_in_document, top_by_sort)
from chipbench.reference.phi4flash import (_is_leaf, _w, clip_and_update,
                                           ein, init_opt, silu, targets_of)

__all__ = ["make_params", "make_loss_and_grad", "init_opt",
           "clip_and_update", "count_params"]

QUERY_BLOCK = 128       # queries a block in the attention
TOKEN_BLOCK = 1024      # tokens a block in the dense layer's MLP
FAULTS = (None, "half_batch", "no_window", "rope_unscaled",
          "router_unscaled")


def dims(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    full = config["rope_parameters"]["full_attention"]
    window = config["rope_parameters"]["sliding_attention"]
    return {"H": config["hidden_size"], "d": config["head_dim"],
            "Hk": config["num_key_value_heads"],
            "heads": config["num_attention_heads_per_layer"][:layers],
            "window": [t == "sliding_attention"
                       for t in config["layer_types"][:layers]],
            "dense": [t == "dense"
                      for t in config["mlp_layer_types"][:layers]],
            "I": config["intermediate_size"],
            "F": config["moe_intermediate_size"],
            "S": config["shared_expert_intermediate_size"],
            "V": config["vocab_size"], "L": layers,
            "E": config["router_experts"], "held": config["num_experts"],
            "first": config["first_expert"],
            "K": config["num_experts_per_tok"],
            "scale": config["moe_routed_scaling_factor"],
            "eps": config["rms_norm_eps"],
            "reach": config["sliding_window"],
            "rope_full": full, "rope_window": window}


# --------------------------------------------------------------------------
# the benchmark's own weights
# --------------------------------------------------------------------------

def _scale(width):
    return {"scale": ("ones", (width,), 0.0)}


def layer_spec(c: dict, i: int) -> dict:
    h, d, heads = c["H"], c["d"], c["heads"][i]
    out = {"attn_norm": _scale(h), "mlp_norm": _scale(h),
           "wq": _w(h, heads * d), "wk": _w(h, c["Hk"] * d),
           "wv": _w(h, c["Hk"] * d), "wg": _w(h, heads),
           "wo": _w(heads * d, h)}
    if c["dense"][i]:
        return {**out, "gate": _w(h, c["I"]), "up": _w(h, c["I"]),
                "down": _w(c["I"], h)}
    expert = lambda a, b: ("normal", (c["held"], a, b), 1.0 / math.sqrt(a))
    return {**out, "router": _w(h, c["E"]),
            "shared_gate": _w(h, c["S"]), "shared_up": _w(h, c["S"]),
            "shared_down": _w(c["S"], h),
            "w_gate": expert(h, c["F"]), "w_up": expert(h, c["F"]),
            "w_down": expert(c["F"], h)}


def param_spec(config: dict) -> dict:
    """Nested dict of ``(init kind, shape, scale)`` leaves under the
    program's parameter names.  Kernels, the experts and both vocabulary
    matrices N(0, 1/fan_in) (fan_in: the hidden size for the embedding),
    scales one."""
    c = dims(config)
    h = c["H"]
    vocab = ("normal", (c["V"], h), 1.0 / math.sqrt(h))
    out = {"tok_embed": {"embedding": vocab}, "lm_head": vocab,
           "final_norm": _scale(h)}
    for i in range(c["L"]):
        out[f"layer_{i}"] = layer_spec(c, i)
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

def yarn_inv_freq(rotary: int, rope: dict):
    """YaRN's rotary frequencies as HF transformers computes them
    (``_compute_yarn_parameters``, ``truncate`` on) -> float32 [rotary / 2]:
    theta's own where a frequency turns more than ``beta_fast`` times over
    the original positions, divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, a linear ramp between the two dimensions (floor and
    ceiling) where that happens."""
    base, factor = rope["rope_theta"], rope["factor"]
    original = rope["original_max_position_embeddings"]

    def correction_dim(turns):
        return (rotary * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), rotary - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rotary // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    pos_freqs = base ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                         / rotary)
    return (1.0 / (factor * pos_freqs)) * (1.0 - extrapolation) \
        + (1.0 / pos_freqs) * extrapolation


def rotate(x, pos, rope: dict, fault=None):
    """``x`` [T, heads, d]: the first ``partial_rotary_factor`` x d
    dimensions turned, pairs ``(i, i + r / 2)``, the rest passed."""
    d = x.shape[-1]
    r = int(d * rope.get("partial_rotary_factor", 1))
    yarn = rope.get("rope_type") == "yarn" and fault != "rope_unscaled"
    freq = (yarn_inv_freq(r, rope) if yarn else rope["rope_theta"] ** (
        -jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    mscale = rope["attention_factor"] if yarn else 1.0
    angle = pos.astype(jnp.float32)[:, None] * freq
    cos = (jnp.cos(angle) * mscale)[:, None, :]
    sin = (jnp.sin(angle) * mscale)[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           axis=-1)


def allowed(q_pos, q_seg, seg, reach):
    """[blk, T] bool: the keys at or before each query, in its document,
    and with ``reach`` fewer than that many positions back."""
    k_pos = jnp.arange(seg.shape[0])[None, :]
    ok = (k_pos <= q_pos[:, None]) & (q_seg[:, None] == seg[None, :])
    if reach is not None:
        ok = ok & (q_pos[:, None] - k_pos < reach)
    return ok


def kind_of(c: dict, i: int) -> tuple:
    """Layer ``i``'s (query heads, window or not, dense or not): what its
    equations depend on beside its parameters."""
    return c["heads"][i], c["window"][i], c["dense"][i]


def attention(p, u, seg, c, kind, precision, fault=None):
    """-> the gated heads' output [T, Hq * d] and the pairs attended (real
    queries)."""
    t, d, hk = u.shape[0], c["d"], c["Hk"]
    hq, window, _ = kind
    rope = c["rope_window"] if window else c["rope_full"]
    reach = c["reach"] if window and fault != "no_window" else None
    pos = positions_in_document(seg)
    proj = lambda name, heads: ein("th,he->te", u, p[name]["kernel"],
                                   precision).reshape(t, heads, d)
    q = rotate(proj("wq", hq), pos, rope, fault).reshape(t, hk, hq // hk, d)
    k = rotate(proj("wk", hk), pos, rope, fault)
    v = proj("wv", hk)

    @partial(jax.checkpoint, prevent_cse=False)
    def queries(args):
        q_b, pos_b, seg_b = args
        mask = allowed(pos_b, seg_b, seg, reach)
        logits = ein("tkgd,skd->kgts", q_b, k, precision) / math.sqrt(d)
        soft = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
        return (ein("kgts,skd->tkgd", soft, v, precision),
                jnp.sum(mask & (seg_b > 0)[:, None]))

    blk = min(QUERY_BLOCK, t)
    cut = lambda x: x.reshape((t // blk, blk) + x.shape[1:])
    o, pairs = jax.lax.map(queries, (cut(q), cut(jnp.arange(t)), cut(seg)))
    gate = jax.nn.sigmoid(ein("th,hg->tg", u, p["wg"]["kernel"], precision))
    o = o.reshape(t, hq, d) * gate[:, :, None]
    return o.reshape(t, hq * d), jnp.sum(pairs)


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
    probs = jax.nn.softmax(ein("th,he->te", z, p["router"]["kernel"],
                               precision), axis=-1)
    chosen = top_by_sort(probs, jnp.ones_like(probs, bool), c["K"])
    total = jnp.sum(jnp.where(chosen, probs, 0.0), axis=-1, keepdims=True)
    scale = 1.0 if fault == "router_unscaled" else c["scale"]
    return jnp.where(chosen, scale * probs / total, 0.0), chosen


def layer(p, x, seg, c, kind, precision="f32", fault=None):
    """A layer of ``kind`` (`kind_of`) -> (its output [T, H], the pairs its
    attention attended, its assignments to held experts [held], or [0] where
    dense)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    u = rms_norm(p["attn_norm"], x, c["eps"])
    o, pairs = attention(p, u, seg, c, kind, precision, fault)
    h = x + ein("te,eh->th", o, p["wo"]["kernel"], precision)
    z = rms_norm(p["mlp_norm"], h, c["eps"])
    if kind[2]:
        return h + dense_mlp(p, z, precision), pairs, jnp.zeros((0,))
    weights, chosen = routing(p, z, c, precision, fault)
    shared = swiglu(z, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                    p["shared_down"]["kernel"], precision)
    held = jnp.sum(chosen[:, c["first"]:c["first"] + c["held"]], axis=0)
    return h + experts(p, z, weights, c, precision) + shared, pairs, held


def targets(tokens, seg, fault=None):
    """-> (targets [T], weights [T]): the next token of the same document;
    ``half_batch`` leaves the second half of the positions out."""
    y, w = targets_of(tokens, seg)
    if fault == "half_batch":
        w = w * (jnp.arange(w.shape[0]) < w.shape[0] // 2)
    return y, w


def batch_loss(params, tokens, seg, c, precision="f32", fault=None):
    """``tokens``, ``seg`` [B, T] -> the mean next-token cross-entropy as
    one differentiable function (tests; `make_loss_and_grad` computes the
    same a layer at a time)."""
    nll = count = 0.0
    for b in range(tokens.shape[0]):
        x = params["tok_embed"]["embedding"][tokens[b]]
        for i in range(c["L"]):
            x, _, _ = layer(params[f"layer_{i}"], x, seg[b], c, kind_of(c, i),
                            precision, fault)
        y, w = targets(tokens[b], seg[b], fault)
        nll = nll + head_nll(params["final_norm"], params["lm_head"], x, y, w,
                             c["eps"], precision)
        count = count + jnp.sum(w)
    return nll / jnp.maximum(count, 1.0)


def make_loss_and_grad(config: dict, precision: str = "f32", fault=None):
    """-> ``fn(params, tokens, seg) -> (loss, grads)``; ``fn.stats`` holds
    the last call's held assignments per routed layer and the pairs each
    attention kind attended.  Reverse mode by hand over the stack: the
    forward pass keeps each layer's input, the backward pass calls `jax.vjp`
    of one layer (which recomputes it) from the last to the first, so the
    device holds one layer's intermediates at a time beside the parameters,
    their gradients and the optimizer's moments (13.0 GB at the published
    widths)."""
    c = dims(config)
    kinds = [kind_of(c, i) for i in range(c["L"])]

    def one(kind):
        return partial(layer, c=c, kind=kind, precision=precision,
                       fault=fault)

    # one program a kind of layer, not a layer
    fwd = {k: jax.jit(one(k)) for k in set(kinds)}

    def make_bwd(kind):
        def bwd(p, x, seg, dy):
            _, pull = jax.vjp(lambda p, x: one(kind)(p, x, seg)[0], p, x)
            return pull(dy)
        return jax.jit(bwd)

    bwd = {k: make_bwd(k) for k in set(kinds)}
    head = jax.jit(jax.value_and_grad(
        lambda norm, w_head, x, y, w: head_nll(norm, w_head, x, y, w,
                                               c["eps"], precision),
        argnums=(0, 1, 2)))
    embed = jax.jit(lambda emb, tokens: emb[tokens])
    scatter = jax.jit(lambda emb, tokens, dx: jnp.zeros_like(emb).at[
        tokens].add(dx))
    add = jax.jit(lambda a, b, scale: jax.tree_util.tree_map(
        lambda x, y: x + y * scale, a, b), donate_argnums=(0,))

    def fn(params, tokens, seg):
        rows = range(tokens.shape[0])
        emb = params["tok_embed"]["embedding"]
        wanted = [targets(tokens[b], seg[b], fault) for b in rows]
        scale = 1.0 / jnp.maximum(sum(jnp.sum(w) for _, w in wanted), 1.0)
        grads, loss = {}, 0.0
        fn.stats = {"held_assignments": [], "window_pairs": 0.0,
                    "full_pairs": 0.0}

        def accumulate(name, g, by=1.0):
            grads[name] = (add(grads[name], g, by) if name in grads
                           else jax.tree_util.tree_map(lambda x: x * by, g))

        for b in rows:
            xs = [embed(emb, tokens[b])]
            for i in range(c["L"]):
                x, pairs, held = fwd[kinds[i]](params[f"layer_{i}"], xs[-1],
                                               seg[b])
                xs.append(x)
                kind = "window_pairs" if c["window"][i] else "full_pairs"
                fn.stats[kind] += float(pairs)
                if held.size:
                    fn.stats["held_assignments"].append(held)
            y, w = wanted[b]
            nll, (g_norm, g_head, dx) = head(
                params["final_norm"], params["lm_head"], xs.pop(), y, w)
            loss = loss + nll * scale
            accumulate("final_norm", g_norm, scale)
            accumulate("lm_head", g_head, scale)
            dx = dx * scale
            for i in reversed(range(c["L"])):
                dp, dx = bwd[kinds[i]](params[f"layer_{i}"], xs.pop(),
                                       seg[b], dx)
                accumulate(f"layer_{i}", dp)
            accumulate("tok_embed", {"embedding": scatter(emb, tokens[b], dx)})
        return loss, grads

    return fn
