"""Keye-VL-2.0-30B-A3B's decoder, as the stream encoder's ``dsa_moe`` kind
carries it, in plain ``jax.numpy``: forward, the two losses, gradients, and
the benchmark's own weights.

The plain reference of the configuration `keye-vl2-30b-a3b`
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json,
``model_type`` ``KeyeVL2``, the language model's keys; its sparse attention
is the DeepSeek-Sparse-Attention form): float32 throughout, every matrix
product at ``highest`` precision, dense index scores, a sort-based top-k,
attention against the explicit selection mask, the experts as a loop over
those held; no kernel, no flax module, nothing of ``nerrf_tpu``.  The weights
are made here from the seed (`make_params`) under the program's parameter
names.

The equations (``x`` the residual, ``t`` a query, ``s <= t`` a key of the
same packed document).  Every layer:

* ``u = RMSNorm(x)``; ``q = W_q u`` (32 heads of 128), ``k = W_k u``, ``v =
  W_v u`` (4 heads of 128); RMSNorm over each head of ``q`` and ``k``; rotary
  embedding (theta 1e7, rotate-half) on ``q`` and ``k`` with the position
  counted inside the document.
* the indexer, on ``stop_gradient(u)``: ``qI = W_qI u`` (16 heads of 64),
  ``kI = LayerNorm(W_kI u)`` (one head of 64), ``w = W_w u / sqrt(16 * 64)``;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; ``S_t`` = the ``min(t +
  1, 2048)`` keys of largest ``I[t, s]`` (of equal scores the earlier key).
* ``o_t = sum_{s in S_t} softmax_{S_t}(q_t . k_s / sqrt(128)) v_s`` per head,
  a key-value head serving 8 query heads; ``h = x + W_o o``.
* ``z = RMSNorm(h)``; ``g = softmax(W_r z)`` over 128 experts; ``E_t`` = the 8
  largest (of equal ones the lower index); ``p_e = g_e / sum_{e' in E_t}
  g_e'``; ``y = h + sum_{e in E_t, e held} p_e W_down,e (silu(W_gate,e z) *
  W_up,e z)``.  The renormalisation is over all 8 chosen, held or not; what
  the absent experts would add is left out.

After the last layer RMSNorm, then ``logits = x W_head^T`` over the held rows
of the untied head.  The loss is the mean next-token cross-entropy over the
targets that are real tokens of their input's document, plus, with weight
1, the indexer's term summed over layers: ``mean_t KL(P_t || softmax_{S_t}
(I[t, .]))`` over real tokens, ``P_t`` the attention's probabilities averaged
over the 32 heads, under `stop_gradient`; the selection passes no gradient.

What the published ``config.json`` leaves open is listed in the
configuration file under ``assumed`` (the per-head norms, the rotary
convention and its one-dimensional reading, the indexer's normalisation,
scale and lack of rotary embedding, its loss); the vocabulary is the 18,992
rows one of eight chips holds of each matrix, the experts 0-15 of 128, the
depth six of 48 (``reduced``).  Departures of this file from a naive
transcription, none of which changes a number: attention is computed a block
of queries at a time, the experts one at a time over all tokens under the
routing weight (zero where the expert was not chosen), the loss a block of
positions at a time, each behind `jax.checkpoint`, and the gradient is taken
one layer at a time by hand (`make_loss_and_grad`; a test holds it equal to
`jax.grad` of the whole).

``precision`` chooses how matrix products are computed (`f32`: the
reference; `bf16`, `fp8`: the lower-precision controls, both operands of
every product rounded first).  ``fault="renormalise_over_held"`` plants the
fault the comparison has to catch: the routing weights are renormalised over
the chosen experts that are held here, not over all 8 chosen.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.reference.phi4flash import (_is_leaf, _w, clip_and_update,
                                           ein, init_opt, silu, targets_of)

__all__ = ["make_params", "make_loss_and_grad", "init_opt",
           "clip_and_update", "count_params", "selections"]

QUERY_BLOCK = 128       # queries a block in the indexer and the attention
LOSS_BLOCK = 1024       # positions a block in the loss


def dims(config: dict) -> dict:
    sa = config["sa_config"]
    return {"H": config["hidden_size"], "Hq": config["num_attention_heads"],
            "Hk": config["num_key_value_heads"], "d": config["head_dim"],
            "F": config["moe_intermediate_size"], "V": config["vocab_size"],
            "L": config["num_hidden_layers"], "E": config["num_local_experts"],
            "held": config["num_experts"], "first": config["first_expert"],
            "K": config["num_experts_per_tok"], "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"]), "J": sa["indexer_num_heads"],
            "e": sa["indexer_head_dim"], "topk": sa["topk"],
            "kl_weight": config["assumed"]["indexer_loss"]["value"]}


# --------------------------------------------------------------------------
# the benchmark's own weights
# --------------------------------------------------------------------------

def _scale(width):
    return {"scale": ("ones", (width,), 0.0)}


def param_spec(config: dict) -> dict:
    """Nested dict of ``(init kind, shape, scale)`` leaves under the
    program's parameter names.  Kernels, the experts and both vocabulary
    matrices N(0, 1/fan_in) (fan_in: the hidden size for the embedding),
    scales one, the indexer's LayerNorm bias zero."""
    c = dims(config)
    h, f = c["H"], c["F"]
    vocab = ("normal", (c["V"], h), 1.0 / math.sqrt(h))
    out = {"tok_embed": {"embedding": vocab}, "lm_head": vocab,
           "final_norm": _scale(h)}
    expert = lambda a, b: ("normal", (c["held"], a, b), 1.0 / math.sqrt(a))
    for i in range(c["L"]):
        out[f"layer_{i}"] = {
            "attn_norm": _scale(h), "moe_norm": _scale(h),
            "q_norm": _scale(c["d"]), "k_norm": _scale(c["d"]),
            "wq": _w(h, c["Hq"] * c["d"]), "wk": _w(h, c["Hk"] * c["d"]),
            "wv": _w(h, c["Hk"] * c["d"]), "wo": _w(c["Hq"] * c["d"], h),
            "index_q": _w(h, c["J"] * c["e"]), "index_k": _w(h, c["e"]),
            "index_w": _w(h, c["J"]),
            "index_k_norm": {**_scale(c["e"]),
                             "bias": ("zeros", (c["e"],), 0.0)},
            "router": _w(h, c["E"]),
            "w_gate": expert(h, f), "w_up": expert(h, f),
            "w_down": expert(f, h)}
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
        out = []
        for k, (kind, shape, scale) in zip(
                jax.random.split(key, len(leaves)), leaves):
            if kind == "normal":
                out.append(scale * jax.random.normal(k, shape, jnp.float32))
            else:
                out.append(jnp.full(shape, float(kind == "ones"),
                                    jnp.float32))
        return out

    return jax.tree_util.tree_unflatten(treedef, build(key))


# --------------------------------------------------------------------------
# one layer, one sequence [T]
# --------------------------------------------------------------------------

def rms_norm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * p["scale"]


def layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def positions_in_document(seg):
    idx = jnp.arange(seg.shape[0])
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    return idx - jax.lax.cummax(jnp.where(first, idx, 0))


def rope(x, pos, theta):
    """``x`` [T, heads, d]: the pair (i, i + d/2) turned by ``pos *
    theta^(-2i/d)``."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = pos.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def top_by_sort(scores, allowed, k):
    """bool like ``scores`` [..., N]: per row the ``k`` allowed entries of
    largest score (all of them where fewer are allowed), ties to the lower
    index.  A stable sort of the negated scores, then each entry's rank."""
    scores = jnp.where(scores == 0, 0.0, scores)        # -0 is 0
    order = jnp.argsort(jnp.where(allowed, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < k) & allowed


def indexer_inputs(p, u, c, precision):
    """-> (qI [T, J, e], kI [T, e], w [T, J])."""
    qi = ein("th,he->te", u, p["index_q"]["kernel"], precision).reshape(
        u.shape[0], c["J"], c["e"])
    ki = layer_norm(p["index_k_norm"], ein(
        "th,he->te", u, p["index_k"]["kernel"], precision), c["eps"])
    wi = ein("th,hj->tj", u, p["index_w"]["kernel"], precision) \
        / math.sqrt(c["J"] * c["e"])
    return qi, ki, wi


def index_scores(qi, ki, wi, precision):
    """A block of queries against every key -> I [blk, T]."""
    return jnp.sum(jax.nn.relu(ein("tje,se->tjs", qi, ki, precision))
                   * wi[:, :, None], axis=1)


def allowed_keys(q_pos, q_seg, seg):
    """[blk, T] bool: the keys at or before each query, in its document."""
    return (jnp.arange(seg.shape[0])[None, :] <= q_pos[:, None]) \
        & (q_seg[:, None] == seg[None, :])


def attention(p, u, seg, c, precision):
    """-> (o [T, Hq * d], the summed KL and the selected pairs, both over
    real queries)."""
    t = u.shape[0]
    hq, hk, d = c["Hq"], c["Hk"], c["d"]
    pos = positions_in_document(seg)
    proj = lambda name, heads, width: ein(
        "th,he->te", u, p[name]["kernel"], precision).reshape(t, heads, width)
    q = rope(rms_norm(p["q_norm"], proj("wq", hq, d), c["eps"]), pos,
             c["theta"]).reshape(t, hk, hq // hk, d)
    k = rope(rms_norm(p["k_norm"], proj("wk", hk, d), c["eps"]), pos,
             c["theta"])
    v = proj("wv", hk, d)
    qi, ki, wi = indexer_inputs(p, jax.lax.stop_gradient(u), c, precision)
    every = jnp.arange(t)

    @partial(jax.checkpoint, prevent_cse=False)
    def queries(args):
        q_b, qi_b, wi_b, pos_b, seg_b = args
        allowed = allowed_keys(pos_b, seg_b, seg)
        index = index_scores(qi_b, ki, wi_b, precision)           # [blk, T]
        chosen = top_by_sort(jax.lax.stop_gradient(index), allowed, c["topk"])
        logits = ein("tkgd,skd->kgts", q_b, k, precision) / math.sqrt(d)
        soft = jax.nn.softmax(jnp.where(chosen, logits, -1e30), axis=-1)
        o = ein("kgts,skd->tkgd", soft, v, precision)
        target = jax.lax.stop_gradient(jnp.mean(soft, axis=(0, 1)))
        log_q = jax.nn.log_softmax(jnp.where(chosen, index, -1e30), axis=-1)
        kl = jnp.sum(jnp.where(chosen, jax.scipy.special.xlogy(
            target, target) - target * log_q, 0.0), axis=-1)
        return (o, jnp.sum(jnp.where(seg_b > 0, kl, 0.0)),
                jnp.sum(chosen & (seg_b > 0)[:, None]))

    blk = min(QUERY_BLOCK, t)
    cut = lambda x: x.reshape((t // blk, blk) + x.shape[1:])
    o, kl, pairs = jax.lax.map(queries, (cut(q), cut(qi), cut(wi),
                                         cut(every), cut(seg)))
    return o.reshape(t, hq * d), jnp.sum(kl), jnp.sum(pairs)


def routing(p, z, c, precision, fault=None):
    """-> weights [T, E]: ``p_e`` where expert e is among the token's K
    best, else 0."""
    gates = jax.nn.softmax(ein("th,he->te", z, p["router"]["kernel"],
                               precision), axis=-1)
    chosen = top_by_sort(gates, jnp.ones_like(gates, bool), c["K"])
    over = chosen
    if fault == "renormalise_over_held":
        here = (jnp.arange(c["E"]) >= c["first"]) \
            & (jnp.arange(c["E"]) < c["first"] + c["held"])
        over = chosen & here
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    total = jnp.sum(jnp.where(over, gates, 0.0), axis=-1, keepdims=True)
    return jnp.where(chosen, gates / jnp.maximum(total, 1e-30), 0.0)


def experts(p, z, weights, c, precision):
    """The held experts' part: a loop over them, each over all tokens under
    its routing weight."""
    @partial(jax.checkpoint, prevent_cse=False)
    def one(args):
        w_gate, w_up, w_down, weight = args
        mid = silu(ein("th,hf->tf", z, w_gate, precision)) \
            * ein("th,hf->tf", z, w_up, precision)
        return ein("tf,fh->th", mid, w_down, precision) * weight[:, None]

    held = weights[:, c["first"]:c["first"] + c["held"]].T       # [held, T]
    return jnp.sum(jax.lax.map(one, (p["w_gate"], p["w_up"], p["w_down"],
                                     held)), axis=0)


def layer(p, x, seg, c, precision, fault):
    """One layer -> (its output [T, H], its summed KL over real queries,
    its selected pairs, its assignments to held experts [held])."""
    u = rms_norm(p["attn_norm"], x, c["eps"])
    o, kl, pairs = attention(p, u, seg, c, precision)
    h = x + ein("te,eh->th", o, p["wo"]["kernel"], precision)
    z = rms_norm(p["moe_norm"], h, c["eps"])
    weights = routing(p, z, c, precision, fault)
    counts = jnp.sum(weights[:, c["first"]:c["first"] + c["held"]] > 0,
                     axis=0)
    return h + experts(p, z, weights, c, precision), kl, pairs, counts


def head_nll(final_norm, head, x, y, w, eps, precision):
    """The final RMSNorm, the untied head and the summed cross-entropy of
    one sequence's targets, a block of positions at a time."""
    @partial(jax.checkpoint, prevent_cse=False)
    def positions(args):
        x, y, w = args
        logits = ein("th,vh->tv", rms_norm(final_norm, x, eps), head,
                     precision)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * w)

    t = x.shape[0]
    blk = min(LOSS_BLOCK, t)
    cut = lambda v: v.reshape((t // blk, blk) + v.shape[1:])
    return jnp.sum(jax.lax.map(positions, (cut(x), cut(y), cut(w))))


def batch_loss(params, tokens, seg, c, precision="f32", fault=None):
    """``tokens``, ``seg`` [B, T] -> the loss (next token + the indexer's
    term) as one differentiable function (tests; `make_loss_and_grad`
    computes the same a layer at a time)."""
    nll = kl = count = real = 0.0
    for b in range(tokens.shape[0]):
        x = params["tok_embed"]["embedding"][tokens[b]]
        for i in range(c["L"]):
            x, kl_i, _, _ = layer(params[f"layer_{i}"], x, seg[b], c,
                                  precision, fault)
            kl = kl + kl_i
        y, w = targets_of(tokens[b], seg[b])
        nll = nll + head_nll(params["final_norm"], params["lm_head"], x, y, w,
                             c["eps"], precision)
        count, real = count + jnp.sum(w), real + jnp.sum(seg[b] > 0)
    return nll / jnp.maximum(count, 1.0) \
        + c["kl_weight"] * kl / jnp.maximum(real, 1)


def make_loss_and_grad(config: dict, precision: str = "f32", fault=None):
    """-> ``fn(params, tokens, seg) -> (loss, grads)``; ``fn.stats`` holds
    the last call's selected pairs and held assignments per layer.  Reverse
    mode by hand over the stack: the forward pass keeps each layer's input,
    the backward pass calls `jax.vjp` of one layer (which recomputes it)
    from the last to the first, so the device holds one layer's
    intermediates at a time beside the parameters, their gradients and the
    optimizer's moments (10.5 GB at the published widths)."""
    c = dims(config)
    one = partial(layer, c=c, precision=precision, fault=fault)
    fwd = jax.jit(one)

    @jax.jit
    def bwd(p, x, seg, dy, dkl):
        _, pull = jax.vjp(lambda p, x: one(p, x, seg)[:2], p, x)
        return pull((dy, dkl))

    head = jax.jit(jax.value_and_grad(
        lambda norm, w_head, x, y, w: head_nll(norm, w_head, x, y, w,
                                               c["eps"], precision),
        argnums=(0, 1, 2)))
    embed = jax.jit(lambda emb, tokens: emb[tokens])
    scatter = jax.jit(lambda emb, tokens, dx, scale: jnp.zeros_like(emb).at[
        tokens].add(dx * scale))
    add = jax.jit(lambda a, b, scale: jax.tree_util.tree_map(
        lambda x, y: x + y * scale, a, b), donate_argnums=(0,))

    def fn(params, tokens, seg):
        targets = [targets_of(tokens[b], seg[b])
                   for b in range(tokens.shape[0])]
        scale = 1.0 / jnp.maximum(sum(jnp.sum(w) for _, w in targets), 1.0)
        kl_scale = c["kl_weight"] / jnp.maximum(jnp.sum(seg > 0), 1)
        grads, loss = {}, 0.0
        fn.stats = {"selected_pairs": [], "held_assignments": []}

        def accumulate(name, g, by):
            grads[name] = (add(grads[name], g, by) if name in grads
                           else jax.tree_util.tree_map(lambda x: x * by, g))

        for b in range(tokens.shape[0]):
            xs = [embed(params["tok_embed"]["embedding"], tokens[b])]
            for i in range(c["L"]):
                x, kl, pairs, counts = fwd(params[f"layer_{i}"], xs[-1],
                                           seg[b])
                xs.append(x)
                loss = loss + kl * kl_scale
                fn.stats["selected_pairs"].append(pairs)
                fn.stats["held_assignments"].append(counts)
            y, w = targets[b]
            nll, (g_norm, g_head, dx) = head(
                params["final_norm"], params["lm_head"], xs.pop(), y, w)
            loss = loss + nll * scale
            accumulate("final_norm", g_norm, scale)
            accumulate("lm_head", g_head, scale)
            for i in reversed(range(c["L"])):
                # dx carries the head's 1 / count from here on
                dp, dx = bwd(params[f"layer_{i}"], xs.pop(), seg[b],
                             dx * (scale if i == c["L"] - 1 else 1.0),
                             kl_scale)
                accumulate(f"layer_{i}", dp, 1.0)
            accumulate("tok_embed", {"embedding": scatter(
                params["tok_embed"]["embedding"], tokens[b], dx, 1.0)}, 1.0)
        return loss, grads

    return fn


def selections(params, tokens, seg, config: dict, precision: str = "f32"):
    """One sequence ``tokens``, ``seg`` [T] -> per layer (the chosen experts
    [T, E] bool, the chosen keys [T, T] bool): what the diagnostic that
    counts how often program and reference choose alike reads."""
    c = dims(config)

    @jax.jit
    def one(p, x):
        u = rms_norm(p["attn_norm"], x, c["eps"])
        t = u.shape[0]
        qi, ki, wi = indexer_inputs(p, u, c, precision)

        def rows(args):
            qi_b, wi_b, pos_b, seg_b = args
            return top_by_sort(index_scores(qi_b, ki, wi_b, precision),
                               allowed_keys(pos_b, seg_b, seg), c["topk"])

        blk = min(QUERY_BLOCK, t)
        cut = lambda v: v.reshape((t // blk, blk) + v.shape[1:])
        keys = jax.lax.map(rows, (cut(qi), cut(wi), cut(jnp.arange(t)),
                                  cut(seg))).reshape(t, t)
        h = x + ein("te,eh->th", attention(p, u, seg, c, precision)[0],
                    p["wo"]["kernel"], precision)
        z = rms_norm(p["moe_norm"], h, c["eps"])
        weights = routing(p, z, c, precision)
        return h + experts(p, z, weights, c, precision), weights > 0, keys

    x = params["tok_embed"]["embedding"][tokens]
    out = []
    for i in range(c["L"]):
        x, chosen_experts, keys = one(params[f"layer_{i}"], x)
        out.append((chosen_experts, keys))
    return out
