"""Kimi-Linear-48B-A3B's decoder, as the stream encoder's ``kda_dense`` /
``kda_moe`` / ``mla_moe`` kinds carry it, in plain ``jax.numpy``: forward,
loss, gradients, and the benchmark's own weights.

The plain reference of the configuration `kimi-linear-48b-a3b`
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json,
``model_type`` ``kimi_linear``; the mechanism is arXiv:2510.26692's): float32
throughout, every matrix product at ``highest`` precision, the delta rule as
the sequential recurrence a token at a time, attention against the explicit
mask, the experts as a loop over those held; no kernel, no chunked form, no
remat policy, no flax module, nothing of ``nerrf_tpu``.  The weights are made
here from the seed (`make_params`) under the program's parameter names.

The equations (``x`` the residual; ``u = RMSNorm(x)``, eps 1e-5, learned
scale, no bias anywhere).  Layer ``n`` (counted from 1, as
``linear_attn_config`` counts) is a KDA layer where ``kda_layers`` holds it
and an MLA layer where ``full_attn_layers`` does; layers up to
``first_k_dense_replace`` end in the dense SwiGLU, the rest in experts.

* **KDA** (32 heads, ``d_k = d_v = 128``).  ``q, k, v = SiLU(conv_4(u
  W_{q,k,v}))``, each 2304 -> 4096, ``conv_4`` causal and depthwise, tap
  ``j`` steps back weighted by ``w[3 - j]``, no bias, a tap reaching across
  a document's start reading zero.  ``q``, ``k`` L2-normalised a head (``x /
  sqrt(sum x^2 + 1e-6)``), ``q`` times ``128 ** -0.5``.  ``g = -exp(A_log_h)
  softplus(u W_f_down W_f_up + dt_bias)`` (2304 -> 128 -> 4096, a channel);
  ``beta = sigmoid(u W_b)`` (2304 -> 32).  Per head, state ``S`` [128, 128]
  zeroed at a document's first token:

      S <- Diag(exp g_t) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;
      o_t = S^T q_t

  ``y = [RMSNorm_head(o) * sigmoid(u W_g_down W_g_up)] W_o`` (the head norm
  over a head's 128 values with a 128-wide scale, eps 1e-5; 4096 -> 2304);
  ``h = x + y``.
* **MLA** (32 heads; no query latent; ``mla_use_nope``).  ``q = u W_q`` as
  32 heads of 128 + 64; ``[c | k_r] = u W_kva`` (512 + 64); ``[k_nope | v]
  = RMSNorm_512(c) W_kvb`` as 32 heads of 128 + 128; ``k = [k_nope | k_r]``,
  the one ``k_r`` behind every head; nothing is rotated.  Scores ``q . k /
  sqrt(192)`` over the keys ``s <= t`` of the query's document, softmax,
  ``o = P v``; ``h = x + o W_o``.
* **Dense layer** (layer 1): ``y = h + W_down (silu(W_gate z) * W_up z)``,
  ``z = RMSNorm(h)``, width 9216.
* **Expert layer** (the rest): ``z = RMSNorm(h)``; ``s = sigmoid(z W_r)``
  over all 256 experts; ``E_t`` = the 8 of largest ``s + b`` (``b`` the
  correction bias, a constant; of equal ones the lower index); ``g_e =
  2.446 s_e / (sum_{e' in E_t} s_e' + 1e-20)``; ``y = h + sum_{e in E_t, e
  held} g_e E_e(z) + Shared(z)``, each a SwiGLU of width 1024.  The
  normalisation is over all 8 chosen, held or not; what the absent experts
  would add is left out; the shared expert is computed whole and ungated.

After the last layer RMSNorm, then ``logits = x W_head^T`` over the held rows
of the untied head; ``L`` the mean cross-entropy over the positions whose next
token is a real token of their document.  No auxiliary loss; no gradient
reaches ``b`` and `clip_and_update` leaves it as it was.

What the published ``config.json`` leaves open is listed in the
configuration file under ``assumed`` (the gates' rank, the inits of
``A_log`` and ``dt_bias``, no convolution bias, the L2 norm's eps, the NoPE
reading, the direct query, the order "decay, then update", the resets, the
head norm, the router's form and its frozen bias, tie-breaks); the
vocabulary is the 20,480 rows one of eight chips holds of each matrix, the
experts 0-7 of 256, the depth published layers 1-5 (``reduced``).
Departures of this file from a naive transcription, none of which changes a
number: the recurrence runs as an outer scan over blocks of `SCAN_BLOCK`
tokens, each behind `jax.checkpoint` (reverse mode then keeps one state a
block, where it would otherwise keep 8192 states of 2 MB); attention is
computed a block of queries at a time, the experts one at a time over all
tokens under the routing weight (zero where the expert was not chosen), the
dense MLP a block of tokens at a time, the loss a block of positions at a
time, each behind `jax.checkpoint`; the gradient is taken one layer at a
time by hand (`make_loss_and_grad`; a test holds it equal to `jax.grad` of
the whole).

``precision`` chooses how matrix products are computed (`f32`: the
reference; `bf16`, `fp8`: the lower-precision controls, both operands of
every product rounded first; the recurrence's own products stay float32).
``fault`` plants one fault, which the comparison has to catch:
``half_batch`` leaves the second half of each sequence's targets out of the
loss; ``no_reset`` carries the delta rule's state across document starts;
``head_decay`` averages each head's decay over its 128 channels (the
scalar-gated rule); ``no_delta`` writes ``S += beta k v^T`` without the
``S^T k`` correction; ``router_unscaled`` leaves the routed part's 2.446 out.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.reference.glm47flash import clip_and_update
from chipbench.reference.keyevl2 import (allowed_keys, experts, head_nll,
                                         rms_norm, top_by_sort)
from chipbench.reference.phi4flash import (_is_leaf, _w, ein, init_opt, silu,
                                           targets_of)

__all__ = ["make_params", "make_loss_and_grad", "loss_and_grad", "init_opt",
           "clip_and_update", "count_params"]

QUERY_BLOCK = 128       # queries a block in the attention
TOKEN_BLOCK = 1024      # tokens a block in the dense layer's MLP
SCAN_BLOCK = 64         # tokens a block of the recurrence's reverse pass
L2_EPS = 1e-6
BIAS_STD = 0.1          # the seeded correction bias: N(0, 0.1^2)
FAULTS = (None, "half_batch", "no_reset", "head_decay", "no_delta",
          "router_unscaled")


def dims(config: dict) -> dict:
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
            "first": config["first_expert"],
            "K": config["num_experts_per_token"],
            "scale": config["routed_scaling_factor"],
            "eps": config["rms_norm_eps"]}


# --------------------------------------------------------------------------
# the benchmark's own weights
# --------------------------------------------------------------------------

def _scale(width):
    return {"scale": ("ones", (width,), 0.0)}


def layer_spec(c: dict, i: int) -> dict:
    h = c["H"]
    if c["delta"][i]:
        width = c["kh"] * c["dk"]
        conv = ("normal", (c["conv"], width), 1.0 / math.sqrt(c["conv"]))
        out = {"wq": _w(h, width), "wk": _w(h, width), "wv": _w(h, width),
               "q_conv": conv, "k_conv": conv, "v_conv": conv,
               "A_log": ("a_log", (c["kh"],), 0.0),
               "dt_bias": ("dt_bias", (width,), 0.0),
               "f_a": _w(h, c["rank"]), "f_b": _w(c["rank"], width),
               "wb": _w(h, c["kh"]), "o_norm": ("ones", (c["dk"],), 0.0),
               "g_a": _w(h, c["rank"]), "g_b": _w(c["rank"], width),
               "wo": _w(width, h)}
    else:
        heads = c["heads"]
        out = {"wq": _w(h, heads * (c["nope"] + c["rope"])),
               "wkv_a": _w(h, c["rkv"] + c["rope"]),
               "kv_a_norm": _scale(c["rkv"]),
               "wkv_b": _w(c["rkv"], heads * (c["nope"] + c["dv"])),
               "wo": _w(heads * c["dv"], h)}
    out.update(attn_norm=_scale(h), mlp_norm=_scale(h))
    if c["dense"][i]:
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
    the convolutions N(0, 1/taps), ``A_log`` log U(1, 16), ``dt_bias``
    softplus^-1 of a log-uniform draw from [0.001, 0.1], the correction bias
    N(0, 0.1^2), scales one."""
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
        out = []
        for k, (kind, shape, scale) in zip(
                jax.random.split(key, len(leaves)), leaves):
            if kind == "normal":
                out.append(scale * jax.random.normal(k, shape, jnp.float32))
            elif kind == "a_log":
                out.append(jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0)))
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                out.append(dt + jnp.log(-jnp.expm1(-dt)))
            else:
                out.append(jnp.full(shape, float(kind == "ones"),
                                    jnp.float32))
        return out

    return jax.tree_util.tree_unflatten(treedef, build(key))


# --------------------------------------------------------------------------
# one layer, one sequence [T]
# --------------------------------------------------------------------------

def firsts(seg):
    """[T] bool: a document's first token (the id changes, and at 0)."""
    return jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])


def short_conv(x, w, seg):
    """Causal depthwise convolution, ``w`` [K, D], tap ``j`` steps back
    weighted by ``w[K - 1 - j]``, zero across a document's start; then
    SiLU."""
    k = w.shape[0]
    out = x * w[k - 1]
    for j in range(1, k):
        same = jnp.concatenate([jnp.zeros((j,), bool), seg[j:] == seg[:-j]])
        past = jnp.concatenate([jnp.zeros((j,) + x.shape[1:]), x[:-j]])
        out = out + jnp.where(same[:, None], past, 0.0) * w[k - 1 - j]
    return silu(out)


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta, seg, fault=None):
    """The sequential recurrence: ``q``, ``k`` [T, H, dk], ``v`` [T, H, dv],
    ``g`` [T, H, dk], ``beta`` [T, H] -> o [T, H, dv], a token at a time."""
    t, h, dk = q.shape
    keep = (jnp.ones((t,)) if fault == "no_reset"
            else jnp.where(firsts(seg), 0.0, 1.0))

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t, keep_t = inp
        s = s * keep_t * jnp.exp(g_t)[:, :, None]
        if fault == "no_delta":
            write = v_t
        else:
            write = v_t - jnp.sum(s * k_t[:, :, None], axis=1)
        s = s + b_t[:, None, None] * k_t[:, :, None] * write[:, None, :]
        return s, jnp.sum(s * q_t[:, :, None], axis=1)

    @partial(jax.checkpoint, prevent_cse=False)
    def block(s, inp):
        return jax.lax.scan(step, s, inp)

    blk = math.gcd(SCAN_BLOCK, t)
    cut = lambda x: x.reshape((t // blk, blk) + x.shape[1:])
    _, o = jax.lax.scan(block, jnp.zeros((h, dk, v.shape[-1])),
                        tuple(cut(x) for x in (q, k, v, g, beta, keep)))
    return o.reshape(t, h, v.shape[-1])


def kda(p, u, seg, c, precision, fault=None):
    """-> the KDA block's output [T, H] (before the residual)."""
    t, heads, dk = u.shape[0], c["kh"], c["dk"]
    proj = lambda name, x: ein("th,he->te", x, p[name]["kernel"], precision)
    split = lambda x: x.reshape(t, heads, -1)
    q = unit(split(short_conv(proj("wq", u), p["q_conv"], seg))) * dk ** -0.5
    k = unit(split(short_conv(proj("wk", u), p["k_conv"], seg)))
    v = split(short_conv(proj("wv", u), p["v_conv"], seg))
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        split(proj("f_b", proj("f_a", u)) + p["dt_bias"]))
    if fault == "head_decay":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(proj("wb", u))
    o = delta_rule(q, k, v, g, beta, seg, fault)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + c["eps"]) * p["o_norm"]
    gate = jax.nn.sigmoid(proj("g_b", proj("g_a", u)))
    return ein("te,eh->th", o.reshape(t, -1) * gate, p["wo"]["kernel"],
               precision)


def mla(p, u, seg, c, precision):
    """-> the MLA block's output [T, H] (before the residual)."""
    t, heads, nope = u.shape[0], c["heads"], c["nope"]
    proj = lambda name, x: ein("th,he->te", x, p[name]["kernel"], precision)
    q = proj("wq", u).reshape(t, heads, nope + c["rope"])
    latent = proj("wkv_a", u)
    kv = proj("wkv_b", rms_norm(p["kv_a_norm"], latent[:, :c["rkv"]],
                                c["eps"])).reshape(t, heads, nope + c["dv"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        latent[:, None, c["rkv"]:], (t, heads, c["rope"]))], axis=-1)
    v = kv[..., nope:]

    @partial(jax.checkpoint, prevent_cse=False)
    def queries(args):
        q_b, pos_b, seg_b = args
        logits = ein("thd,shd->hts", q_b, k, precision) / math.sqrt(
            nope + c["rope"])
        soft = jax.nn.softmax(jnp.where(allowed_keys(pos_b, seg_b, seg),
                                        logits, -1e30), axis=-1)
        return ein("hts,shd->thd", soft, v, precision)

    blk = math.gcd(QUERY_BLOCK, t)
    cut = lambda x: x.reshape((t // blk, blk) + x.shape[1:])
    o = jax.lax.map(queries, (cut(q), cut(jnp.arange(t)), cut(seg)))
    return ein("te,eh->th", o.reshape(t, heads * c["dv"]), p["wo"]["kernel"],
               precision)


def swiglu(z, w_gate, w_up, w_down, precision):
    return ein("tf,fh->th", silu(ein("th,hf->tf", z, w_gate, precision))
               * ein("th,hf->tf", z, w_up, precision), w_down, precision)


def dense_mlp(p, z, precision):
    @partial(jax.checkpoint, prevent_cse=False)
    def tokens(z):
        return swiglu(z, p["gate"]["kernel"], p["up"]["kernel"],
                      p["down"]["kernel"], precision)

    t = z.shape[0]
    blk = math.gcd(TOKEN_BLOCK, t)
    return jax.lax.map(tokens, z.reshape(t // blk, blk, -1)).reshape(z.shape)


def routing(p, z, c, precision, fault=None):
    """-> (weights [T, E]: ``g_e`` where expert e is among the token's K
    chosen, else 0; the chosen experts [T, E] bool)."""
    scores = jax.nn.sigmoid(ein("th,he->te", z, p["router"]["kernel"],
                                precision))
    biased = scores + jax.lax.stop_gradient(p["router_bias"])
    chosen = top_by_sort(biased, jnp.ones_like(scores, bool), c["K"])
    total = jnp.sum(jnp.where(chosen, scores, 0.0), axis=-1, keepdims=True)
    scale = 1.0 if fault == "router_unscaled" else c["scale"]
    return (jnp.where(chosen, scale * scores / (total + 1e-20), 0.0),
            chosen)


def layer(p, x, seg, c, i, precision="f32", fault=None):
    """Layer ``i`` -> (its output [T, H], its assignments to held experts
    [held], or [0] where dense)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    u = rms_norm(p["attn_norm"], x, c["eps"])
    mix = (kda(p, u, seg, c, precision, fault) if c["delta"][i]
           else mla(p, u, seg, c, precision))
    h = x + mix
    z = rms_norm(p["mlp_norm"], h, c["eps"])
    if c["dense"][i]:
        return h + dense_mlp(p, z, precision), jnp.zeros((0,))
    weights, chosen = routing(p, z, c, precision, fault)
    shared = swiglu(z, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                    p["shared_down"]["kernel"], precision)
    held = jnp.sum(chosen[:, c["first"]:c["first"] + c["held"]], axis=0)
    return h + experts(p, z, weights, c, precision) + shared, held


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
            x, _ = layer(params[f"layer_{i}"], x, seg[b], c, i, precision,
                         fault)
        y, w = targets(tokens[b], seg[b], fault)
        nll = nll + head_nll(params["final_norm"], params["lm_head"], x, y, w,
                             c["eps"], precision)
        count = count + jnp.sum(w)
    return nll / jnp.maximum(count, 1.0)


def _kind(c: dict, i: int) -> tuple:
    """Layer ``i``'s (delta rule or not, dense or not): what its equations
    depend on beside its parameters."""
    return c["delta"][i], c["dense"][i]


def make_loss_and_grad(config: dict, precision: str = "f32", fault=None):
    """-> ``fn(params, tokens, seg) -> (loss, grads)``; ``fn.stats`` holds
    the last call's held assignments per routed layer.  Reverse mode by
    hand over the stack: the forward pass keeps each layer's input, the
    backward pass calls `jax.vjp` of one layer (which recomputes it) from
    the last to the first, so the device holds one layer's intermediates at
    a time beside the parameters, their gradients and the optimizer's
    moments (9.6 GB at the published widths).  One program a kind of
    layer."""
    c = dims(config)
    index = {}
    for i in range(c["L"]):
        index.setdefault(_kind(c, i), i)

    def one(kind):
        return partial(layer, c=c, i=index[kind], precision=precision,
                       fault=fault)

    fwd = {k: jax.jit(one(k)) for k in index}

    def make_bwd(kind):
        def bwd(p, x, seg, dy):
            _, pull = jax.vjp(lambda p, x: one(kind)(p, x, seg)[0], p, x)
            return pull(dy)
        return jax.jit(bwd)

    bwd = {k: make_bwd(k) for k in index}
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
        fn.stats = {"held_assignments": []}

        def accumulate(name, g, by=1.0):
            grads[name] = (add(grads[name], g, by) if name in grads
                           else jax.tree_util.tree_map(lambda x: x * by, g))

        for b in rows:
            xs = [embed(emb, tokens[b])]
            for i in range(c["L"]):
                x, held = fwd[_kind(c, i)](params[f"layer_{i}"], xs[-1],
                                           seg[b])
                xs.append(x)
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
                dp, dx = bwd[_kind(c, i)](params[f"layer_{i}"], xs.pop(),
                                          seg[b], dx)
                accumulate(f"layer_{i}", dp)
            accumulate("tok_embed", {"embedding": scatter(emb, tokens[b], dx)})
        return loss, grads

    return fn


def loss_and_grad(params, tokens, seg, config: dict, precision: str = "f32",
                  fault=None):
    """One call of `make_loss_and_grad`'s function."""
    return make_loss_and_grad(config, precision, fault)(params, tokens, seg)
