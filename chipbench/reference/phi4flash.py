"""The decoder-hybrid-decoder stream encoder in plain ``jax.numpy``: forward,
next-token loss, gradients, and the benchmark's own weights.

The plain reference of the configuration `phi4-mini-flash`
(Phi-4-mini-flash-reasoning's decoder, SambaY, arXiv:2507.06607; its
differential attention arXiv:2410.05258; its Mamba-1 mixer arXiv:2312.00752):
float32 throughout, every matrix product at ``highest`` precision, no kernel,
no flax module, nothing of ``nerrf_tpu``.  The weights are made here from
the seed (`make_params`) under the program's parameter names, so that both
sides are handed the same arrays and neither takes anything the other made.

The equations.  Pre-norm throughout: ``h = x + Mixer_i(LN(x))``, ``y = h +
MLP(LN(h))``; LayerNorm affine with bias, eps 1e-5; ``MLP(u) =
W_down(silu(W_gate u) * W_up u)``, no bias.  No positional encoding.  Final
LayerNorm, then ``logits = x E^T`` with ``E`` the held rows of the tied
embedding.  Mixers by kind:

* ``mamba``: ``[x, z] = W_in u``; ``x = silu(conv(x) + b)``, a causal
  depthwise convolution over 4 steps; ``[d, B, C] = W_x x``; ``D_t =
  softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(D_t A) *
  s_{t-1} + (D_t x_t) B_t^T``; ``y_t = s_t C_t + D * x_t``; ``out = W_out(y *
  silu(z))``.  At a document's first token ``s`` and the convolution's
  history are zero.  The last ``mamba`` layer before the ``full`` layer also
  hands ``m = y`` down the stack.
* ``swa``: differential attention, causal, inside the window (a key is seen
  while ``0 <= t_q - t_k < window``), within a document.
* ``full``: the same without the window; hands its projected ``K, V`` down.
* ``cross``: its own ``W_q``, ``W_o`` only; attends causally, within a
  document, to the ``full`` layer's ``K, V``.
* differential attention: query heads in adjacent pairs ``(2p, 2p + 1)``,
  key heads likewise, a key-value pair serving ``heads / kv_heads`` query
  pairs; ``A = softmax(Q1 K1^T / sqrt(d)) - lam * softmax(Q2 K2^T /
  sqrt(d))``, ``O = A [V1, V2]``, RMSNorm over the ``2 d`` of each pair with
  a learned scale, times ``1 - lam_init``; ``lam = exp(lq1 . lk1) - exp(lq2 .
  lk2) + lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 * layer_index)`` with
  the layer's index in the *published* stack.
* ``gmu``: ``out = W_out(m * silu(W_in u))``.

The loss is the mean next-token cross-entropy over the targets that are
real tokens of the same document as their input (a document's first token
is never a target, padding never counts).

Departures from the published model, all shared with the program: the
projections of the attention layers carry no bias (the released code's may:
not checked, no network here); Mamba's ``d_state``, ``d_conv``, ``expand``
and ``dt_rank`` are the family's defaults (the configuration lists them
under ``assumed``); the vocabulary is the 25,008 rows one of eight chips
holds and the depth is cut to six layers (``reduced``).  Departures of this
file from a naive transcription, none of which changes a number: the scan's
state is kept ``[d_state, d_inner]`` (channels last), attention is computed
a block of queries at a time against an explicit mask, the MLP a block of
tokens and the loss a block of positions at a time, each such block behind
`jax.checkpoint`, and the gradient is taken one layer at a time by hand
(`make_loss_and_grad`; a test holds it equal to `jax.grad` of the whole), so
that the whole fits on the chip beside its own optimizer state.

``precision`` chooses how matrix products are computed (`f32`: the
reference; `bf16`, `fp8`: the lower-precision controls, both operands of
every product rounded first).  ``fault="scan_ignores_documents"`` plants the
fault the comparison has to catch: the scan's state is not reset where a
document starts.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.reference import adamw
from chipbench.reference.nerrfnet import _round_operand

LN_EPS = 1e-5
QUERY_BLOCK = 128       # queries a block in attention
TOKEN_BLOCK = 1024      # tokens a block in the MLP
LOSS_BLOCK = 1024       # positions a block in the loss
SCAN_BLOCK = 256        # steps a block in the scan's reverse pass
HIGHEST = jax.lax.Precision.HIGHEST


def dims(config: dict) -> dict:
    """The widths the equations need, from the configuration file: the
    published keys at its top level and the sizes it lists as assumed."""
    a = {k: v["value"] for k, v in config["assumed"].items()
         if isinstance(v, dict) and "value" in v}
    h = config["hidden_size"]
    return {"H": h, "Hq": config["num_attention_heads"],
            "Hk": config["num_key_value_heads"],
            "d": h // config["num_attention_heads"],
            "F": config["intermediate_size"], "V": config["vocab_size"],
            "W": config["sliding_window"], "Di": a["expand"] * h,
            "N": a["d_state"], "K": a["d_conv"], "R": a["dt_rank"],
            "kinds": list(config["kinds"]),
            "published": list(config["published_layers"])}


def lam_init(published_index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * published_index)


# --------------------------------------------------------------------------
# the benchmark's own weights
# --------------------------------------------------------------------------

def _w(fan_in, fan_out):
    return {"kernel": ("normal", (fan_in, fan_out), 1.0 / math.sqrt(fan_in))}


def _ln(width):
    return {"scale": ("ones", (width,), 0.0), "bias": ("zeros", (width,), 0.0)}


def param_spec(config: dict) -> dict:
    """Nested dict of ``(init kind, shape, scale)`` leaves under the
    program's parameter names.  Kernels N(0, 1/fan_in), the embedding
    N(0, 1/hidden), ``A_log = log(1..d_state)`` and the ``dt`` bias at
    softplus^-1 of a log-uniform draw from [0.001, 0.1] (Mamba's own
    initialisation), the ``lam`` vectors N(0, 0.1^2) as the paper draws
    them, scales one, other biases zero."""
    c = dims(config)
    h, di, d = c["H"], c["Di"], c["d"]
    out = {"tok_embed": {"embedding": ("normal", (c["V"], h),
                                       1.0 / math.sqrt(h))},
           "final_ln": _ln(h)}
    for i, kind in enumerate(c["kinds"]):
        layer = {"mix_ln": _ln(h),
                 "mlp": {"mlp_ln": _ln(h), "gate": _w(h, c["F"]),
                         "up": _w(h, c["F"]), "down": _w(c["F"], h)}}
        if kind == "mamba":
            layer["mamba"] = {
                "in_proj": _w(h, 2 * di),
                "conv_w": ("normal", (c["K"], di), 1.0 / math.sqrt(c["K"])),
                "conv_b": ("zeros", (di,), 0.0),
                "x_proj": _w(di, c["R"] + 2 * c["N"]),
                "dt_proj": {**_w(c["R"], di),
                            "bias": ("dt_bias", (di,), 0.0)},
                "A_log": ("a_log", (di, c["N"]), 0.0),
                "D": ("ones", (di,), 0.0),
                "out_proj": _w(di, h)}
        elif kind in ("swa", "full", "cross"):
            attn = {"wq": _w(h, c["Hq"] * d), "wo": _w(c["Hq"] * d, h),
                    "subln": ("ones", (2 * d,), 0.0)}
            for name in ("lq1", "lk1", "lq2", "lk2"):
                attn[name] = ("normal", (d,), 0.1)
            if kind != "cross":
                attn["wk"] = _w(h, c["Hk"] * d)
                attn["wv"] = _w(h, c["Hk"] * d)
            layer["attn"] = attn
        elif kind == "gmu":
            layer["gmu_in"] = _w(h, di)
            layer["gmu_out"] = _w(di, h)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        out[f"layer_{i}"] = layer
    return out


def _is_leaf(x):
    return isinstance(x, tuple)


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
                out.append(jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[1] + 1, dtype=jnp.float32)), shape))
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                out.append(dt + jnp.log(-jnp.expm1(-dt)))
            elif kind == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append(jnp.zeros(shape, jnp.float32))
        return out

    return jax.tree_util.tree_unflatten(treedef, build(key))


# --------------------------------------------------------------------------
# the forward pass, one sequence [T] at a time
# --------------------------------------------------------------------------

def ein(spec, a, b, precision):
    return jnp.einsum(spec, _round_operand(a, precision),
                      _round_operand(b, precision), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def layer_norm(p, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def mlp(p, x, precision):
    @partial(jax.checkpoint, prevent_cse=False)
    def tokens(x):
        u = layer_norm(p["mlp_ln"], x)
        g = silu(ein("th,hf->tf", u, p["gate"]["kernel"], precision))
        g = g * ein("th,hf->tf", u, p["up"]["kernel"], precision)
        return x + ein("tf,fh->th", g, p["down"]["kernel"], precision)

    t = x.shape[0]
    blk = min(TOKEN_BLOCK, t)
    return jax.lax.map(tokens, x.reshape(t // blk, blk, -1)).reshape(x.shape)


def mamba(p, u, seg, c, precision, reset: bool):
    """-> (the mixer's output [T, H], the scan's output ``y`` [T, Di])."""
    di, n, r, k = c["Di"], c["N"], c["R"], c["K"]
    t = u.shape[0]
    xz = ein("th,hd->td", u, p["in_proj"]["kernel"], precision)
    x, z = xz[:, :di], xz[:, di:]
    conv = x * p["conv_w"][k - 1]
    for j in range(1, k):   # tap j steps back, zero across a document's start
        same = jnp.concatenate([jnp.zeros((j,), bool), seg[j:] == seg[:-j]])
        past = jnp.concatenate([jnp.zeros((j, di)), x[:-j]])
        conv = conv + jnp.where(same[:, None], past, 0.0) * p["conv_w"][k - 1 - j]
    x = silu(conv + p["conv_b"])
    dbc = ein("td,de->te", x, p["x_proj"]["kernel"], precision)
    dt = jax.nn.softplus(ein("tr,rd->td", dbc[:, :r], p["dt_proj"]["kernel"],
                             precision) + p["dt_proj"]["bias"])
    b, cc = dbc[:, r:r + n], dbc[:, r + n:]
    a_t = -jnp.exp(p["A_log"]).T                          # [N, Di]
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    keep = jnp.where(first, 0.0, 1.0) if reset else jnp.ones((t,))

    def step(s, inp):
        x_t, dt_t, b_t, c_t, keep_t = inp
        s = jnp.exp(dt_t[None, :] * a_t) * keep_t * s \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    @partial(jax.checkpoint, prevent_cse=False)
    def block(s, inp):
        return jax.lax.scan(step, s, inp)

    blk = min(SCAN_BLOCK, t)
    cut = lambda v: v.reshape((t // blk, blk) + v.shape[1:])
    _, y = jax.lax.scan(block, jnp.zeros((n, di)),
                        (cut(x), cut(dt), cut(b), cut(cc), cut(keep)))
    y = y.reshape(t, di) + p["D"] * x
    return ein("td,dh->th", y * silu(z), p["out_proj"]["kernel"],
               precision), y


def diff_attention(p, u, seg, kv, window, lam0, c, precision):
    """-> (the mixer's output [T, H], (K, V) as projected here or handed
    in).  ``kv`` None: project this layer's own keys and values."""
    hq, hk, d = c["Hq"], c["Hk"], c["d"]
    t = u.shape[0]
    q = ein("th,he->te", u, p["wq"]["kernel"], precision)
    if kv is None:
        kv = (ein("th,he->te", u, p["wk"]["kernel"], precision),
              ein("th,he->te", u, p["wv"]["kernel"], precision))
    g, per = hk // 2, hq // hk      # key-value pairs, query pairs on each
    q = q.reshape(t, g, per, 2, d)
    k = kv[0].reshape(t, g, 2, d)
    v = kv[1].reshape(t, g, 2 * d)
    lam = (jnp.exp(jnp.sum(p["lq1"] * p["lk1"]))
           - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam0)
    pos = jnp.arange(t)
    blk = min(QUERY_BLOCK, t)

    @partial(jax.checkpoint, prevent_cse=False)
    def queries(args):
        q_blk, q_pos, q_seg = args
        ok = (pos[None, :] <= q_pos[:, None]) & (q_seg[:, None] == seg[None, :])
        if window is not None:
            ok = ok & (q_pos[:, None] - pos[None, :] < window)
        scores = ein("tgpcd,sgcd->gpcts", q_blk, k, precision) / math.sqrt(d)
        soft = jax.nn.softmax(jnp.where(ok, scores, -1e30), axis=-1)
        a = soft[:, :, 0] - lam * soft[:, :, 1]            # [g, per, blk, T]
        return ein("gpts,sge->tgpe", a, v, precision)

    cut = lambda x: x.reshape((t // blk, blk) + x.shape[1:])
    o = jax.lax.map(queries, (cut(q), cut(pos), cut(seg)))
    o = o.reshape(t, g * per, 2 * d)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + LN_EPS)
    o = o * p["subln"] * (1.0 - lam0)
    return ein("te,eh->th", o.reshape(t, hq * d), p["wo"]["kernel"],
               precision), kv


def layer(p, x, seg, taken, lam0, kind, c, precision, fault):
    """One layer -> (its output [T, H], what it hands down: the scan's
    output ``m`` of a ``mamba`` layer, the ``(K, V)`` of a ``full`` layer,
    else ``()``).  ``taken`` is what it reads of another layer's: ``m`` for
    ``gmu``, ``(K, V)`` for ``cross``, else ``()``."""
    u = layer_norm(p["mix_ln"], x)
    made = ()
    if kind == "mamba":
        out, made = mamba(p["mamba"], u, seg, c, precision,
                          reset=fault != "scan_ignores_documents")
    elif kind == "gmu":
        gate = silu(ein("th,hd->td", u, p["gmu_in"]["kernel"], precision))
        out = ein("td,dh->th", taken * gate, p["gmu_out"]["kernel"],
                  precision)
    else:
        out, own = diff_attention(
            p["attn"], u, seg, taken if kind == "cross" else None,
            c["W"] if kind == "swa" else None, lam0, c, precision)
        if kind == "full":
            made = own
    return mlp(p["mlp"], x + out, precision), made


def _takes(kind, m, kv):
    return m if kind == "gmu" else kv if kind == "cross" else ()


def sequence_hidden(params, tokens, seg, c, precision, fault):
    """One packed sequence -> [T, H] after the final LayerNorm."""
    x = params["tok_embed"]["embedding"][tokens]
    m = kv = ()
    for i, kind in enumerate(c["kinds"]):
        x, made = layer(params[f"layer_{i}"], x, seg, _takes(kind, m, kv),
                        lam_init(c["published"][i]), kind, c, precision,
                        fault)
        if kind == "mamba":
            m = made
        elif kind == "full":
            kv = made
    return layer_norm(params["final_ln"], x)


def targets_of(tokens, seg):
    """-> (targets [T], weights [T]): position t predicts token t + 1 where
    that is a real token (segment > 0) of the same document."""
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    ok = jnp.concatenate([(seg[1:] == seg[:-1]) & (seg[1:] > 0),
                          jnp.zeros((1,), bool)])
    return nxt, ok.astype(jnp.float32)


def head_nll(final_ln, emb, x, y, w, precision):
    """The final LayerNorm, the tied head and the summed cross-entropy of
    one sequence's targets, a block of positions at a time."""
    @partial(jax.checkpoint, prevent_cse=False)
    def positions(args):
        x, y, w = args
        logits = ein("th,vh->tv", layer_norm(final_ln, x), emb, precision)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * w)

    t = x.shape[0]
    blk = min(LOSS_BLOCK, t)
    cut = lambda v: v.reshape((t // blk, blk) + v.shape[1:])
    return jnp.sum(jax.lax.map(positions, (cut(x), cut(y), cut(w))))


def batch_loss(params, tokens, seg, c, precision="f32", fault=None):
    """``tokens``, ``seg`` [B, T] -> the mean next-token cross-entropy, as
    one differentiable function (tests; `make_loss_and_grad` computes the
    same a layer at a time)."""
    total, count = 0.0, 0.0
    for b in range(tokens.shape[0]):
        x = params["tok_embed"]["embedding"][tokens[b]]
        m = kv = ()
        for i, kind in enumerate(c["kinds"]):
            x, made = layer(params[f"layer_{i}"], x, seg[b],
                            _takes(kind, m, kv), lam_init(c["published"][i]),
                            kind, c, precision, fault)
            m, kv = (made if kind == "mamba" else m,
                     made if kind == "full" else kv)
        y, w = targets_of(tokens[b], seg[b])
        total = total + head_nll(params["final_ln"],
                                 params["tok_embed"]["embedding"], x, y, w,
                                 precision)
        count = count + jnp.sum(w)
    return total / jnp.maximum(count, 1.0)


def logits_of(params, tokens, seg, config, precision="f32", fault=None):
    """[B, T, V] logits (tests only: a whole sequence's logits at once)."""
    c = dims(config)
    hidden = jnp.stack([sequence_hidden(params, tokens[b], seg[b], c,
                                        precision, fault)
                        for b in range(tokens.shape[0])])
    return ein("bth,vh->btv", hidden, params["tok_embed"]["embedding"],
               precision)


def make_loss_and_grad(config: dict, precision: str = "f32", fault=None):
    """-> ``fn(params, tokens, seg) -> (loss, grads)``.  Reverse mode by
    hand over the stack, one layer's backward at a time: the forward pass
    keeps each layer's input and the two hand-downs, the backward pass calls
    `jax.vjp` of one layer (which recomputes it) from the last to the first
    and routes the cotangents of ``m`` and ``(K, V)`` from their readers
    back to the layer that made them.  So the device holds one layer's
    intermediates at a time beside the parameters, their gradients and the
    optimizer's moments (11.2 GB at the published widths)."""
    c = dims(config)
    kinds = c["kinds"]
    lam0 = [jnp.float32(lam_init(i)) for i in c["published"]]

    def one(kind):
        return partial(layer, kind=kind, c=c, precision=precision,
                       fault=fault)

    fwd = {k: jax.jit(one(k)) for k in set(kinds)}

    def make_bwd(kind):
        def bwd(p, x, seg, taken, lam, ct):
            _, pull = jax.vjp(lambda p, x, taken: one(kind)(
                p, x, seg, taken, lam), p, x, taken)
            return pull(ct)
        return jax.jit(bwd)

    bwd = {k: make_bwd(k) for k in set(kinds)}
    head = jax.jit(jax.value_and_grad(
        lambda ln, emb, x, y, w: head_nll(ln, emb, x, y, w, precision),
        argnums=(0, 1, 2)))
    embed = jax.jit(lambda emb, tokens: emb[tokens])
    scatter = jax.jit(lambda g, tokens, dx, scale: g.at[tokens].add(
        dx * scale), donate_argnums=(0,))
    add = jax.jit(lambda a, b, scale: jax.tree_util.tree_map(
        lambda x, y: x + y * scale, a, b), donate_argnums=(0,))
    zeros = lambda shapes: jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    def fn(params, tokens, seg):
        emb = params["tok_embed"]["embedding"]
        targets = [targets_of(tokens[b], seg[b])
                   for b in range(tokens.shape[0])]
        count = jnp.maximum(sum(jnp.sum(w) for _, w in targets), 1.0)
        scale = 1.0 / count
        grads, total = {}, 0.0

        def accumulate(name, g):
            grads[name] = (add(grads[name], g, scale) if name in grads
                           else jax.tree_util.tree_map(
                               lambda x: x * scale, g))

        for b in range(tokens.shape[0]):
            xs, taken, src = [embed(emb, tokens[b])], [], []
            m = kv = ()
            made_by = {"m": None, "kv": None}
            for i, kind in enumerate(kinds):
                taken.append(_takes(kind, m, kv))
                src.append(made_by["m"] if kind == "gmu" else
                           made_by["kv"] if kind == "cross" else None)
                x, made = fwd[kind](params[f"layer_{i}"], xs[-1], seg[b],
                                    taken[-1], lam0[i])
                if kind == "mamba":
                    m, made_by["m"] = made, i
                elif kind == "full":
                    kv, made_by["kv"] = made, i
                xs.append(x)
            y, w = targets[b]
            nll, (g_ln, g_emb, dx) = head(params["final_ln"], emb, xs.pop(),
                                          y, w)
            total = total + nll
            accumulate("final_ln", g_ln)
            accumulate("tok_embed", {"embedding": g_emb})
            handed = {}      # cotangents of what layer i handed down
            for i in reversed(range(len(kinds))):
                kind = kinds[i]
                p = params[f"layer_{i}"]
                if kind in ("mamba", "full"):
                    ct_made = handed.pop(i, None)
                    if ct_made is None:
                        ct_made = zeros(jax.eval_shape(
                            fwd[kind], p, xs[i], seg[b], taken[i],
                            lam0[i])[1])
                else:
                    ct_made = ()
                dp, dx, dtaken = bwd[kind](p, xs.pop(), seg[b], taken.pop(),
                                           lam0[i], (dx, ct_made))
                accumulate(f"layer_{i}", dp)
                if src[i] is not None:
                    handed[src[i]] = (add(handed[src[i]], dtaken, 1.0)
                                      if src[i] in handed else dtaken)
            grads["tok_embed"]["embedding"] = scatter(
                grads["tok_embed"]["embedding"], tokens[b], dx, scale)
        return total * scale, grads

    return fn


# --------------------------------------------------------------------------
# the optimizer, a layer's parameters at a time
# --------------------------------------------------------------------------

def init_opt(params) -> dict:
    """AdamW's state for `clip_and_update`: two trees of zeros that share
    nothing (it takes them apart)."""
    zeros = lambda: {k: jax.tree_util.tree_map(jnp.zeros_like, v)
                     for k, v in params.items()}
    return {"count": 0, "mu": zeros(), "nu": zeros()}


def clip_and_update(params, grads, state, opt: dict, fetch: bool = False):
    """`adamw.update` applied to one leaf at a time, so that no second copy
    of the whole state ever exists (697 M parameters with their gradients
    and moments are 11.2 GB in float32).  The global-norm clip is applied
    first, over all leaves; each leaf's norm is then at most 1 and
    `adamw.update`'s own clip leaves it as it is.  ``params``, ``grads`` and
    ``state`` are consumed.  -> (params, state, the clipped gradient: leaf
    by leaf its norm, or with ``fetch`` the leaf itself on the host)."""
    scale = jnp.where((norm := adamw.global_norm(grads)) < 1.0, 1.0,
                      1.0 / norm)
    flat = lambda tree: jax.tree_util.tree_flatten(tree)
    (p, treedef), (g, _) = flat(params), flat(grads)
    (mu, _), (nu, _) = flat(state["mu"]), flat(state["nu"])
    for tree in (params, grads, state["mu"], state["nu"]):
        tree.clear()
    clipped = []
    for i in range(len(p)):
        sub = {"count": state["count"], "mu": mu[i], "nu": nu[i]}
        gi, g[i] = g[i] * scale, None
        p[i], sub, gi = adamw.update(p[i], gi, sub, opt)
        mu[i], nu[i] = sub["mu"], sub["nu"]
        clipped.append(jax.device_get(gi) if fetch else jnp.linalg.norm(gi))
    un = lambda leaves: jax.tree_util.tree_unflatten(treedef, leaves)
    return (un(p), {"count": state["count"] + 1, "mu": un(mu), "nu": un(nu)},
            un(clipped))
