"""NerrfNet in plain ``jax.numpy``: forward, the three-part loss, gradients.

The plain reference of both configurations (`joint-100h`, `joint-dense`):
float32 throughout, every matrix product at ``highest`` precision, one
window at a time (`window_forward`), batched only by ``jax.vmap`` over a
block of windows so that a whole timed batch fits beside nothing else on
the chip.  It imports nothing of the program (no flax module, no
``nerrf_tpu.ops``) and takes nothing the program made: the weights are the
benchmark's own (`chipbench.reference.params`), the dropout masks are drawn
here from the step's key the way the layer library derives them
(`dropout_key`).

The equations, per window (N nodes, E edges sorted by destination, S
sequences of T events):

* BiLSTM (2 layers x 256): ``x = gelu(seq_feat W_in + b) * mask``; time is
  flipped to a prefix-first layout; per layer the forward direction reads
  ``x``, the backward direction reads ``x`` reversed inside each
  sequence's valid prefix; gates ``i, f, g, o = split(x W_i + h W_h + b)``,
  ``c = s(f) c + s(i) tanh(g)``, ``h = s(o) tanh(c)``; the two directions are
  concatenated, merged by a dense layer + gelu and masked; the masked mean
  over time is layer-normed, dropped out, and gives ``seq_logit`` and the
  sequence embedding.
* Fusion: ``node_feat += scatter_add(seq_emb W_s + b_s -> seq_node_idx)``.
* GraphSAGE-T (28 x 160): ``h = gelu(node_feat W_n + b + type_emb + aux_emb)
  * mask``; edge embedding ``e = gelu(edge_feat W_e + b)``; edge weight
  ``w = (edge_feat[:, 12] + 0.1) * edge_mask``.  Per block, with ``hn =
  LN(h)`` and ``msg = hn W_m + b_m``::

      agg[n] = sum_{e: dst(e)=n} w_e (msg[src(e)] + e_e + b_0) / max(sum w, 1e-6)
             + sum_{e: src(e)=n} w_e (msg[dst(e)] + e_e + b_1) / max(sum w, 1e-6)
      h      = (h + gelu([hn, agg] W_s + b_s)) * mask

  written here in matrix form, ``agg = A msg + c`` with ``A = D_f^-1 W +
  D_r^-1 W^T`` and ``W[dst, src] += w`` built once per window, so that the
  reference's time goes into matrix products the chip is good at.  That is
  algebra, not a departure: the program's three aggregation paths
  (`segment`, `dense_adj`, `fused`) all compute this sum.
* Heads: ``h = dropout(LN(h))``; ``node_logit = h w_n + b``; ``edge_logit =
  gelu([h_src, h_dst, h_src * h_dst, e] W_1 + b_1) w_2 + b_2``; masked
  logits are set to -30.
* Loss: class-rebalanced BCE-with-logits (``pos_weight`` on positives),
  each part a masked sum over the WHOLE batch divided by the batch's mask
  count, combined with the configuration's three weights.

``precision`` chooses how matrix products are computed: ``"f32"`` is the
reference; ``"bf16"`` and ``"fp8"`` (per-tensor scaled: e4m3 forward, e5m2
for the cotangents) round both operands of every product first (the lower-precision *controls* of the comparison that decides
`correct`; see `chipbench/compare.py`).
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp

LN_EPS = 1e-6          # the layer library's LayerNorm default
MASKED_LOGIT = -30.0


# --------------------------------------------------------------------------
# primitives
# --------------------------------------------------------------------------

def _scaled_fp8(x, dtype):
    """Round to an 8-bit float with one scale per tensor from its largest
    magnitude (the usual fp8 recipe; unscaled, small values all round to
    zero)."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    """An fp8 matmul operand: e4m3 on the way forward, and the cotangent
    that comes back through it in e5m2, as fp8 training keeps gradients."""
    return _scaled_fp8(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (_scaled_fp8(x, jnp.float8_e4m3fn), None),
                    lambda _, g: (_scaled_fp8(g, jnp.float8_e5m2),))


def _round_operand(x, precision: str):
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        return _fp8_operand(x)
    raise ValueError(f"unknown precision {precision!r}")


def matmul(a, b, precision: str = "f32"):
    """``a @ b`` in float32 at the highest matmul precision, both operands
    first rounded to ``precision``."""
    return jnp.matmul(_round_operand(a, precision),
                      _round_operand(b, precision),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def dense(p, x, precision):
    return matmul(x, p["kernel"], precision) + p["bias"]


def layer_norm(p, x):
    mean = x.mean(-1, keepdims=True)
    var = jnp.maximum((x * x).mean(-1, keepdims=True) - mean * mean, 0.0)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu(x):
    """tanh-approximate GELU (the layer library's default)."""
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def dropout_key(step_dropout_key, path: tuple):
    """The key a dropout layer at module ``path`` draws its mask from, given
    the ``dropout`` key handed to ``apply``: the layer library folds the
    SHA-1 of (module path..., draw count = 1) into it."""
    m = hashlib.sha1()
    for x in tuple(path) + (1,):
        if isinstance(x, str):
            m.update(x.encode("utf-8"))
        else:
            m.update(x.to_bytes((x.bit_length() + 7) // 8, byteorder="big"))
    word = int.from_bytes(m.digest()[:4], byteorder="big")
    return jax.random.fold_in(step_dropout_key, jnp.uint32(word))


def dropout(x, key, rate: float):
    if rate == 0.0 or key is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, p=keep, shape=x.shape)
    return jnp.where(mask, x / keep, 0.0)


# --------------------------------------------------------------------------
# BiLSTM
# --------------------------------------------------------------------------

def _flip_valid(x, lengths):
    """Reverse each sequence inside its valid prefix; zero past it.
    x [S, T, F], lengths [S]."""
    t = jnp.arange(x.shape[1])
    src = lengths[:, None] - 1 - t[None, :]
    ok = src >= 0
    g = jnp.take_along_axis(x, jnp.where(ok, src, 0)[..., None], axis=1)
    return g * ok[..., None].astype(x.dtype)


def _cell_weights(cell):
    wi = jnp.concatenate([cell[f"i{g}"]["kernel"] for g in "ifgo"], axis=1)
    wh = jnp.concatenate([cell[f"h{g}"]["kernel"] for g in "ifgo"], axis=1)
    b = jnp.concatenate([cell[f"h{g}"]["bias"] for g in "ifgo"], axis=0)
    return wi, wh, b


def _lstm_direction(cell, x, precision):
    """x [S, T, F] -> hidden states [S, T, H], zero initial state."""
    wi, wh, b = _cell_weights(cell)
    hid = wh.shape[0]
    xin = matmul(x, wi, precision) + b            # [S, T, 4H]
    h0 = jnp.zeros((x.shape[0], hid), jnp.float32)

    @jax.checkpoint   # keep (h, c) per step only: the batch has to fit
    def step(carry, x_t):
        h, c = carry
        gates = x_t + matmul(h, wh, precision)
        gi, gf, gg, go = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(gf) * c + jax.nn.sigmoid(gi) * jnp.tanh(gg)
        h = jax.nn.sigmoid(go) * jnp.tanh(c)
        return (h, c), h

    _, hs = jax.lax.scan(step, (h0, h0), jnp.swapaxes(xin, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def lstm_forward(p, cfg, seq_feat, seq_mask, drop_key, precision):
    """-> (seq_logit [S], seq_emb [S, H])."""
    mask = seq_mask.astype(jnp.float32)
    x = gelu(dense(p["in_proj"], seq_feat, precision)) * mask[..., None]
    lengths = seq_mask.sum(-1).astype(jnp.int32)
    x = jnp.flip(x, axis=1)
    mask_pf = jnp.flip(mask, axis=1)[..., None]
    for i in range(cfg["num_layers"]):
        fwd = _lstm_direction(p[f"OptimizedLSTMCell_{2 * i}"], x, precision)
        bwd = _flip_valid(
            _lstm_direction(p[f"OptimizedLSTMCell_{2 * i + 1}"],
                            _flip_valid(x, lengths), precision), lengths)
        y = jnp.concatenate([fwd, bwd], axis=-1)
        x = gelu(dense(p[f"merge_{i}"], y, precision)) * mask_pf
    pooled = (x * mask_pf).sum(1) / jnp.maximum(mask_pf.sum(1), 1.0)
    pooled = layer_norm(p["pool_ln"], pooled)
    pooled = dropout(pooled, drop_key, cfg["dropout"])
    return dense(p["head"], pooled, precision)[:, 0], pooled


# --------------------------------------------------------------------------
# GraphSAGE-T
# --------------------------------------------------------------------------

def _adjacency(edge_src, edge_dst, w, n):
    """(A, d_fwd, d_rev): ``A = D_f^-1 W + D_r^-1 W^T``, W[dst, src] += w."""
    w_raw = jnp.zeros((n, n), jnp.float32).at[edge_dst, edge_src].add(w)
    d_fwd = jnp.zeros((n,), jnp.float32).at[edge_dst].add(w)
    d_rev = jnp.zeros((n,), jnp.float32).at[edge_src].add(w)
    inv_f = 1.0 / jnp.maximum(d_fwd, 1e-6)
    inv_r = 1.0 / jnp.maximum(d_rev, 1e-6)
    adj = w_raw * inv_f[:, None] + w_raw.T * inv_r[:, None]
    return adj, d_fwd * inv_f, d_rev * inv_r, inv_f, inv_r


def gnn_forward(p, cfg, node_feat, node_type, node_aux, node_mask, edge_src,
                edge_dst, edge_feat, edge_mask, drop_key, precision):
    """-> (edge_logit [E], node_logit [N])."""
    n = node_feat.shape[0]
    nmask = node_mask.astype(jnp.float32)[:, None]
    h = dense(p["node_enc"], node_feat, precision)
    h = gelu(h + p["type_emb"]["embedding"][node_type]
             + p["aux_emb"]["embedding"][node_aux]) * nmask
    e_emb = gelu(dense(p["edge_enc"], edge_feat, precision))
    w = (edge_feat[:, 12] + 0.1) * edge_mask.astype(jnp.float32)

    adj, s_f, s_r, inv_f, inv_r = _adjacency(edge_src, edge_dst, w, n)
    we = w[:, None] * e_emb
    c_sum = (jnp.zeros((n, e_emb.shape[1]), jnp.float32).at[edge_dst].add(we)
             * inv_f[:, None]
             + jnp.zeros((n, e_emb.shape[1]), jnp.float32).at[edge_src].add(we)
             * inv_r[:, None])

    blocks = [p[f"block_{i}"] for i in range(cfg["num_layers"])]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)

    @jax.checkpoint
    def block(h, bp):
        hn = layer_norm(bp["ln"], h)
        msg = dense(bp["w_msg"], hn, precision)
        agg = (matmul(adj, msg, precision) + c_sum
               + bp["dir_bias"][0] * s_f[:, None]
               + bp["dir_bias"][1] * s_r[:, None])
        upd = dense(bp["w_self"], jnp.concatenate([hn, agg], -1), precision)
        return (h + gelu(upd)) * nmask, None

    h, _ = jax.lax.scan(block, h, stacked)

    h = layer_norm(p["final_ln"], h)
    h = dropout(h, drop_key, cfg["dropout"])
    node_logit = dense(p["node_head"], h, precision)[:, 0]
    h_src, h_dst = h[edge_src], h[edge_dst]
    pair = jnp.concatenate([h_src, h_dst, h_src * h_dst, e_emb], -1)
    z = gelu(dense(p["edge_head_1"], pair, precision))
    edge_logit = dense(p["edge_head_2"], z, precision)[:, 0]
    return (jnp.where(edge_mask, edge_logit, MASKED_LOGIT),
            jnp.where(node_mask, node_logit, MASKED_LOGIT))


# --------------------------------------------------------------------------
# the joint model, one window
# --------------------------------------------------------------------------

def window_forward(params, model_cfg, win, step_dropout_key=None,
                   precision: str = "f32"):
    """One window (a dict of unbatched arrays) -> dict of logits.
    ``step_dropout_key`` None means no dropout (evaluation)."""
    k_lstm = k_gnn = None
    if step_dropout_key is not None:
        k_lstm = dropout_key(step_dropout_key, ("lstm", "Dropout_0"))
        k_gnn = dropout_key(step_dropout_key, ("gnn", "Dropout_0"))
    seq_logit, seq_emb = lstm_forward(
        params["lstm"], model_cfg["lstm"], win["seq_feat"], win["seq_mask"],
        k_lstm, precision)
    node_feat = win["node_feat"]
    if model_cfg["fuse"]:
        n = node_feat.shape[0]
        h_seq = dense(params["seq_to_node"], seq_emb, precision)
        ok = win["seq_node_idx"] >= 0
        tgt = jnp.where(ok, win["seq_node_idx"], n)
        fused = jnp.zeros((n + 1, h_seq.shape[1]), jnp.float32).at[tgt].add(
            h_seq * ok[:, None].astype(jnp.float32))[:n]
        node_feat = node_feat + fused
    edge_logit, node_logit = gnn_forward(
        params["gnn"], model_cfg["gnn"], node_feat, win["node_type"],
        win["node_aux"], win["node_mask"], win["edge_src"], win["edge_dst"],
        win["edge_feat"], win["edge_mask"], k_gnn, precision)
    return {"edge_logit": edge_logit, "node_logit": node_logit,
            "seq_logit": seq_logit}


# --------------------------------------------------------------------------
# loss and gradients over a batch, in blocks
# --------------------------------------------------------------------------

_PARTS = (("edge", "edge_logit", "edge_label", "edge_mask"),
          ("node", "node_logit", "node_label", "node_mask"),
          ("seq", "seq_logit", "seq_label", "seq_valid"))


def _bce_sum(logit, label, mask, pos_weight):
    loss = -(pos_weight * label * jax.nn.log_sigmoid(logit)
             + (1.0 - label) * jax.nn.log_sigmoid(-logit))
    return (loss * mask).sum()


def block_loss(params, model_cfg, loss_cfg, block, denoms, step_dropout_key,
               precision):
    """A block's share of the batch loss: each part's masked sum over the
    block divided by the WHOLE batch's mask count ``denoms[part]``."""
    out = jax.vmap(lambda w: window_forward(
        params, model_cfg, w, step_dropout_key, precision))(block)
    total = 0.0
    for part, logit, label, mask in _PARTS:
        s = _bce_sum(out[logit], block[label],
                     block[mask].astype(jnp.float32), loss_cfg["pos_weight"])
        total = total + loss_cfg[f"{part}_loss_weight"] * s / denoms[part]
    return total


def batch_denoms(batch):
    """Mask counts over the whole batch, floored at 1 (as the loss does)."""
    return {part: jnp.maximum(
        jnp.asarray(batch[mask]).astype(jnp.float32).sum(), 1.0)
        for part, _, _, mask in _PARTS}


def make_loss_and_grad(model_cfg, loss_cfg, precision: str = "f32"):
    """-> jitted ``f(params, block, denoms, key) -> (loss_share, grads)``
    for one block of windows; the caller sums shares over the blocks."""
    def f(params, block, denoms, step_dropout_key):
        return jax.value_and_grad(block_loss)(
            params, model_cfg, loss_cfg, block, denoms, step_dropout_key,
            precision)
    return jax.jit(f)


def loss_and_grad(fn, params, batch, step_dropout_key, block_size: int):
    """Loss and gradient of one training batch (a dict of [B, ...] device
    arrays), accumulated over blocks of ``block_size`` windows."""
    b = next(iter(batch.values())).shape[0]
    if b % block_size:
        raise ValueError(f"batch {b} is not a multiple of the reference "
                         f"block {block_size}")
    denoms = batch_denoms(batch)
    loss = grads = None
    for lo in range(0, b, block_size):
        block = {k: v[lo:lo + block_size] for k, v in batch.items()}
        l, g = fn(params, block, denoms, step_dropout_key)
        loss = l if loss is None else loss + l
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return loss, grads
