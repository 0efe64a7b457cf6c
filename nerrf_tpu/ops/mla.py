"""Latent attention (the multi-head latent attention of the DeepSeek-V2/V3
family) as it trains: queries, keys and values are rebuilt from narrow
latents and the core is ordinary causal attention inside a packed document.

A head's query and key are assembled from two sources (`assemble`): an
un-rotated part of its own (``nope`` wide, from the latent) and a rotary part
(``q``'s own; the key's is ONE rotated vector a token, ``k_r``, that every
head shares: it is broadcast over the heads, and reverse mode sums its
gradient over them).  Values may be wider or narrower than the keys.  For a
query ``t`` and a key ``s <= t`` of the same document:

    o_t = sum_s softmax_s((q_nope_t . k_nope_s + q_rope_t . k_r_s)
                          / sqrt(nope + rope)) v_s          (`mla_attention`)

The absorbed form (scores against the latent itself, what a serving cache
wants) is not here: in training the latent is expanded.

The core `attention` is the stream stacks' causal packed-document attention
whatever makes its operands: the ``gqa_*`` kinds (`models/stream.py`) call it
with grouped heads (fewer key-value heads than query heads: query head ``h``
reads key-value head ``h // group``) and, in their window layers, a
``window`` w (a key no more than w - 1 positions back), each under a scope of
its own (``scope``: ``gqa_window_attention``, ``gqa_full_attention``).
Without them it traces what it traced before they existed.

`attention` has two routes, chosen by `attention_route` from the backend and
the static shapes and by nothing else (docs/kernel-paths.md):

* ``pallas_flash`` (a TPU, one head width for ``q``, ``k`` and ``v``): two
  fused kernels in which a tile's scores, probabilities and their derivatives
  live in the chip's vector memory only.  Forward: online softmax over the
  key tiles at or below the diagonal.  Backward: ONE kernel that computes a
  tile's scores once more from the saved log-sum-exp and takes all three
  gradients from them.
* ``xla_blocked`` (everywhere else; the fused route's oracle):
  `ops/dsa.py::sparse_attention`'s dense twin, plain XLA: a block of queries
  at a time against the keys before the block's end, rounded up to a span
  (one `lax.switch` branch per key length), a block's float32 scores alive
  only while it is computed, through HBM.

Both round where the other rounds (bf16 products accumulated in float32,
softmax in float32, probabilities and the scores' gradient cast to the
compute type before their products).  Either way the derivative is written
by hand (`jax.custom_vjp`): the forward pass keeps ``o`` and each head's
log-sum-exp ``[H, T]`` under the name `SAVED`, the flash-style backward pass
computes the scores once more from them.  Reverse mode through the XLA
route's scan would keep or recompute every block's scores, and the
operations of a transposed `lax.switch` carry no scope of their own.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from nerrf_tpu.ops import dsa

# queries a block, and the multiple of keys a block's keys end at (as in
# `ops/dsa.py`).  A block's scores are [heads, block, keys] float32 (0.67 GB
# at 20 heads and 8192 keys).  Forward + backward of one 8192-token
# sequence at 20 heads of 256, alone on a v5e (PR 35): 59.9 / 52.6 / 47.3 /
# 44.7 ms at 256 / 512 / 1024 / 2048 queries a block: an iteration of the
# backward scan costs its products and a fixed part (the accumulators'
# rows, the loop's own copies); the whole step's scratch is 5.2-5.7 GB at
# any of them (compile for a described v5e)
QUERY_BLOCK = 1024
KEY_SPAN = 2048
# the name under which the forward pass's residuals can be kept by a remat
# policy (`jax.checkpoint_policies.save_only_these_names`)
SAVED = "mla_saved"


def assemble(q, k_r, kv, pos, *, nope: int, theta: Optional[float]):
    """One packed sequence.  ``q`` [T, H, nope + rope] (a head's un-rotated
    part, then its rotary part, not yet rotated), ``k_r`` [T, rope] (the one
    rotary key a token, not yet rotated), ``kv`` [T, H, nope + dv] (a head's
    un-rotated key, then its value), ``pos`` [T] the positions inside the
    document -> (q [T, H, nope + rope], k [T, H, nope + rope], v [T, H, dv]):
    both rotary parts rotated over all their dimensions, the one rotated
    ``k_r`` behind every head's un-rotated key.  ``theta`` None: nothing is
    rotated (position-free latent attention; ``pos`` is not read), the
    "rotary" columns stay plain columns."""
    t, heads, _ = q.shape
    if theta is None:
        k_r = k_r[:, None, :]
    else:
        k_r = dsa.rope(k_r[:, None, :], pos, theta)
        q = jnp.concatenate([q[..., :nope],
                             dsa.rope(q[..., nope:], pos, theta)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r, (t, heads, k_r.shape[-1]))], -1)
    return q, k, kv[..., nope:]


def _window_mask(nq: int, nk: int, q_seg, k_seg, q_pos0, k_pos0, window):
    """The pairs of a block of the window form that attend: queries from
    position ``q_pos0``, keys from ``k_pos0``, the same document, causal,
    and no more than ``window`` - 1 positions back."""
    q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, (nq, 1), 0)
    k_pos = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, (1, nk), 1)
    return ((k_pos <= q_pos) & (q_pos - k_pos < window)
            & (q_seg[:, None] == k_seg[None, :]))


def _block_forward(q, k, v, valid):
    """One block of queries against L keys, heads leading: ``q`` [H, Q,
    d], ``k`` [H, L, d], ``v`` [H, L, dv], the pairs that attend ``valid``
    [Q, L] -> (o [H, Q, dv], the heads' log-sum-exp [H, Q])."""
    logits = jnp.einsum("hqd,hld->hql", q, k,
                        preferred_element_type=jnp.float32)
    logits = jnp.where(valid, logits * q.shape[-1] ** -0.5, -1e9)
    top = jnp.max(logits, axis=-1, keepdims=True)
    pexp = jnp.exp(logits - top)
    norm = jnp.sum(pexp, axis=-1, keepdims=True)
    o = jnp.einsum("hql,hld->hqd", pexp.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    return (o / norm).astype(q.dtype), (top + jnp.log(norm))[..., 0]


def _block_backward(q, k, v, o, lse, d_o, valid):
    """Flash-style backward of one block: the scores are computed again
    from ``q``, ``k`` and the saved log-sum-exp -> (dq [H, Q, d], dk [H, L,
    d], dv [H, L, dv] float32)."""
    dt = q.dtype
    scale = q.shape[-1] ** -0.5
    f32 = dict(preferred_element_type=jnp.float32)
    logits = jnp.einsum("hqd,hld->hql", q, k, **f32) * scale
    prob = jnp.where(valid, jnp.exp(logits - lse[..., None]), 0.0)
    d_prob = jnp.einsum("hqd,hld->hql", d_o, v, **f32)
    rows = jnp.sum(d_o.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    d_logits = (prob * (d_prob - rows[..., None]) * scale).astype(dt)
    return (jnp.einsum("hql,hld->hqd", d_logits, k, **f32),
            jnp.einsum("hql,hqd->hld", d_logits, q, **f32),
            jnp.einsum("hql,hqd->hld", prob.astype(dt), d_o, **f32))


def _query_blocks(x, block: int):
    """[H, T, ...] -> [T / block, H, block, ...]."""
    h, t = x.shape[:2]
    return jnp.moveaxis(x.reshape((h, t // block, block) + x.shape[2:]), 1, 0)


def _join(x):
    """[T / block, H, block, ...] -> [H, T, ...]."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


def _front(k, v, seg, window: int):
    """``window`` key rows before position 0 (zeros, in no document: a
    block's key span then starts at its start - ``window`` everywhere)."""
    pad = lambda x: jnp.pad(x, ((0, 0), (window, 0), (0, 0)))
    return pad(k), pad(v), jnp.concatenate(
        [jnp.full((window,), -1, seg.dtype), seg])


def _window_block(t: int, block: int, window: int) -> int:
    """The window form's queries a block: at most the window, so that a
    block's key span (its start - window to its end) holds no more than
    twice the pairs it needs, and its float32 scores stay small."""
    narrow = min(block, window)
    return narrow if t % narrow == 0 else block


def _forward(q, k, v, seg, block, span, window):
    t = q.shape[1]
    block, span = dsa._spans(t, block or QUERY_BLOCK, span or KEY_SPAN)
    # nerrflint: ok[recompile-hazard] window is None or a Python int of the static configuration (`StreamConfig.window`), never a traced value: it shapes the program
    if window is not None:
        # one key span for every block: from its start - window to its end
        block = _window_block(t, block, window)
        k, v, k_seg = _front(k, v, seg, window)
        keys = lambda x, lo, axis=1: jax.lax.dynamic_slice_in_dim(
            x, lo, window + block, axis=axis)
        o, lse = jax.lax.map(
            lambda xs: _block_forward(
                xs[0], keys(k, xs[2]), keys(v, xs[2]), _window_mask(
                    block, window + block, xs[1], keys(k_seg, xs[2], 0),
                    xs[2], xs[2] - window, window)),
            (_query_blocks(q, block), dsa._blocks(seg, block),
             jnp.arange(0, t, block)))
        return _join(o), _join(lse)

    def branch(n):
        return lambda q_b, seg_b, lo: _block_forward(
            q_b, k[:, :n], v[:, :n],
            dsa._allowed(q_b.shape[1], n, seg_b, seg[:n], lo))

    branches = [branch(n) for n in range(span, t + 1, span)]
    o, lse = jax.lax.map(
        lambda xs: jax.lax.switch(xs[-1] // span, branches, *xs),
        (_query_blocks(q, block), dsa._blocks(seg, block),
         jnp.arange(0, t, block)))
    return _join(o), _join(lse)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _attention(q, k, v, seg, block, span, window=None, scope="mla_attention"):
    return _forward(q, k, v, seg, block, span, window)[0]


def _attention_fwd(q, k, v, seg, block, span, window, scope):
    o, lse = _forward(q, k, v, seg, block, span, window)
    # named so that a layer's remat can keep them (`SAVED`): what the
    # backward pass needs of the forward pass's [T, T] work
    o, lse = checkpoint_name(o, SAVED), checkpoint_name(lse, SAVED)
    return o, (q, k, v, seg, o, lse)


def _window_backward(q, k, v, seg, o, lse, d_o, block, window):
    """The backward scan of `_forward`'s window form: a block's dk, dv are
    added into its key span of accumulators that start ``window`` rows
    early."""
    t = q.shape[1]
    k, v, k_seg = _front(k, v, seg, window)
    span = window + block
    keys = lambda x, lo: jax.lax.dynamic_slice_in_dim(x, lo, span, axis=1)

    def step(carry, xs):
        dk, dv = carry
        q_b, o_b, lse_b, d_o_b, seg_b, lo = xs
        dq, dk_b, dv_b = _block_backward(
            q_b, keys(k, lo), keys(v, lo), o_b, lse_b, d_o_b, _window_mask(
                block, span, seg_b, jax.lax.dynamic_slice_in_dim(
                    k_seg, lo, span), lo, lo - window, window))
        add = lambda acc, g: jax.lax.dynamic_update_slice_in_dim(
            acc, keys(acc, lo) + g, lo, axis=1)
        return (add(dk, dk_b), add(dv, dv_b)), dq

    (dk, dv), dq = jax.lax.scan(
        step, (jnp.zeros(k.shape, jnp.float32),
               jnp.zeros(v.shape, jnp.float32)),
        (_query_blocks(q, block), _query_blocks(o, block),
         _query_blocks(lse, block), _query_blocks(d_o, block),
         dsa._blocks(seg, block), jnp.arange(0, t, block)))
    return dq, dk[:, window:], dv[:, window:]


def _attention_bwd(block, span, window, scope, res, d_o):
    q, k, v, seg, o, lse = res
    t = q.shape[1]
    block, span = dsa._spans(t, block or QUERY_BLOCK, span or KEY_SPAN)
    # nerrflint: ok[recompile-hazard] window is None or a Python int of the static configuration (`StreamConfig.window`), never a traced value: it shapes the program
    if window is not None:
        block = _window_block(t, block, window)
        with jax.named_scope(scope):
            dq, dk, dv = _window_backward(q, k, v, seg, o, lse, d_o, block,
                                          window)
        return (_join(dq).astype(q.dtype), dk.astype(k.dtype),
                dv.astype(v.dtype), None)

    def branch(n):
        # a block's dk, dv are added into the rows it read, in place: the
        # accumulators are [H, T, 256] float32 (168 MB each at 20 heads),
        # and padding a block's share to T and adding it whole moved them
        # through HBM three times an iteration
        def run(dk, dv, q_b, o_b, lse_b, d_o_b, seg_b, lo):
            dq, dk_b, dv_b = _block_backward(
                q_b, k[:, :n], v[:, :n], o_b, lse_b, d_o_b,
                dsa._allowed(q_b.shape[1], n, seg_b, seg[:n], lo))
            return dk.at[:, :n].add(dk_b), dv.at[:, :n].add(dv_b), dq
        return run

    branches = [branch(n) for n in range(span, t + 1, span)]

    def step(carry, xs):
        dk, dv, dq = jax.lax.switch(xs[-1] // span, branches, *carry, *xs)
        return (dk, dv), dq

    with jax.named_scope(scope):
        (dk, dv), dq = jax.lax.scan(
            step, (jnp.zeros(k.shape, jnp.float32),
                   jnp.zeros(v.shape, jnp.float32)),
            (_query_blocks(q, block), _query_blocks(o, block),
             _query_blocks(lse, block), _query_blocks(d_o, block),
             dsa._blocks(seg, block), jnp.arange(0, t, block)))
    return (_join(dq).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), None)


_attention.defvjp(_attention_fwd, _attention_bwd)


# --------------------------------------------------------------------------
# the fused route: a tile's scores never leave the chip's vector memory
# --------------------------------------------------------------------------

# queries and keys a tile of the fused kernels (chosen on a v5e in the whole
# step: `docs/kernel-paths.md`), and the fast memory a kernel may plan
# (a v5e core has 128 MiB; the compiler's own scoped limit is 16 MiB)
FLASH_BLOCK_Q = 512
FLASH_BLOCK_K = 512
FLASH_VMEM_BYTES = 96 << 20
# the backward kernel keeps one head's whole ``dq`` [T, d] in fast memory,
# in float32 and, twice (the pipeline's two buffers), in the compute type:
# 16 MiB at 8192 x 256
FLASH_MAX_ROW_ELEMENTS = 4 << 20
_LANES, _SUBLANES = 128, 8
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def attention_route(t: int, d: int, dv: int) -> str:
    """Which form `attention` traces for a sequence of ``t`` tokens with
    ``d``-wide keys and ``dv``-wide values: a function of the backend and
    these static shapes, nothing else.  ``"pallas_flash"`` (the fused
    kernels) on a TPU when one head width serves ``q``, ``k`` and ``v``, it
    is whole lanes, and ``t`` is whole tiles whose ``dq`` rows fit;
    ``"xla_blocked"`` otherwise.  A window takes the same route
    (`docs/kernel-paths.md`)."""
    if (jax.default_backend() == "tpu" and d == dv and d % _LANES == 0
            and t % FLASH_BLOCK_Q == 0 and t % FLASH_BLOCK_K == 0
            and t * d <= FLASH_MAX_ROW_ELEMENTS):
        return "pallas_flash"
    return "xla_blocked"


def _tile_mask(q_seg, k_seg, q0, k0, shape, q_axis: int, window=None):
    """Which pairs of a tile attend: the same document (``q_seg``, ``k_seg``
    broadcast against each other), key position <= query position and, with
    a ``window`` w, no more than w - 1 positions back; queries run along
    ``q_axis`` of ``shape`` from position ``q0``, keys along the other axis
    from ``k0``."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = (q_seg == k_seg) & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    return mask


def _forward_walk(t: int, window):
    """The forward grid's walk over the key tiles of query tile ``i`` ->
    (steps, first): step ``s`` reads key tile ``first(i) + s``, or tile
    ``s`` where ``first`` is None (no window: every tile); tiles past the
    diagonal are skipped.  With a window the walk starts at the first tile
    the window reaches and takes as many steps as the widest reach."""
    bq, bk = FLASH_BLOCK_Q, FLASH_BLOCK_K
    # nerrflint: ok[recompile-hazard] window is None or a Python int of the static configuration (`StreamConfig.window`), never a traced value: it shapes the program
    if window is None:
        return t // bk, None
    steps = max(((i + 1) * bq - 1) // bk - max(i * bq - window + 1, 0) // bk
                + 1 for i in range(t // bq))
    return steps, lambda i: jnp.maximum(i * bq - window + 1, 0) // bk


def _backward_walk(t: int, window):
    """The backward grid's walk over the query tiles that meet key tile
    ``j`` -> (steps, first, last): step ``s`` reads query tile ``first(j) +
    s`` up to ``last(j)``, or tile ``s`` where ``first`` is None (no window:
    every tile, those before the diagonal skipped)."""
    bq, bk = FLASH_BLOCK_Q, FLASH_BLOCK_K
    # nerrflint: ok[recompile-hazard] window is None or a Python int of the static configuration (`StreamConfig.window`), never a traced value: it shapes the program
    if window is None:
        return t // bq, None, None
    reach = lambda j: min(((j + 1) * bk - 2 + window) // bq, t // bq - 1)
    steps = max(reach(j) - (j * bk) // bq + 1 for j in range(t // bk))
    return (steps, lambda j: (j * bk) // bq,
            lambda j: jnp.minimum(((j + 1) * bk - 2 + window) // bq,
                                  t // bq - 1))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, q_seg_ref, k_seg_ref, o_ref,
                      lse_ref, m_ref, l_ref, acc_ref, *, scale, bq, bk,
                      window, first):
    """One (head, query tile, key tile) step of the online softmax: ``m``
    the running maximum, ``l`` the running sum (both lane-replicated
    [bq, 128]), ``acc`` the un-normalised output [bq, d]."""
    from jax.experimental import pallas as pl

    i, step = pl.program_id(1), pl.program_id(2)
    j = step if first is None else first(i) + step

    @pl.when(step == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -1e9, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # a key tile wholly above the diagonal is skipped
    @pl.when(j * bk < (i + 1) * bq)
    def _():
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(_tile_mask(
            jnp.tile(q_seg_ref[...], (1, bk // _LANES)), k_seg_ref[:1, :],
            i * bq, j * bk, (bq, bk), 0, window), s, -1e9)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - jnp.tile(m_next, (1, bk // _LANES)))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = (
            acc_ref[...] * jnp.tile(alpha, (1, acc_ref.shape[1] // _LANES))
            + jnp.dot(p.astype(v_ref.dtype), v_ref[...],
                      preferred_element_type=jnp.float32))

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.tile(
            l, (1, acc_ref.shape[1] // _LANES))).astype(o_ref.dtype)
        # the log-sum-exp leaves as one row of the compact [H, 1, T]
        lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1]


def _flash_bwd_kernel(q_ref, k_ref, v_ref, d_o_ref, lse_ref, rows_ref,
                      q_seg_ref, k_seg_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                      dk_acc, dv_acc, *, scale, bq, bk, window, first, last):
    """One (head, key tile, query tile) step of the backward pass, scores
    transposed ([bk, bq]: the queries' log-sum-exp and ``rows`` = sum(d_o *
    o) are then plain rows).  The scores are computed once for all three
    gradients: ``dk``, ``dv`` gather over the query tiles in ``dk_acc``,
    ``dv_acc``; ``dq`` over the key tiles in ``dq_acc``, the head's whole
    [T, d] float32, which like ``dq_ref``, the head's block of the output,
    stays in fast memory while the head's tiles run."""
    from jax.experimental import pallas as pl

    j, step = pl.program_id(1), pl.program_id(2)
    i = step if first is None else first(j) + step
    last_step = pl.num_programs(2) - 1

    @pl.when((j == 0) & (step == 0))
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    @pl.when(step == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(j * bk < (i + 1) * bq if first is None else i <= last(j))
    def _():
        q, k, d_o = q_ref[...], k_ref[...], d_o_ref[...]
        f32 = dict(preferred_element_type=jnp.float32)
        s = jax.lax.dot_general(k, q, _NT, **f32) * scale
        prob = jnp.where(_tile_mask(
            q_seg_ref[:1, :], jnp.tile(k_seg_ref[...], (1, bq // _LANES)),
            i * bq, j * bk, (bk, bq), 1, window), jnp.exp(s - lse_ref[...]),
            0.0)
        dv_acc[...] += jnp.dot(prob.astype(d_o.dtype), d_o, **f32)
        d_prob = jax.lax.dot_general(v_ref[...], d_o, _NT, **f32)
        d_s = (prob * (d_prob - rows_ref[...]) * scale).astype(q.dtype)
        dk_acc[...] += jnp.dot(d_s, q, **f32)
        dq_acc[pl.ds(pl.multiple_of(i * bq, bq), bq), :] += (
            jax.lax.dot_general(d_s, k, _TN, **f32))

    @pl.when(step == last_step)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((j == pl.num_programs(1) - 1) & (step == last_step))
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _seg_operands(seg):
    """``seg`` [T] as the two small operands a tile reads it from: along the
    sublanes [T, 128] and along the lanes [8, T]."""
    t = seg.shape[0]
    return (jnp.broadcast_to(seg[:, None], (t, _LANES)),
            jnp.broadcast_to(seg[None, :], (_SUBLANES, t)))


def _flash_params(semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=FLASH_VMEM_BYTES)


def _kv_head(q, k):
    """Query head ``h`` -> the key-value head it reads (``h // group``)."""
    group = q.shape[0] // k.shape[0]
    return group, (lambda h: h) if group == 1 else (lambda h: h // group)


def _flash_forward(q, k, v, seg, window=None):
    """``q`` [H, T, d], ``k``, ``v`` [Hk, T, d] -> (o [H, T, d], lse [H, T]
    float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bq, bk = FLASH_BLOCK_Q, FLASH_BLOCK_K
    h, t, d = q.shape
    _, kv = _kv_head(q, k)
    steps, first = _forward_walk(t, window)
    # a skipped step asks for the key tile it already holds: nothing is
    # copied for it
    held = lambda i, s: jnp.minimum(s if first is None else first(i) + s,
                                    ((i + 1) * bq - 1) // bk)
    q_spec = pl.BlockSpec((None, bq, d), lambda h, i, s: (h, i, 0))
    k_spec = pl.BlockSpec((None, bk, d),
                          lambda h, i, s: (kv(h), held(i, s), 0))
    o, lse = pl.pallas_call(
        partial(_flash_fwd_kernel, scale=d ** -0.5, bq=bq, bk=bk,
                window=window, first=first),
        grid=(h, t // bq, steps),
        in_specs=[q_spec, k_spec, k_spec,
                  pl.BlockSpec((bq, _LANES), lambda h, i, s: (i, 0)),
                  pl.BlockSpec((_SUBLANES, bk),
                               lambda h, i, s: (0, held(i, s)))],
        out_specs=[q_spec,
                   pl.BlockSpec((None, 1, bq), lambda h, i, s: (h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((h, 1, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_flash_params(("parallel", "parallel", "arbitrary")),
        name="mla_flash_fwd",
    )(q, k, v, *_seg_operands(seg))
    return o, lse[:, 0]


def _flash_backward(q, k, v, seg, o, lse, d_o, window=None):
    """-> (dq, dk, dv) in the operands' type.  With grouped heads the
    kernel writes each query head's share of ``dk``, ``dv`` and the shares
    of a group are added after it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bq, bk = FLASH_BLOCK_Q, FLASH_BLOCK_K
    h, t, d = q.shape
    group, kv = _kv_head(q, k)
    steps, first, last = _backward_walk(t, window)
    rows = jnp.sum(d_o.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    seg_sublanes, seg_lanes = _seg_operands(seg)
    # a skipped step asks for the query tile it will hold next: the first
    # that meets the key tile (with a window: the last)
    held = ((lambda j, s: jnp.maximum(s, (j * bk) // bq)) if first is None
            else (lambda j, s: jnp.minimum(first(j) + s, last(j))))
    q_spec = pl.BlockSpec((None, bq, d), lambda h, j, s: (h, held(j, s), 0))
    k_spec = pl.BlockSpec((None, bk, d), lambda h, j, s: (kv(h), j, 0))
    share_spec = pl.BlockSpec((None, bk, d), lambda h, j, s: (h, j, 0))
    row_spec = pl.BlockSpec((None, 1, bq), lambda h, j, s: (h, 0, held(j, s)))
    share = jax.ShapeDtypeStruct((h, t, d), k.dtype)
    dq, dk, dv = pl.pallas_call(
        partial(_flash_bwd_kernel, scale=d ** -0.5, bq=bq, bk=bk,
                window=window, first=first, last=last),
        grid=(h, t // bk, steps),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec,
                  pl.BlockSpec((_SUBLANES, bq),
                               lambda h, j, s: (0, held(j, s))),
                  pl.BlockSpec((bk, _LANES), lambda h, j, s: (j, 0))],
        out_specs=[pl.BlockSpec((None, t, d), lambda h, j, s: (h, 0, 0)),
                   share_spec, share_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype) if group == 1
                   else share,
                   jax.ShapeDtypeStruct(v.shape, v.dtype) if group == 1
                   else share],
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_flash_params(("parallel", "arbitrary", "arbitrary")),
        name="mla_flash_bwd",
    )(q, k, v, d_o, lse[:, None], rows[:, None], seg_lanes, seg_sublanes)
    if group > 1:
        fold = lambda g: jnp.sum(g.reshape((k.shape[0], group, t, d)).astype(
            jnp.float32), axis=1).astype(k.dtype)
        dk, dv = fold(dk), fold(dv)
    return dq, dk, dv


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash(q, k, v, seg, window, scope):
    return _flash_forward(q, k, v, seg, window)[0]


def _flash_fwd(q, k, v, seg, window, scope):
    o, lse = _flash_forward(q, k, v, seg, window)
    # the same residuals under the same name as the other route's
    o, lse = checkpoint_name(o, SAVED), checkpoint_name(lse, SAVED)
    return o, (q, k, v, seg, o, lse)


def _flash_bwd(window, scope, res, d_o):
    with jax.named_scope(scope):
        return _flash_backward(*res, d_o, window) + (None,)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attention(q, k, v, seg, *, block: int = None, span: int = None,
              window: int = None, scope: str = "mla_attention"):
    """One packed sequence.  ``q`` [T, H, d], ``k`` [T, Hk, d], ``v`` [T,
    Hk, dv] in the compute type, ``seg`` [T] -> o [T, H, dv]: causal
    softmax attention inside the query's document, scores scaled by ``d **
    -0.5``, softmax in float32.  Padding (``seg`` 0) attends within itself
    like a document.  Grouped heads: query head ``h`` reads key-value head
    ``h // (H / Hk)``.  A ``window`` w keeps the keys no more than w - 1
    positions back.  ``block`` queries at a time, keys up to the next
    multiple of ``span`` (`ops/dsa.py`'s defaults; with a window, at most w
    queries a block, its keys from the block's start - w to its end).  ``scope`` names the core in a trace,
    forward and backward."""
    with jax.named_scope(scope):
        # heads lead inside: a block's two products are then plain batched
        # matmuls (with the heads in the middle the TPU compiler writes
        # them as dilated convolutions)
        heads_first = lambda x: jnp.swapaxes(x, 0, 1)
        route = attention_route(q.shape[0], q.shape[-1], v.shape[-1])
        q, k, v = heads_first(q), heads_first(k), heads_first(v)
        if route == "pallas_flash":
            o = _flash(q, k, v, seg, window, scope)
        else:
            group = q.shape[0] // k.shape[0]
            if group > 1:
                # the blocked form reads each query head's own copy; the
                # copies' gradients add up to the shared heads'
                k, v = (jnp.repeat(x, group, axis=0) for x in (k, v))
            o = _attention(q, k, v, seg, block, span, window, scope)
        # what reads ``o`` (the output projection's gradient) would
        # otherwise make a remat that keeps `SAVED` run the forward pass
        # again for it
        return checkpoint_name(heads_first(o), SAVED)
