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

`attention` is `ops/dsa.py::sparse_attention`'s dense twin, plain XLA: a
block of queries at a time against the keys before the block's end, rounded
up to a span (one `lax.switch` branch per key length), a block's float32
scores alive only while it is computed.  Its derivative is written by hand
(`jax.custom_vjp`): the forward pass keeps ``o`` and each head's
log-sum-exp under the name `SAVED`, the flash-style backward pass computes a
block's scores once more from them.  Reverse mode through the scan would
keep or recompute every block's scores, and the operations of a transposed
`lax.switch` carry no scope of their own.
"""

from __future__ import annotations

from functools import partial

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


def assemble(q, k_r, kv, pos, *, nope: int, theta: float):
    """One packed sequence.  ``q`` [T, H, nope + rope] (a head's un-rotated
    part, then its rotary part, not yet rotated), ``k_r`` [T, rope] (the one
    rotary key a token, not yet rotated), ``kv`` [T, H, nope + dv] (a head's
    un-rotated key, then its value), ``pos`` [T] the positions inside the
    document -> (q [T, H, nope + rope], k [T, H, nope + rope], v [T, H, dv]):
    both rotary parts rotated over all their dimensions, the one rotated
    ``k_r`` behind every head's un-rotated key."""
    t, heads, _ = q.shape
    k_r = dsa.rope(k_r[:, None, :], pos, theta)
    q = jnp.concatenate([q[..., :nope],
                         dsa.rope(q[..., nope:], pos, theta)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r, (t, heads, k_r.shape[-1]))], -1)
    return q, k, kv[..., nope:]


def _block_forward(q, k, v, q_seg, k_seg, q_pos0):
    """One block of queries against the keys ``[0, L)``, heads leading:
    ``q`` [H, Q, d], ``k`` [H, L, d], ``v`` [H, L, dv] -> (o [H, Q, dv], the
    heads' log-sum-exp [H, Q])."""
    valid = dsa._allowed(q.shape[1], k.shape[1], q_seg, k_seg, q_pos0)
    logits = jnp.einsum("hqd,hld->hql", q, k,
                        preferred_element_type=jnp.float32)
    logits = jnp.where(valid, logits * q.shape[-1] ** -0.5, -1e9)
    top = jnp.max(logits, axis=-1, keepdims=True)
    pexp = jnp.exp(logits - top)
    norm = jnp.sum(pexp, axis=-1, keepdims=True)
    o = jnp.einsum("hql,hld->hqd", pexp.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    return (o / norm).astype(q.dtype), (top + jnp.log(norm))[..., 0]


def _block_backward(q, k, v, o, lse, d_o, q_seg, k_seg, q_pos0):
    """Flash-style backward of one block: the scores are computed again
    from ``q``, ``k`` and the saved log-sum-exp -> (dq [H, Q, d], dk [H, L,
    d], dv [H, L, dv] float32)."""
    dt = q.dtype
    scale = q.shape[-1] ** -0.5
    f32 = dict(preferred_element_type=jnp.float32)
    valid = dsa._allowed(q.shape[1], k.shape[1], q_seg, k_seg, q_pos0)
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


def _forward(q, k, v, seg, block, span):
    t = q.shape[1]
    block, span = dsa._spans(t, block or QUERY_BLOCK, span or KEY_SPAN)

    def branch(n):
        return lambda q_b, seg_b, lo: _block_forward(
            q_b, k[:, :n], v[:, :n], seg_b, seg[:n], lo)

    branches = [branch(n) for n in range(span, t + 1, span)]
    o, lse = jax.lax.map(
        lambda xs: jax.lax.switch(xs[-1] // span, branches, *xs),
        (_query_blocks(q, block), dsa._blocks(seg, block),
         jnp.arange(0, t, block)))
    return _join(o), _join(lse)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attention(q, k, v, seg, block, span):
    return _forward(q, k, v, seg, block, span)[0]


def _attention_fwd(q, k, v, seg, block, span):
    o, lse = _forward(q, k, v, seg, block, span)
    # named so that a layer's remat can keep them (`SAVED`): what the
    # backward pass needs of the forward pass's [T, T] work
    o, lse = checkpoint_name(o, SAVED), checkpoint_name(lse, SAVED)
    return o, (q, k, v, seg, o, lse)


def _attention_bwd(block, span, res, d_o):
    q, k, v, seg, o, lse = res
    t = q.shape[1]
    block, span = dsa._spans(t, block or QUERY_BLOCK, span or KEY_SPAN)

    def branch(n):
        # a block's dk, dv are added into the rows it read, in place: the
        # accumulators are [H, T, 256] float32 (168 MB each at 20 heads),
        # and padding a block's share to T and adding it whole moved them
        # through HBM three times an iteration
        def run(dk, dv, q_b, o_b, lse_b, d_o_b, seg_b, lo):
            dq, dk_b, dv_b = _block_backward(q_b, k[:, :n], v[:, :n], o_b,
                                             lse_b, d_o_b, seg_b, seg[:n], lo)
            return dk.at[:, :n].add(dk_b), dv.at[:, :n].add(dv_b), dq
        return run

    branches = [branch(n) for n in range(span, t + 1, span)]

    def step(carry, xs):
        dk, dv, dq = jax.lax.switch(xs[-1] // span, branches, *carry, *xs)
        return (dk, dv), dq

    with jax.named_scope("mla_attention"):
        (dk, dv), dq = jax.lax.scan(
            step, (jnp.zeros(k.shape, jnp.float32),
                   jnp.zeros(v.shape, jnp.float32)),
            (_query_blocks(q, block), _query_blocks(o, block),
             _query_blocks(lse, block), _query_blocks(d_o, block),
             dsa._blocks(seg, block), jnp.arange(0, t, block)))
    return (_join(dq).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), None)


_attention.defvjp(_attention_fwd, _attention_bwd)


def attention(q, k, v, seg, *, block: int = None, span: int = None):
    """One packed sequence.  ``q``, ``k`` [T, H, d], ``v`` [T, H, dv] in
    the compute type, ``seg`` [T] -> o [T, H, dv]: causal softmax attention
    inside the query's document, scores scaled by ``d ** -0.5``, softmax in
    float32.  Padding (``seg`` 0) attends within itself like a document.
    ``block`` queries at a time, keys up to the next multiple of ``span``
    (`ops/dsa.py`'s defaults)."""
    with jax.named_scope("mla_attention"):
        # heads lead inside: a block's two products are then plain batched
        # matmuls (with the heads in the middle the TPU compiler writes
        # them as dilated convolutions)
        heads_first = lambda x: jnp.swapaxes(x, 0, 1)
        o = _attention(heads_first(q), heads_first(k), heads_first(v), seg,
                       block, span)
        # what reads ``o`` (the output projection's gradient) would
        # otherwise make a remat that keeps `SAVED` run the forward pass
        # again for it
        return checkpoint_name(heads_first(o), SAVED)
