"""Ring attention: sequence-parallel exact attention over the ``sp`` mesh axis.

The long-context path of the framework.  The reference has no sequence
parallelism of any kind (SURVEY.md §2.3) — its longest sequence is the
LSTM's 100-event window — but NERRF's real input is an unbounded syscall
stream (the spec'd corpus is 100 h of traces, `ROADMAP.md:50`), and a
whole-stream attention detector needs sequences far past one chip's HBM.

Design: flash-style blockwise softmax accumulation + K/V rotation.  Each
``sp`` shard holds one contiguous chunk of Q/K/V; at every step it computes
its queries against the K/V block it currently holds, folds the result into
an online-softmax accumulator (running max ``m``, denominator ``l``,
numerator ``o``), then passes the block to its ring neighbor with
`lax.ppermute` — XLA lowers the rotation onto ICI, overlapping it with the
block matmuls.  After P steps every query has seen every key exactly once;
memory stays O(chunk²) per device and the result is *exact* attention, not
an approximation.  (Blockwise/ring formulation per the public Ring Attention
literature; see PAPERS.md.)

Causality is global: chunk offsets are derived from `lax.axis_index`, so the
mask is identical to single-device causal attention.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG = -1e30


def _attention_dense(q, k, v, causal: bool) -> jnp.ndarray:
    """Plain materialized attention — the reference semantics both the ring
    and the blockwise local path must reproduce.  O(T²) memory: use only for
    tests/small shapes.  q,k,v: [B, T, H, D] → [B, T, H, D]."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        scores = jnp.where(mask[None, None], scores, _NEG)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


_LOCAL_BLOCK = 512


def _attention_local(q, k, v, causal: bool) -> jnp.ndarray:
    """Exact single-device attention, blockwise (flash-style).

    Queries are processed one block at a time; each query block scans only
    the key blocks its causal mask can reach (0..i), so no FLOPs are spent
    on fully-masked future blocks — at T=4096 that halves attention compute
    vs the naive all-blocks scan.  Online-softmax accumulation keeps peak
    memory O(block²) — never the [B, H, T, T] score tensor, which at bench
    stream shapes is gigabytes of HBM traffic per layer.  Matmuls run in the
    input dtype (bf16 on TPU → MXU rate); accumulation is float32."""
    b, t, h, d = q.shape
    if t <= 2 * _LOCAL_BLOCK:
        return _attention_dense(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal).astype(q.dtype)
    block = _LOCAL_BLOCK
    pad = (-t) % block
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    tp = q.shape[1]
    nb = tp // block
    scale = d ** -0.5

    k_blocks = k.reshape(b, nb, block, h, d).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, nb, block, h, d).transpose(1, 0, 2, 3, 4)
    in_pos = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)

    def block_step(q_blk, q_pos, carry, blk, masked):
        """One (q-block, k-block) flash update.  masked=True applies the
        intra-block causal triangle + key-padding mask (diagonal block);
        off-diagonal blocks below the diagonal need no mask at all."""
        o, m, l, k_pos0 = carry
        k_blk, v_blk = blk
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q_blk, k_blk,
            preferred_element_type=jnp.float32) * scale
        if masked:
            k_pos = k_pos0 + in_pos
            valid = k_pos < t
            if causal:
                valid = valid & (k_pos <= q_pos)
            # -1e9 stays far inside bf16 range (±1e30 NaNs bf16 cotangents)
            scores = jnp.where(valid[None, None], scores, -1e9)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        pexp = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + pexp.sum(axis=-1)
        # second matmul in compute dtype too: pexp ∈ [0,1] is safe in bf16,
        # and an f32×bf16 einsum would fall off the MXU fast path
        o = alpha.transpose(0, 2, 1)[..., None] * o + jnp.einsum(
            "bhqk,bkhd->bqhd", pexp.astype(q.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return o, m_new, l, k_pos0 + block

    # Remat each block update: without it, reverse-mode saves scores/pexp
    # ([B,H,block,block] f32) for every block pair of every layer — at bench
    # shapes that is ~13 GB of residuals and OOMs a v5e chip (BENCH_r01
    # stream leg failure).  Checkpointing recomputes the two block matmuls
    # in the backward pass; only the O(block·D) carries are stored.
    remat_step = jax.checkpoint(
        lambda qb, qp, c, blk: block_step(qb, qp, c, blk, False),
        prevent_cse=False)
    remat_diag = jax.checkpoint(
        lambda qb, qp, c, blk: block_step(qb, qp, c, blk, True),
        prevent_cse=False)

    outs = []
    for i in range(nb):
        q_blk = q[:, i * block:(i + 1) * block]
        q_pos = i * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        o0 = jnp.zeros((b, block, h, d), jnp.float32)
        m0 = jnp.full((b, h, block), -1e9, jnp.float32)
        l0 = jnp.zeros((b, h, block), jnp.float32)
        carry = (o0, m0, l0, 0)
        n_full = i if causal else 0
        if n_full:
            carry = jax.lax.scan(
                lambda c, blk: (remat_step(q_blk, q_pos, c, blk), None),
                carry, (k_blocks[:n_full], v_blocks[:n_full]))[0]
        lo = n_full
        hi = i + 1 if causal else nb
        for j in range(lo, hi):
            carry = remat_diag(q_blk, q_pos, carry,
                               (k_blocks[j], v_blocks[j]))
        o, m, l, _ = carry
        outs.append(o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None])
    out = jnp.concatenate(outs, axis=1)
    if pad:
        out = out[:, :t]
    return out.astype(q.dtype)


def _ring_shard(q, k, v, *, axis_name: str, manual_axes: tuple, causal: bool) -> jnp.ndarray:
    """Per-shard body under shard_map.  q,k,v: [B, C, H, D] local chunks."""
    p = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, c, h, d = q.shape
    scale = d ** -0.5

    q32 = q.astype(jnp.float32)
    q_pos = my * c + jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)  # [C,1] global

    # fresh zeros are axis-invariant; mark them varying over the manual axes
    # so the fori_loop carry type matches its (varying) outputs
    pv = lambda x: jax.lax.pcast(x, manual_axes, to="varying")
    o0 = pv(jnp.zeros((b, c, h, d), jnp.float32))
    m0 = pv(jnp.full((b, h, c), _NEG, jnp.float32))
    l0 = pv(jnp.zeros((b, h, c), jnp.float32))
    perm = [(j, (j + 1) % p) for j in range(p)]

    def step(i, carry):
        o, m, l, k_blk, v_blk = carry
        src = (my - i) % p  # original owner of the block we hold now

        def attend(o, m, l):
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)
            ) * scale
            if causal:
                k_pos = src * c + jax.lax.broadcasted_iota(
                    jnp.int32, (1, c), 1)
                scores = jnp.where((k_pos <= q_pos)[None, None], scores, _NEG)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            pexp = jnp.exp(scores - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + pexp.sum(axis=-1)
            o_new = alpha.transpose(0, 2, 1)[..., None] * o + jnp.einsum(
                "bhqk,bkhd->bqhd", pexp, v_blk.astype(jnp.float32)
            )
            return o_new, m_new, l_new

        if causal:
            # a block from a strictly-future shard (src > my) is entirely
            # masked — min k_pos = src·c exceeds max q_pos = my·c + c − 1 —
            # so skip both matmuls; the ring rotation below still runs every
            # hop (identical collective schedule on every shard)
            o, m, l = jax.lax.cond(
                src <= my, attend, lambda o, m, l: (o, m, l), o, m, l)
        else:
            o, m, l = attend(o, m, l)
        k_blk, v_blk = jax.lax.ppermute((k_blk, v_blk), axis_name, perm)
        return o, m, l, k_blk, v_blk

    # same residual blow-up as the local path: remat each ring step so the
    # backward pass recomputes scores instead of storing one [B,H,C,C] f32
    # tensor per ring hop per layer
    o, m, l, _, _ = jax.lax.fori_loop(
        0, p, jax.checkpoint(step, prevent_cse=False), (o0, m0, l0, k, v))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Optional[Mesh] = None,
    *,
    seq_axis: str = "sp",
    batch_axis: str = "dp",
    causal: bool = True,
) -> jnp.ndarray:
    """Exact attention over [B, T, H, D], sequence-sharded when sp > 1.

    With no mesh (or sp == 1) this is ordinary attention; with sp > 1 the
    T axis is chunked over the ``sp`` mesh axis and K/V blocks rotate over
    ICI.  B stays sharded over ``dp`` (no communication on that axis).
    """
    if mesh is None or mesh.shape.get(seq_axis, 1) == 1:
        # blockwise local path: keeps matmul inputs in their compute dtype
        # (bf16 → MXU rate) and accumulates in f32 internally
        return _attention_local(q, k, v, causal)

    spec = P(batch_axis, seq_axis, None, None)
    fn = shard_map(
        partial(
            _ring_shard,
            axis_name=seq_axis,
            manual_axes=(batch_axis, seq_axis),
            causal=causal,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
