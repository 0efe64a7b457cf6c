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


def _pair_mask(q_pos, k_pos, causal, window, q_seg=None, k_seg=None):
    """Which (query, key) pairs may attend: ``q_pos`` [Tq, 1], ``k_pos``
    [1, Tk] -> [1, Tq, Tk], or [B, Tq, Tk] with segment ids [B, Tq] /
    [B, Tk] (a pair in two documents never attends).  ``window`` w keeps
    the keys with ``0 <= q_pos - k_pos < w``.  None where nothing is
    masked."""
    valid = None
    if causal:
        valid = (k_pos <= q_pos)[None]
    if window is not None:
        near = (q_pos - k_pos < window)[None]
        valid = near if valid is None else valid & near
    if q_seg is not None:
        same = q_seg[:, :, None] == k_seg[:, None, :]
        valid = same if valid is None else valid & same
    return valid


def _attention_dense(q, k, v, causal: bool, *, window=None, q_seg=None,
                     k_seg=None) -> jnp.ndarray:
    """Plain materialized attention — the reference semantics both the ring
    and the blockwise local path must reproduce.  O(T²) memory: use only for
    tests/small shapes.  q,k: [B, T, H, D], v: [B, T, H, Dv] → [B, T, H, Dv]."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    tq, tk = q.shape[1], k.shape[1]
    valid = _pair_mask(jnp.arange(tq)[:, None], jnp.arange(tk)[None, :],
                       causal, window, q_seg, k_seg)
    if valid is not None:
        scores = jnp.where(valid[:, None], scores, _NEG)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


_LOCAL_BLOCK = 512


def _attention_local(q, k, v, causal: bool, *, window=None, q_seg=None,
                     k_seg=None) -> jnp.ndarray:
    """Exact single-device attention, blockwise (flash-style).

    Queries are processed one block at a time; each query block visits only
    the key blocks its mask can reach: 0..i under the causal mask, and of
    those only the ones inside ``window`` (keys with ``0 <= q_pos - k_pos <
    window``), so no FLOPs are spent on blocks the positions mask out
    entirely.  With segment ids (``q_seg`` [B, Tq], ``k_seg`` [B, Tk]: packed
    documents) a pair in two documents never attends, and a block below the
    diagonal whose documents cannot meet the query block's is skipped at run
    time (`lax.cond` on the blocks' id ranges).  ``k`` / ``v`` may come from
    another layer and ``v`` may be wider than ``q`` / ``k`` (differential
    attention hands the value pair ``[V1, V2]`` to both of its softmaxes,
    each a head here).  Online-softmax accumulation keeps peak memory
    O(block²) — never the [B, H, T, T] score tensor, which at bench stream
    shapes is gigabytes of HBM traffic per layer.  Matmuls run in the input
    dtype (bf16 on TPU → MXU rate); accumulation is float32."""
    b, t, h, d = q.shape
    dv = v.shape[-1]
    if t <= 2 * _LOCAL_BLOCK:
        return _attention_dense(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal, window=window, q_seg=q_seg,
            k_seg=k_seg).astype(q.dtype)
    block = _LOCAL_BLOCK
    pad = (-t) % block
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if q_seg is not None:
            q_seg = jnp.pad(q_seg, ((0, 0), (0, pad)), constant_values=-1)
            k_seg = jnp.pad(k_seg, ((0, 0), (0, pad)), constant_values=-1)
    tp = q.shape[1]
    nb = tp // block
    scale = d ** -0.5
    segmented = q_seg is not None

    k_blocks = k.reshape(b, nb, block, h, d).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, nb, block, h, dv).transpose(1, 0, 2, 3, 4)
    if segmented:
        ks_blocks = k_seg.reshape(b, nb, block).transpose(1, 0, 2)
        ks_lo, ks_hi = ks_blocks.min(axis=(1, 2)), ks_blocks.max(axis=(1, 2))
    in_pos = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)

    def block_step(q_blk, q_pos, qs_blk, carry, blk, masked):
        """One (q-block, k-block) flash update.  masked=True applies the
        positional masks (causal triangle, window edge, key padding); a
        block strictly inside them needs none.  The segment mask applies
        to every block of a packed sequence."""
        o, m, l, k_pos0 = carry
        k_blk, v_blk = blk[:2]
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q_blk, k_blk,
            preferred_element_type=jnp.float32) * scale
        k_pos = k_pos0 + in_pos
        valid = _pair_mask(q_pos, k_pos, causal and masked,
                           window if masked else None, qs_blk,
                           blk[2] if segmented else None)
        if masked and pad:
            valid = (k_pos < t)[None] if valid is None else valid & (k_pos < t)
        if valid is not None:
            # -1e9 stays far inside bf16 range (±1e30 NaNs bf16 cotangents)
            scores = jnp.where(valid[:, None], scores, -1e9)
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
        lambda qb, qp, qs, c, blk: block_step(qb, qp, qs, c, blk, False),
        prevent_cse=False)
    remat_diag = jax.checkpoint(
        lambda qb, qp, qs, c, blk: block_step(qb, qp, qs, c, blk, True),
        prevent_cse=False)

    def blocks_of(lo, hi):
        out = (k_blocks[lo:hi], v_blocks[lo:hi])
        return out + (ks_blocks[lo:hi],) if segmented else out

    outs = []
    for i in range(nb):
        q_blk = q[:, i * block:(i + 1) * block]
        q_pos = i * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        qs_blk = q_seg[:, i * block:(i + 1) * block] if segmented else None
        # key blocks the positions can reach, and of them the ones that lie
        # strictly inside both masks (every pair attends: no mask needed)
        first = 0
        if causal and window is not None:
            first = max(0, (i * block - (window - 1)) // block)
        last = i if causal else nb - 1
        n_inner = 0
        if causal:
            inner = [j for j in range(first, i)
                     if window is None
                     or (i + 1) * block - 1 - j * block < window]
            # the unmasked blocks are a contiguous run that ends at i - 1
            first_inner = inner[0] if inner else i
            n_inner = len(inner)
        else:
            first_inner = last + 1
        o0 = jnp.zeros((b, block, h, dv), jnp.float32)
        m0 = jnp.full((b, h, block), -1e9, jnp.float32)
        l0 = jnp.zeros((b, h, block), jnp.float32)
        carry = (o0, m0, l0, first * block)
        edge = [j for j in range(first, last + 1)
                if not first_inner <= j < first_inner + n_inner]
        for j in [j for j in edge if j < first_inner]:
            carry = remat_diag(q_blk, q_pos, qs_blk, carry,
                               tuple(x[0] for x in blocks_of(j, j + 1)))
        if n_inner:
            def inner_step(c, blk):
                if not segmented:
                    return remat_step(q_blk, q_pos, qs_blk, c, blk), None
                # documents are contiguous: the blocks can share one only
                # if their id ranges overlap
                meet = ((blk[3] <= qs_blk.max()) & (qs_blk.min() <= blk[4]))
                skip = lambda c, blk: c[:3] + (c[3] + block,)
                return jax.lax.cond(
                    meet,
                    lambda c, blk: remat_step(q_blk, q_pos, qs_blk, c, blk),
                    skip, c, blk[:3]), None

            xs = blocks_of(first_inner, first_inner + n_inner)
            if segmented:
                xs = xs + (ks_lo[first_inner:first_inner + n_inner],
                           ks_hi[first_inner:first_inner + n_inner])
            carry = jax.lax.scan(inner_step, carry, xs)[0]
        for j in [j for j in edge if j >= first_inner]:
            carry = remat_diag(q_blk, q_pos, qs_blk, carry,
                               tuple(x[0] for x in blocks_of(j, j + 1)))
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
    window: Optional[int] = None,
    q_seg: Optional[jnp.ndarray] = None,
    k_seg: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Exact attention over [B, T, H, D], sequence-sharded when sp > 1.

    With no mesh (or sp == 1) this is ordinary attention; with sp > 1 the
    T axis is chunked over the ``sp`` mesh axis and K/V blocks rotate over
    ICI.  B stays sharded over ``dp`` (no communication on that axis).

    ``window`` and the segment ids of packed documents (see
    `_attention_local`) exist on the local path only: the ring knows
    causal-full attention over whole sequences and refuses the rest.
    """
    if mesh is None or mesh.shape.get(seq_axis, 1) == 1:
        # blockwise local path: keeps matmul inputs in their compute dtype
        # (bf16 → MXU rate) and accumulates in f32 internally
        return _attention_local(q, k, v, causal, window=window, q_seg=q_seg,
                                k_seg=k_seg)
    if window is not None or q_seg is not None or v.shape != k.shape:
        raise NotImplementedError(
            "ring attention (sp > 1) runs causal-full attention over whole "
            "sequences: no window, no packed documents, no values wider "
            "than the keys")

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
