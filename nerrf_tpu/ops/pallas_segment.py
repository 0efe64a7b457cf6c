"""Hand-tiled Pallas TPU kernels for sparse neighbor aggregation.

The reference framework never built its AI subsystem, so it has no sparse ops;
the north star requires neighbor aggregation and sampling gathers as Pallas
kernels (SURVEY.md §7 step 2).  On TPU the fastest formulation of a segment
reduction at our graph sizes (N ≤ a few thousand nodes, E ≤ a few thousand
edges, F ≤ 512 features) is *not* a scatter at all — scatters serialize on the
VPU — but a one-hot contraction that rides the 128×128 MXU:

    out[n, f] = Σ_e [seg_ids[e] == n] · data[e, f]

i.e. ``onehotᵀ @ data``.  The kernel tiles (segments × features) over the grid
and accumulates over edge tiles, building each one-hot block in VMEM with a
broadcasted iota compare (never materializing the full [E, N] matrix in HBM).
The same trick gives the row gather ``table[idx]`` as ``onehot @ table``.

Both kernels are order-independent (no sorted-ids requirement) and carry
custom VJPs — the adjoint of a segment-sum is a row gather and vice versa, so
the backward passes reuse the same two kernels.

Use :func:`register` to install the segment sums (and the fused SAGE
aggregate) behind ``nerrf_tpu.ops``; ``segment.py`` registers them on first
use when the active backend is TPU.  The row-gather kernels serve as the
segment sums' adjoints only: ``nerrf_tpu.ops.gather_rows`` itself is
compiler-written on a TPU (one selection matmul each way, 14-29x faster than
`gather_rows` here from 1024 to 8192 nodes: `ops.segment.SELECTION_MATMUL_MAX_ROWS`
has the chip's sweep), and since then no bucket the repo ships reaches any
kernel of this module through the model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tile sizes: lane dim is always 128; 128 edge rows per accumulation step
# keeps the one-hot block square on the MXU.
_TN = 128  # segment (output-row) tile
_TE = 128  # edge (contraction) tile
_TF = 128  # feature tile

# Every contraction here is f32 in, f32 out, and stands in for an XLA scatter
# or gather that is exact.  The MXU's default pass rounds f32 operands to
# bf16 — harmless for the 0/1 one-hot block, but the data operand (and the
# fused kernel's weighted one-hot) lose 16 mantissa bits: ~2e-2 absolute
# error against the XLA composition on a v5e at N(0,1) inputs.  HIGHEST keeps
# the contraction in f32 (≤1e-6 there), which is what lets kernel and oracle
# be compared on the chip at the tolerance the CPU tests use.
_PRECISION = jax.lax.Precision.HIGHEST


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value) -> jnp.ndarray:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


def _band_ptrs(ids, n_pad):
    """Band pointers over a nondecreasing [Ep, 1] id column padded with
    ``n_pad``: edges for segment tile i live in edge tiles [t0[i], t1[i]).
    Shared by every banded kernel (sorted segment sum, fused SAGE) so the
    out-of-band convention cannot desynchronize between them."""
    bounds = jnp.searchsorted(
        ids[:, 0], jnp.arange(0, n_pad + 1, _TN, dtype=jnp.int32))
    return ((bounds[:-1] // _TE).astype(jnp.int32),
            ((bounds[1:] + _TE - 1) // _TE).astype(jnp.int32))


# --- segment sum -------------------------------------------------------------


def _accumulate_onehot(ids_ref, data_ref, out_ref, seg_base):
    """out += onehot(ids, seg_base..seg_base+TN)ᵀ @ data — the shared MXU
    contraction body of both segment-sum kernels."""
    ids = ids_ref[:]  # [TE, 1] int32
    cols = jax.lax.broadcasted_iota(jnp.int32, (_TE, _TN), 1) + seg_base
    onehot = (ids == cols).astype(jnp.float32)  # [TE, TN]
    out_ref[:] += jax.lax.dot_general(
        onehot,
        data_ref[:].astype(jnp.float32),
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=_PRECISION,
        preferred_element_type=jnp.float32,
    )


def _segment_sum_kernel(ids_ref, data_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    _accumulate_onehot(ids_ref, data_ref, out_ref, pl.program_id(0) * _TN)


def _segment_sum_call(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    E, F = data.shape
    # nerrflint: ok[recompile-hazard] num_segments is a static shape arg;
    if E == 0 or F == 0 or num_segments == 0:  # degenerate: nothing to tile
        return jnp.zeros((num_segments, F), data.dtype)
    ids = _pad_to(segment_ids.astype(jnp.int32).reshape(-1, 1), 0, _TE, -1)
    dat = _pad_to(_pad_to(data, 0, _TE, 0), 1, _TF, 0)
    n_pad = num_segments + ((-num_segments) % _TN)
    Ep, Fp = dat.shape

    grid = (n_pad // _TN, Fp // _TF, Ep // _TE)
    out = pl.pallas_call(
        _segment_sum_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TE, 1), lambda i, j, k: (k, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_TE, _TF), lambda i, j, k: (k, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (_TN, _TF), lambda i, j, k: (i, j), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, Fp), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * Ep * n_pad * Fp,
            bytes_accessed=4 * (Ep * Fp + n_pad * Fp) + 4 * Ep,
            transcendentals=0,
        ),
        interpret=interpret,
    )(ids, dat)
    return out[:num_segments, :F].astype(data.dtype)


# --- sorted (banded) segment sum ---------------------------------------------
#
# The dense kernel above contracts every (segment-tile × edge-tile) pair —
# O(N·E·F) MXU work, fine at toy capacity but quadratic at the ~25k-event
# density.  The graph builder emits edges sorted by destination with padding
# slots pointing at the last node
# (builder.py:458-478), so ``edge_dst`` is globally nondecreasing — and then
# each segment tile only receives contributions from a contiguous *band* of
# edge tiles.  This variant prefetches the per-segment-tile band pointers as
# scalars, skips the dot for grid cells outside the band, and freezes the
# input block index once past the band so Mosaic elides the repeated copies:
# MXU work and HBM traffic become O((E + N)·F) for bounded in-degree skew.


def _segment_sum_sorted_kernel(t0_ref, t1_ref, ids_ref, data_ref, out_ref):
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(t0_ref[i] + k < t1_ref[i])
    def _():
        _accumulate_onehot(ids_ref, data_ref, out_ref, i * _TN)


def _segment_sum_sorted_call(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Banded segment sum; ``segment_ids`` must be nondecreasing."""
    E, F = data.shape
    # nerrflint: ok[recompile-hazard] num_segments is a static shape arg;
    if E == 0 or F == 0 or num_segments == 0:  # degenerate: nothing to tile
        return jnp.zeros((num_segments, F), data.dtype)
    n_pad = num_segments + ((-num_segments) % _TN)
    # pad ids with n_pad: ≥ every valid id (keeps the vector sorted) and
    # beyond the last column tile (matches no output row)
    ids = _pad_to(segment_ids.astype(jnp.int32).reshape(-1, 1), 0, _TE, n_pad)
    dat = _pad_to(_pad_to(data, 0, _TE, 0), 1, _TF, 0)
    Ep, Fp = dat.shape
    n_tiles, f_tiles, e_tiles = n_pad // _TN, Fp // _TF, Ep // _TE

    t0, t1 = _band_ptrs(ids, n_pad)

    def _edge_tile(i, k, t0r, t1r):
        # freeze on the band's last tile once k passes it → consecutive
        # identical block indices, whose copies Mosaic elides; the final
        # clamp keeps even empty-band-past-the-end tiles (t0 == t1 ==
        # e_tiles) inside the valid block range
        return jnp.minimum(
            jnp.minimum(t0r[i] + k, jnp.maximum(t1r[i] - 1, t0r[i])),
            e_tiles - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, f_tiles, e_tiles),
        in_specs=[
            pl.BlockSpec((_TE, 1),
                         lambda i, j, k, t0r, t1r: (_edge_tile(i, k, t0r, t1r), 0)),
            pl.BlockSpec((_TE, _TF),
                         lambda i, j, k, t0r, t1r: (_edge_tile(i, k, t0r, t1r), j)),
        ],
        out_specs=pl.BlockSpec((_TN, _TF), lambda i, j, k, t0r, t1r: (i, j)),
    )
    out = pl.pallas_call(
        _segment_sum_sorted_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, Fp), jnp.float32),
        # typical-case banded cost (band ≈ 2 edge tiles per segment tile);
        # the dense kernels' estimates are the quadratic upper bound
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * _TE * n_pad * Fp,
            bytes_accessed=4 * (2 * n_pad * _TE // _TN * Fp + n_pad * Fp)
            + 4 * Ep,
            transcendentals=0,
        ),
        interpret=interpret,
    )(t0, t1, ids, dat)
    return out[:num_segments, :F].astype(data.dtype)


# --- sorted row gather (the banded sum's adjoint) ----------------------------
#
# grad_data[e] = g[ids[e]] with *nondecreasing* ids: edge tile k only reads
# rows from the contiguous band of segment tiles spanned by
# ids[k·TE .. (k+1)·TE).  A tile holds 128 edges, so the band covers at most
# 128 segment tiles and for dense-ish sorted ids (the builder's layout)
# typically one or two; the grid's band dimension spans the worst case and
# runtime-skips past each tile's actual band, with the block index frozen so
# the repeated copies are elided.  The backward of the banded segment sum
# therefore stays linear as well (the dense gather would hand the quadratic
# cost right back in training, where ~2/3 of the FLOPs live).


def _gather_sorted_kernel(s0_ref, s1_ref, nt_ref, idx_ref, table_ref, out_ref):
    k = pl.program_id(0)
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when((s0_ref[k] + b < s1_ref[k]) & (s0_ref[k] + b < nt_ref[0]))
    def _():
        _gather_onehot(idx_ref, table_ref, out_ref, (s0_ref[k] + b) * _TN)


def _gather_sorted_call(
    table: jnp.ndarray, idx: jnp.ndarray, *, interpret: bool = False
) -> jnp.ndarray:
    """Row gather ``table[idx]`` for nondecreasing ``idx``."""
    N, F = table.shape
    E = idx.shape[0]
    if E == 0 or F == 0 or N == 0:  # degenerate: nothing to tile
        return jnp.zeros((E, F), table.dtype)
    n_pad = N + ((-N) % _TN)
    # pad ids with n_pad: keeps the vector sorted, matches no table row
    ids = _pad_to(idx.astype(jnp.int32).reshape(-1, 1), 0, _TE, n_pad)
    tab = _pad_to(_pad_to(table, 0, _TN, 0), 1, _TF, 0)
    Ep = ids.shape[0]
    Np, Fp = tab.shape
    e_tiles, f_tiles, n_tiles = Ep // _TE, Fp // _TF, Np // _TN

    # per-edge-tile band of segment tiles: [s0, s1); width is typically 1-2
    # for dense-ish sorted ids but can reach min(TE, n_tiles) when sparse,
    # so the grid spans the worst case and runtime-skips the rest
    first = ids[::_TE, 0]
    last = ids[_TE - 1::_TE, 0]
    s0 = (first // _TN).astype(jnp.int32)
    s1 = (last // _TN + 1).astype(jnp.int32)
    nt = jnp.full((1,), n_tiles, jnp.int32)

    def _seg_tile(k, b, s0r, s1r, ntr):
        # freeze on the band's last tile once b passes it (identical block
        # indices → elided copies); the final clamp keeps all-pad edge
        # tiles (whose band starts at n_tiles) inside the valid range
        return jnp.minimum(
            jnp.minimum(s0r[k] + b, jnp.maximum(s1r[k] - 1, s0r[k])),
            ntr[0] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(e_tiles, f_tiles, n_tiles),
        in_specs=[
            pl.BlockSpec((_TE, 1), lambda k, j, b, s0r, s1r, ntr: (k, 0)),
            pl.BlockSpec((_TN, _TF),
                         lambda k, j, b, s0r, s1r, ntr:
                         (_seg_tile(k, b, s0r, s1r, ntr), j)),
        ],
        out_specs=pl.BlockSpec((_TE, _TF),
                               lambda k, j, b, s0r, s1r, ntr: (k, j)),
    )
    out = pl.pallas_call(
        _gather_sorted_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Ep, Fp), jnp.float32),
        # typical-case banded cost (band ≈ 2 segment tiles per edge tile)
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * _TN * Ep * Fp,
            bytes_accessed=4 * (2 * Ep * _TN // _TE * Fp + Ep * Fp) + 4 * Ep,
            transcendentals=0,
        ),
        interpret=interpret,
    )(s0, s1, nt, ids, tab)
    return out[:E, :F].astype(table.dtype)


# --- row gather --------------------------------------------------------------


def _gather_onehot(idx_ref, table_ref, out_ref, row_base):
    """out += onehot(idx, row_base..row_base+TN) @ table — the shared MXU
    body of both gather kernels."""
    idx = idx_ref[:]  # [TE, 1] int32
    cols = jax.lax.broadcasted_iota(jnp.int32, (_TE, _TN), 1) + row_base
    onehot = (idx == cols).astype(jnp.float32)  # [TE, TN]
    out_ref[:] += jnp.dot(
        onehot, table_ref[:].astype(jnp.float32), precision=_PRECISION,
        preferred_element_type=jnp.float32,
    )


def _gather_kernel(idx_ref, table_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    _gather_onehot(idx_ref, table_ref, out_ref, pl.program_id(2) * _TN)


def _gather_call(
    table: jnp.ndarray, idx: jnp.ndarray, *, interpret: bool = False
) -> jnp.ndarray:
    N, F = table.shape
    E = idx.shape[0]
    if E == 0 or F == 0 or N == 0:  # degenerate: nothing to tile
        return jnp.zeros((E, F), table.dtype)
    ids = _pad_to(idx.astype(jnp.int32).reshape(-1, 1), 0, _TE, -1)
    tab = _pad_to(_pad_to(table, 0, _TN, 0), 1, _TF, 0)
    Ep = ids.shape[0]
    Np, Fp = tab.shape

    grid = (Ep // _TE, Fp // _TF, Np // _TN)
    out = pl.pallas_call(
        _gather_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TE, 1), lambda i, j, k: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_TN, _TF), lambda i, j, k: (k, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (_TE, _TF), lambda i, j, k: (i, j), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((Ep, Fp), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * Ep * Np * Fp,
            bytes_accessed=4 * (Np * Fp + Ep * Fp) + 4 * Ep,
            transcendentals=0,
        ),
        interpret=interpret,
    )(ids, tab)
    return out[:E, :F].astype(table.dtype)


# --- fused bidirectional SAGE aggregation ------------------------------------
#
# The segment path above serves the GNN as ~6 small kernels per layer (two
# row gathers + two segment-mean numerator/denominator pairs), each paying
# a fixed launch cost — at 28 layers that is ~168 sequential launches per
# window, and gather/scatter launch overhead
# is exactly what dominates TPU GNN runtimes in the accelerator benchmarking
# literature (arXiv:2210.12247).  The dense_adj alternative is one matmul
# per layer against an [N, N] adjacency, O(N²·H) MXU work for graphs with
# E ≪ N².
#
# Where it stands on the chip (benchmarks/results/kernel_bench_v5e.json, a
# v5e: 28 layers forward + backward, vmap batch 8, H = 160, e = 2n): this
# kernel is 1.8-6.7x faster than the segment path and 10-30x SLOWER than
# dense_adj at every bucket from 1024 to 8192 nodes (2.5 / 5.8 / 14.4 / 41.7
# ms a layer against 0.04 / 0.13 / 0.70 / 2.7), so `auto` takes the matmul
# wherever its adjacency fits (models/graphsage.py DENSE_ADJ_MAX_NODES).
# The grid below is (f_tiles, n_tiles, e_tiles): with e = 2n its step count
# grows as N² and the band test lets about 3 of every 64 steps at 4096 do
# work; the measured time grows 2.3-2.9x a doubling of N, between the O(E)
# useful work and the O(N²) grid.  At 16384 nodes Mosaic refuses the kernel
# (16.44 MB of scoped VMEM against 16), where dense_adj at batch 8 no longer
# fits HBM either.
#
# This kernel is the third shape: ONE `pallas_call` per layer, O(E·H) useful
# work.
# Both directions of the bidirectional weighted-mean aggregate
#
#     out[n] = Σ_{e: dst(e)=n} ŵf(e)·msg[src(e)] + Σ_{e: src(e)=n} ŵr(e)·msg[dst(e)]
#
# are computed blocked-CSR style over the builder's dst-sorted edge list and
# the model's precomputed src-sorted view: per output tile of 128 nodes, the
# contributing edges live in a contiguous *band* of edge tiles (scalar-
# prefetched band pointers, exactly like the banded segment sum above).  For
# each in-band edge tile the kernel gathers the 128 source rows of `msg`
# into a VMEM scratch with dynamic row loads, then scatter-accumulates them
# onto the output tile as one weighted one-hot MXU contraction.  Gather +
# weight + accumulate all happen in VMEM; the weights arrive pre-normalized
# (ŵ = w / max(Σw, ε), computed once per forward, NOT per layer), so no
# normalization pass is needed and empty segments stay exactly zero.
#
# The adjoint of out = (Wf + Wr)@msg is (Wfᵀ + Wrᵀ)@g — the SAME operation
# with the two directions' weights exchanged across the two sorted views
# (Wfᵀ scatters to src, i.e. rides the src-sorted band with the fwd weights;
# Wrᵀ symmetrically) — so the backward pass is one more call to this kernel
# and training stays at one kernel per layer per pass.


def _sage_band_tile(i, k, t0, t1, e_tiles):
    """Edge tile for band step ``k`` of output tile ``i``: freeze on the
    band's last tile once past it (identical consecutive block indices →
    Mosaic elides the copies) and clamp into the valid block range."""
    return jnp.minimum(
        jnp.minimum(t0[i] + k, jnp.maximum(t1[i] - 1, t0[i])), e_tiles - 1)


def _sage_kernel(t0f_ref, t1f_ref, t0r_ref, t1r_ref, srcg_ref, dstg_ref,
                 dstid_ref, wf_ref, srcid_ref, wr_ref, msg_ref,
                 out_ref, scratch_ref):
    i = pl.program_id(1)  # output (node) tile
    k = pl.program_id(2)  # band step

    @pl.when(k == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    def _accumulate_direction(t0_ref, t1_ref, gidx_ref, ids_ref, w_ref):
        tile = t0_ref[i] + k

        @pl.when(tile < t1_ref[i])
        def _():
            # gather the tile's 128 source rows into VMEM scratch (indices
            # stream from SMEM scalar prefetch; padded edges index row 0
            # and carry weight 0, so they contribute nothing)
            def body(e, carry):
                r = gidx_ref[tile * _TE + e]
                scratch_ref[pl.ds(e, 1), :] = msg_ref[pl.ds(r, 1), :]
                return carry

            jax.lax.fori_loop(0, _TE, body, 0)
            # weighted one-hot scatter-accumulate on the MXU: fold the
            # pre-normalized edge weight into the one-hot block
            ids = ids_ref[:]  # [TE, 1] int32
            cols = jax.lax.broadcasted_iota(jnp.int32, (_TE, _TN), 1) + i * _TN
            ow = (ids == cols).astype(jnp.float32) * w_ref[:]
            out_ref[:] += jax.lax.dot_general(
                ow, scratch_ref[:],
                dimension_numbers=(((0,), (0,)), ((), ())),
                precision=_PRECISION,
                preferred_element_type=jnp.float32,
            )

    _accumulate_direction(t0f_ref, t1f_ref, srcg_ref, dstid_ref, wf_ref)
    _accumulate_direction(t0r_ref, t1r_ref, dstg_ref, srcid_ref, wr_ref)


def _sage_call(msg, dst_ids, src_by_dst, w_dst, src_ids, dst_by_src, w_src,
               num_nodes, *, interpret=False):
    """One fused pass: ``Σ w_dst·msg[src_by_dst] → dst_ids`` plus
    ``Σ w_src·msg[dst_by_src] → src_ids``.  ``dst_ids`` and ``src_ids`` must
    be nondecreasing; ``msg`` must have ``num_nodes`` rows."""
    N, F = msg.shape
    E = dst_ids.shape[0]
    # nerrflint: ok[recompile-hazard] num_nodes is a static shape arg;
    if E == 0 or F == 0 or num_nodes == 0:  # degenerate: nothing to tile
        return jnp.zeros((num_nodes, F), msg.dtype)
    n_pad = num_nodes + ((-num_nodes) % _TN)
    # segment ids pad with n_pad: keeps both vectors sorted and matches no
    # output row; gather indices pad with 0 (a valid row) under weight 0
    dstid = _pad_to(dst_ids.astype(jnp.int32).reshape(-1, 1), 0, _TE, n_pad)
    srcid = _pad_to(src_ids.astype(jnp.int32).reshape(-1, 1), 0, _TE, n_pad)
    srcg = _pad_to(src_by_dst.astype(jnp.int32), 0, _TE, 0)
    dstg = _pad_to(dst_by_src.astype(jnp.int32), 0, _TE, 0)
    wf = _pad_to(w_dst.astype(jnp.float32).reshape(-1, 1), 0, _TE, 0.0)
    wr = _pad_to(w_src.astype(jnp.float32).reshape(-1, 1), 0, _TE, 0.0)
    # f32 msg block: single dynamic rows of bf16 would fight the (16, 128)
    # tiling; the one-per-layer [N, F] upcast is noise next to the matmuls
    dat = _pad_to(_pad_to(msg.astype(jnp.float32), 0, _TN, 0), 1, _TF, 0)
    Ep = dstid.shape[0]
    Np, Fp = dat.shape
    f_tiles, n_tiles, e_tiles = Fp // _TF, n_pad // _TN, Ep // _TE

    t0f, t1f = _band_ptrs(dstid, n_pad)
    t0r, t1r = _band_ptrs(srcid, n_pad)

    def _fwd_tile(j, i, k, t0f, t1f, t0r, t1r, sg, dg):
        return (_sage_band_tile(i, k, t0f, t1f, e_tiles), 0)

    def _rev_tile(j, i, k, t0f, t1f, t0r, t1r, sg, dg):
        return (_sage_band_tile(i, k, t0r, t1r, e_tiles), 0)

    # grid order (feature, node, band): the full-height msg block's index
    # depends only on the OUTERMOST dim, so it is copied in once per
    # feature tile and stays VMEM-resident across every node tile and band
    # step; the output tile stays resident across its band.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(f_tiles, n_tiles, e_tiles),
        in_specs=[
            pl.BlockSpec((_TE, 1), _fwd_tile),                    # dst ids
            pl.BlockSpec((_TE, 1), _fwd_tile),                    # ŵ fwd
            pl.BlockSpec((_TE, 1), _rev_tile),                    # src ids
            pl.BlockSpec((_TE, 1), _rev_tile),                    # ŵ rev
            pl.BlockSpec((Np, _TF),
                         lambda j, i, k, *refs: (0, j)),          # msg
        ],
        out_specs=pl.BlockSpec((_TN, _TF), lambda j, i, k, *refs: (i, j)),
        scratch_shapes=[pltpu.VMEM((_TE, _TF), jnp.float32)],
    )
    out = pl.pallas_call(
        _sage_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, Fp), jnp.float32),
        # typical-case banded cost, two directions (band ≈ 2 edge tiles per
        # node tile per direction)
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * 2 * _TE * n_pad * Fp,
            bytes_accessed=4 * (Np * Fp + n_pad * Fp + 4 * Ep) + 8 * Ep,
            transcendentals=0,
        ),
        interpret=interpret,
    )(t0f, t1f, t0r, t1r, srcg, dstg, dstid, wf, srcid, wr, dat)
    return out[:num_nodes, :F].astype(msg.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def sage_aggregate_fused(msg, dst_ids, src_by_dst, src_ids, dst_by_src,
                         wf_d, wf_s, wr_s, wr_d, num_nodes, interpret=False):
    """Fused bidirectional SAGE aggregation, one kernel per call.

    ``(dst_ids, src_by_dst, wf_d)`` is the builder's dst-sorted edge list
    with pre-normalized forward weights; ``(src_ids, dst_by_src, wr_s)`` the
    src-sorted view with pre-normalized reverse weights.  ``wf_s``/``wr_d``
    are the same two weight vectors carried in the *other* view's order —
    unused forward, they are exactly what the adjoint needs (transposing a
    direction swaps which sorted band it rides), keeping backward at one
    kernel too.  Differentiable in ``msg`` only; ids and weights are graph
    structure."""
    return _sage_call(msg, dst_ids, src_by_dst, wf_d, src_ids, dst_by_src,
                      wr_s, num_nodes, interpret=interpret)


def _sage_fwd(msg, dst_ids, src_by_dst, src_ids, dst_by_src,
              wf_d, wf_s, wr_s, wr_d, num_nodes, interpret):
    out = _sage_call(msg, dst_ids, src_by_dst, wf_d, src_ids, dst_by_src,
                     wr_s, num_nodes, interpret=interpret)
    return out, (dst_ids, src_by_dst, src_ids, dst_by_src, wf_s, wr_d)


def _sage_bwd(num_nodes, interpret, res, g):
    dst_ids, src_by_dst, src_ids, dst_by_src, wf_s, wr_d = res
    # (Wf + Wr)ᵀ @ g: Wfᵀ scatters to src — the src-sorted band with the
    # forward weights; Wrᵀ scatters to dst — the dst-sorted band with the
    # reverse weights.  Same kernel, weights exchanged across the views.
    gmsg = _sage_call(g, dst_ids, src_by_dst, wr_d, src_ids, dst_by_src,
                      wf_s, num_nodes, interpret=interpret)
    return (gmsg, None, None, None, None, None, None, None, None)


sage_aggregate_fused.defvjp(_sage_fwd, _sage_bwd)


# --- custom VJPs (adjoint of sum is gather, and vice versa) ------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def segment_sum(data, segment_ids, num_segments, interpret=False):
    """MXU one-hot segment-sum: rows of ``data`` [E, F] → buckets [N, F]."""
    return _segment_sum_call(data, segment_ids, num_segments, interpret=interpret)


def _segment_sum_fwd(data, segment_ids, num_segments, interpret):
    return _segment_sum_call(data, segment_ids, num_segments, interpret=interpret), (
        segment_ids,
    )


def _segment_sum_bwd(num_segments, interpret, res, g):
    (segment_ids,) = res
    return _gather_call(g, segment_ids, interpret=interpret), None


segment_sum.defvjp(_segment_sum_fwd, _segment_sum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def segment_sum_sorted(data, segment_ids, num_segments, interpret=False):
    """Banded MXU segment-sum for nondecreasing ``segment_ids`` (the graph
    builder's sorted-by-dst edge layout).  Same contract as
    :func:`segment_sum`, linear instead of quadratic MXU work."""
    return _segment_sum_sorted_call(
        data, segment_ids, num_segments, interpret=interpret)


def _segment_sum_sorted_fwd(data, segment_ids, num_segments, interpret):
    return _segment_sum_sorted_call(
        data, segment_ids, num_segments, interpret=interpret), (segment_ids,)


def _segment_sum_sorted_bwd(num_segments, interpret, res, g):
    (segment_ids,) = res
    # adjoint is a gather by the same nondecreasing ids — banded too
    return _gather_sorted_call(g, segment_ids, interpret=interpret), None


segment_sum_sorted.defvjp(_segment_sum_sorted_fwd, _segment_sum_sorted_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gather_rows(table, idx, interpret=False):
    """MXU one-hot row gather: ``table[idx]`` without an XLA scatter/gather."""
    return _gather_call(table, idx, interpret=interpret)


def _gather_fwd(table, idx, interpret):
    return _gather_call(table, idx, interpret=interpret), (idx, table.shape[0])


def _gather_bwd(interpret, res, g):
    idx, num_rows = res
    return _segment_sum_call(g, idx, num_rows, interpret=interpret), None


gather_rows.defvjp(_gather_fwd, _gather_bwd)


# --- static resource inventory (the deep-lint surface) -----------------------


def kernel_vmem_blocks(num_nodes: int, num_edges: int,
                       num_features: int) -> dict:
    """Per-kernel VMEM block inventory at the given (padded-up) problem
    shape: ``{kernel: [(block, shape, dtype, copies), ...]}``.

    THE static description of what each kernel keeps resident in VMEM per
    grid cell, mirroring the BlockSpecs/scratch_shapes above — kept next
    to the kernels so a tiling change and its budget model move in one
    diff.  ``copies=2`` marks grid-streamed blocks (Mosaic double-buffers
    the HBM→VMEM copies); scratch and accumulator blocks are single.  The
    deep static pass (`nerrf lint --deep`, pallas-budget) costs this
    against the per-core VMEM budget for every serve-ladder bucket, so an
    over-VMEM tile combination fails on CPU in seconds instead of as a
    Mosaic allocation error minutes into a chip run."""
    n_pad = num_nodes + ((-num_nodes) % _TN)
    del num_edges, num_features  # tiled away (_TE rows / _TF lanes per block)
    return {
        "segment_sum": [
            ("ids", (_TE, 1), "int32", 2),
            ("data", (_TE, _TF), "float32", 2),
            ("out", (_TN, _TF), "float32", 1),
        ],
        "segment_sum_sorted": [
            ("ids", (_TE, 1), "int32", 2),
            ("data", (_TE, _TF), "float32", 2),
            ("out", (_TN, _TF), "float32", 1),
        ],
        "gather_rows": [
            ("ids", (_TE, 1), "int32", 2),
            ("table", (_TN, _TF), "float32", 2),
            ("out", (_TE, _TF), "float32", 1),
        ],
        "gather_rows_sorted": [
            ("ids", (_TE, 1), "int32", 2),
            ("table", (_TN, _TF), "float32", 2),
            ("out", (_TE, _TF), "float32", 1),
        ],
        # the fused kernel keeps the FULL-HEIGHT message block resident
        # across every node tile and band step (grid order f, n, e) — the
        # one block here whose footprint grows with the bucket, and the
        # reason the budget check exists
        "sage_fused": [
            ("band_ptrs", (4, max(n_pad // _TN, 1)), "int32", 1),
            ("ids+weights", (4 * _TE, 1), "int32", 2),
            ("msg", (n_pad, _TF), "float32", 2),
            ("out", (_TN, _TF), "float32", 1),
            ("scratch", (_TE, _TF), "float32", 1),
        ],
    }


def tile_constants() -> dict:
    """The kernel tile sizes, exported for the deep pass's divisibility
    check (lane dim 128, f32 sublane 8 — docs/kernel-paths.md)."""
    return {"TN": _TN, "TE": _TE, "TF": _TF}


# --- registration ------------------------------------------------------------


def register(interpret: bool = False) -> None:
    """Install the Pallas segment sums (dense, and banded with its banded
    gather adjoint) and the fused SAGE aggregate behind ``nerrf_tpu.ops``'
    switchboard, unconditionally: a kernel Mosaic refuses raises at the
    compile that needs it instead of being traded for an XLA op nobody
    asked for.  `chip_smoke.py` compiles and checks all five kernels of this
    module on the chip, the blocked gather (the dense sum's adjoint)
    among them."""
    from nerrf_tpu.ops import segment as _seg

    _seg.use_pallas(
        lambda data, ids, n: segment_sum(data, ids, n, interpret),
        sorted_sum_fn=lambda data, ids, n: segment_sum_sorted(
            data, ids, n, interpret),
        sage_fn=lambda msg, *edges_and_n: sage_aggregate_fused(
            msg, *edges_and_n, interpret),
    )


def unregister() -> None:
    from nerrf_tpu.ops import segment as _seg

    _seg.use_pallas(None)
