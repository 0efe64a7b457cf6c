"""Sparse neighbor-aggregation primitives.

The graph builder emits edges sorted by destination node, so aggregation is a
segment reduction over a monotone id vector — the memory-friendly layout for
TPU.  This module is the single switchboard for those primitives.  Off TPU
they are XLA's scatter-add and gather (`jax.ops.segment_sum`, `jnp.take`);
`nerrf_tpu.ops.pallas_segment` provides hand-tiled Pallas kernels for the
segment reductions on a TPU and registers itself here; the row gather is
compiler-written on every backend (:func:`gather_rows`: on a TPU one
selection matmul each way).  ``sorted_ids=True`` is a **contract**
(ids really are nondecreasing — it routes to a banded kernel that drops
out-of-band rows on unsorted input), not a hint; the default is the safe
order-independent path.

(The reference framework has no sparse ops at all — its AI subsystem was never
built; this realizes the north-star requirement that neighbor-sampling and
sparse aggregation be written as Pallas kernels.)
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp

# Optional overrides installed by nerrf_tpu.ops.pallas_segment.register().
_SEGMENT_SUM_IMPL: Optional[Callable] = None
_SEGMENT_SUM_SORTED_IMPL: Optional[Callable] = None
_SAGE_FUSED_IMPL: Optional[Callable] = None
_AUTO_TRIED = False
# per-thread: a sharded trace in one thread must not change what a serve
# program traced concurrently in another is made of
_TRACING = threading.local()


@contextlib.contextmanager
def xla_only():
    """Serve every op from its XLA composition while the enclosed code
    traces.  GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a shard_map"), so
    a program jitted over more than one device traces its ops under this
    context (`parallel.train.mesh_ops`); :func:`active_impls` inside it
    reports ``xla``, which is what the program's `kernel_path` and
    compile-cache key then say."""
    prev = getattr(_TRACING, "xla_only", False)
    _TRACING.xla_only = True
    try:
        yield
    finally:
        _TRACING.xla_only = prev


def _impl(fn: Optional[Callable]) -> Optional[Callable]:
    return None if getattr(_TRACING, "xla_only", False) else fn


def use_pallas(sum_fn: Optional[Callable],
               sorted_sum_fn: Optional[Callable] = None,
               sage_fn: Optional[Callable] = None) -> None:
    """Install (or clear) the Pallas segment-sum / aggregation kernels.

    ``sorted_sum_fn`` (if given) serves calls that declare nondecreasing ids
    (the builder's sorted-by-dst layout) — the banded kernel with linear MXU
    work; ``sum_fn`` stays the order-independent fallback.  ``sage_fn`` (if
    given) serves :func:`sage_aggregate` — the fused one-kernel-per-layer
    bidirectional aggregation.

    An explicit call — including clearing — is a deliberate choice, so it also
    disables the one-shot TPU registration in :func:`_maybe_auto_register`.
    """
    global _SEGMENT_SUM_IMPL, _SEGMENT_SUM_SORTED_IMPL, _SAGE_FUSED_IMPL, \
        _AUTO_TRIED
    _SEGMENT_SUM_IMPL = sum_fn
    _SEGMENT_SUM_SORTED_IMPL = sorted_sum_fn
    _SAGE_FUSED_IMPL = sage_fn
    _AUTO_TRIED = True


def active_impls() -> dict:
    """Which implementation serves each op on this backend (and under
    :func:`xla_only`, if active) — benchmark artifacts and the training log
    record this (`kernel_path`) so a chip number can be attributed to the
    kernel that actually ran."""
    _maybe_auto_register()
    dense, banded = _impl(_SEGMENT_SUM_IMPL), _impl(_SEGMENT_SUM_SORTED_IMPL)
    return {
        "segment_sum": "pallas_dense" if dense else "xla",
        "segment_sum_sorted": (
            "pallas_banded" if banded else "pallas_dense" if dense else "xla"),
        # the route of every shipped bucket; `gather_rows_route(n)` is the
        # rule for a table of n rows
        "gather_rows": gather_rows_route(),
        "sage_aggregate": (
            "pallas_fused" if _impl(_SAGE_FUSED_IMPL) else "xla"),
    }


def _maybe_auto_register() -> None:
    """On the first aggregation call — traced or eager — install the Pallas
    kernels iff the backend is a TPU.  At call time, not import time, so
    importing the library never forces backend initialization.  Nothing is
    probed: a kernel Mosaic refuses raises where it is compiled."""
    global _AUTO_TRIED
    if _AUTO_TRIED:
        return
    _AUTO_TRIED = True
    if jax.default_backend() == "tpu":
        from nerrf_tpu.ops import pallas_segment

        pallas_segment.register()


def segment_sum(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    *,
    sorted_ids: bool = False,
) -> jnp.ndarray:
    """Sum rows of ``data`` [E, F] into ``num_segments`` buckets [N, F].

    ``sorted_ids=True`` is a *contract*, not a hint: it routes to the banded
    Pallas kernel, which silently drops out-of-band rows if ids are not
    actually nondecreasing.  The default is therefore the safe
    order-independent path; declare sortedness only where the layout
    guarantees it (the builder's sorted-by-dst edges)."""
    _maybe_auto_register()
    # The Pallas kernels compute through f32, so integer data keeps the
    # exact XLA path.  Callers declaring sorted ids (the builder's
    # sorted-by-dst edges) get the banded kernel — linear MXU work; the
    # dense one-hot contraction is order-independent and serves the rest.
    if data.ndim == 2 and jnp.issubdtype(data.dtype, jnp.floating):
        banded = _impl(_SEGMENT_SUM_SORTED_IMPL)
        dense = _impl(_SEGMENT_SUM_IMPL)
        if sorted_ids and banded is not None:
            return banded(data, segment_ids, num_segments)
        if dense is not None:
            return dense(data, segment_ids, num_segments)
    return jax.ops.segment_sum(
        data, segment_ids, num_segments=num_segments, indices_are_sorted=sorted_ids
    )


def segment_mean(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    weights: Optional[jnp.ndarray] = None,
    *,
    sorted_ids: bool = False,
) -> jnp.ndarray:
    """(Weighted) mean aggregation; safe for empty segments.

    ``sorted_ids`` follows :func:`segment_sum`'s contract semantics."""
    if weights is not None:
        w = weights[:, None] if weights.ndim == 1 else weights
        total = segment_sum(data * w, segment_ids, num_segments, sorted_ids=sorted_ids)
        denom = segment_sum(w, segment_ids, num_segments, sorted_ids=sorted_ids)
    else:
        total = segment_sum(data, segment_ids, num_segments, sorted_ids=sorted_ids)
        denom = segment_sum(
            jnp.ones((data.shape[0], 1), data.dtype), segment_ids, num_segments,
            sorted_ids=sorted_ids,
        )
    return total / jnp.maximum(denom, 1e-6)


def sage_aggregate(
    msg: jnp.ndarray,
    dst_ids: jnp.ndarray,
    src_by_dst: jnp.ndarray,
    src_ids: jnp.ndarray,
    dst_by_src: jnp.ndarray,
    wf_d: jnp.ndarray,
    wf_s: jnp.ndarray,
    wr_s: jnp.ndarray,
    wr_d: jnp.ndarray,
    num_nodes: int,
) -> jnp.ndarray:
    """Fused bidirectional SAGE aggregation over pre-sorted edge views.

    Computes, for every node ``n`` of ``num_nodes``::

        out[n] = Σ_{e: dst(e)=n} wf(e) · msg[src(e)]
               + Σ_{e: src(e)=n} wr(e) · msg[dst(e)]

    Arguments carry the graph in BOTH sorted orders — ``(dst_ids,
    src_by_dst)`` is the builder's dst-sorted edge list, ``(src_ids,
    dst_by_src)`` the per-window src-sorted view — and each weight vector in
    both orders (``wf_d``/``wf_s`` forward, ``wr_s``/``wr_d`` reverse).
    Sortedness of ``dst_ids`` and ``src_ids`` is a **contract** (the banded
    Pallas kernel drops out-of-band rows on unsorted input), and weights are
    expected pre-normalized (``w / max(Σw, ε)`` per segment), which makes the
    op a pure weighted scatter: empty segments are exactly zero and no
    normalization pass runs per layer.

    On TPU this is served by ONE Pallas kernel per call (``pallas_fused`` in
    :func:`active_impls`), replacing the segment path's ~6 kernels per layer;
    elsewhere an XLA gather + segment-sum composition with identical
    semantics serves as the portable parity oracle.  Both are differentiable
    in ``msg`` (the fused adjoint reuses the same kernel with the weight
    vectors exchanged across the two sorted views — that is why all four are
    taken)."""
    _maybe_auto_register()
    # named scope mirrors the host tracing spine's stage names, so the op's
    # rows in an XLA trace line up with the host spans in Perfetto
    with jax.named_scope("sage_aggregate"):
        fused = _impl(_SAGE_FUSED_IMPL)
        if (
            fused is not None
            and msg.ndim == 2
            and jnp.issubdtype(msg.dtype, jnp.floating)
        ):
            return fused(msg, dst_ids, src_by_dst, src_ids, dst_by_src,
                         wf_d, wf_s, wr_s, wr_d, num_nodes)
        return sage_aggregate_xla(msg, dst_ids, src_by_dst, src_ids,
                                  dst_by_src, wf_d, wf_s, wr_s, wr_d,
                                  num_nodes)


def sage_aggregate_xla(msg, dst_ids, src_by_dst, src_ids, dst_by_src,
                       wf_d, wf_s, wr_s, wr_d, num_nodes):
    """The XLA gather + segment-sum composition behind
    :func:`sage_aggregate` — exposed by name so parity harnesses (tests,
    benchmarks/run_kernel_bench.py) can pin the fused kernel against THE
    fallback that serves production off-TPU, not a reimplementation that
    could drift from it.  ``wf_s``/``wr_d`` are unused here (only the fused
    kernel's adjoint needs the exchanged orders); kept for signature
    parity."""
    del wf_s, wr_d
    m = msg.astype(jnp.float32)
    fwd = jax.ops.segment_sum(
        wf_d[:, None].astype(jnp.float32) * jnp.take(m, src_by_dst, axis=0),
        dst_ids, num_segments=num_nodes, indices_are_sorted=True)
    rev = jax.ops.segment_sum(
        wr_s[:, None].astype(jnp.float32) * jnp.take(m, dst_by_src, axis=0),
        src_ids, num_segments=num_nodes, indices_are_sorted=True)
    return (fwd + rev).astype(msg.dtype)


# The largest table, in rows, that `gather_rows` serves with a selection
# matmul on a TPU.  From the chip's sweep (benchmarks/results/
# kernel_bench_v5e.json `head_gather`, `python benchmarks/run_kernel_bench.py
# --head-gather`: the heads' two gathers of E = 2N rows from [N,160] bf16 and
# their adjoints, batch 8, one v5e; ms forward + backward):
#
#     N        256   1024   2048   4096   8192   16384
#     matmul   0.15  0.26   0.64   2.19   8.12   31.99
#     take     0.31  0.81   1.69   3.98   8.26   18.52
#     Pallas   0.39  3.78   14.3   59.1   237    954
#
# The matmul does O(N E) work and the gather O(E): the matmul wins every
# bucket anyone ships (256 .. 4096) by 1.8-3.1x, the two meet at 8192 and the
# gather wins by 1.7x at 16384.  The Pallas one-hot kernel (float32 at
# `HIGHEST` over an (E/128, F/128, N/128) grid; what served this op until PR
# 30) is 14-29x behind the matmul from 1024 to 8192.
SELECTION_MATMUL_MAX_ROWS = 8192


def gather_rows_route(num_rows: Optional[int] = None) -> str:
    """The route :func:`gather_rows` takes for a floating ``[num_rows, F]``
    table in a program traced here and now: ``xla_selection_matmul`` on a
    TPU up to `SELECTION_MATMUL_MAX_ROWS` rows (no ``num_rows``: the answer
    for every bucket the repo ships), ``xla`` (the compiler's gather)
    otherwise: past that size, off a TPU, and under :func:`xla_only`.  A
    function of the backend and a static shape, like
    `GraphSAGEConfig.resolved_aggregation`; nothing sets it."""
    if (jax.default_backend() == "tpu"
            and not getattr(_TRACING, "xla_only", False)
            and (num_rows is None or num_rows <= SELECTION_MATMUL_MAX_ROWS)):
        return "xla_selection_matmul"
    return "xla"


def _f32_adjoint(fwd: Callable, bwd: Callable) -> Callable:
    """``fwd(table, idx)`` with ``bwd(g, idx, num_rows) -> f32 [num_rows, F]``
    as its adjoint in ``table``.  The adjoint of a row gather sums every
    cotangent row that read the same table row: that sum is taken in
    float32 on every route and cast to the table's dtype afterwards, so a
    bf16 table's hub node (thousands of in-edges) is not accumulated in
    bf16."""

    @jax.custom_vjp
    def gather(table, idx):
        return fwd(table, idx)

    def gather_fwd(table, idx):
        return fwd(table, idx), (idx, table.shape[0])

    def gather_bwd(res, g):
        idx, num_rows = res
        return bwd(g, idx, num_rows).astype(g.dtype), None

    gather.defvjp(gather_fwd, gather_bwd)
    return gather


def _select(idx, num_rows, operand, contract: int):
    """``one_hot(idx, num_rows)`` [E, N] contracted with ``operand`` over its
    axis ``contract`` (1: rows of a table picked, [N, F] -> [E, F]; 0: rows
    of a cotangent summed by index, [E, F] -> [N, F]), accumulated in
    float32.  Every product is by 0.0 or 1.0, so one bf16 pass is exact for
    a bf16 operand; a wider operand takes `HIGHEST`, whose bf16 pieces of a
    float32 add back exactly.  XLA fuses the one-hot into the product's
    operand: it is never held in HBM."""
    sel = jax.nn.one_hot(idx, num_rows, dtype=operand.dtype)
    return jax.lax.dot_general(
        sel, operand, (((contract,), (0,)), ((), ())),
        precision=(None if operand.dtype == jnp.bfloat16
                   else jax.lax.Precision.HIGHEST),
        preferred_element_type=jnp.float32)


_gather_take = _f32_adjoint(
    lambda table, idx: jnp.take(table, idx, axis=0),
    lambda g, idx, num_rows: jax.ops.segment_sum(
        g.astype(jnp.float32), idx, num_segments=num_rows))
_gather_select = _f32_adjoint(
    lambda table, idx: _select(idx, table.shape[0], table, 1
                               ).astype(table.dtype),
    lambda g, idx, num_rows: _select(idx, num_rows, g, 0))


def gather_rows(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Row gather ``table[idx]``, a named op so that what serves it is
    chosen in one place: see :func:`gather_rows_route`.  Both routes are
    code the compiler writes, return the same rows bit for bit, and sum
    the adjoint in float32 (`_f32_adjoint`)."""
    if not (table.ndim == 2 and idx.ndim == 1
            and jnp.issubdtype(table.dtype, jnp.floating)):
        return jnp.take(table, idx, axis=0)
    if gather_rows_route(table.shape[0]) == "xla_selection_matmul":
        return _gather_select(table, idx)
    return _gather_take(table, idx)
