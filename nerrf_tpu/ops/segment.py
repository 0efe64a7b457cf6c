"""Sparse neighbor-aggregation primitives.

The graph builder emits edges sorted by destination node, so aggregation is a
segment reduction over a monotone id vector — the memory-friendly layout for
TPU.  This module is the single switchboard for those primitives.  Off TPU
they are XLA's scatter-add and gather (`jax.ops.segment_sum`, `jnp.take`);
`nerrf_tpu.ops.pallas_segment` provides hand-tiled Pallas kernels for the hot
TPU path and registers itself here.  ``sorted_ids=True`` is a **contract**
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
_GATHER_IMPL: Optional[Callable] = None
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


def use_pallas(sum_fn: Optional[Callable], gather_fn: Optional[Callable] = None,
               sorted_sum_fn: Optional[Callable] = None,
               sage_fn: Optional[Callable] = None) -> None:
    """Install (or clear) pallas segment-sum / row-gather implementations.

    ``sorted_sum_fn`` (if given) serves calls that declare nondecreasing ids
    (the builder's sorted-by-dst layout) — the banded kernel with linear MXU
    work; ``sum_fn`` stays the order-independent fallback.  ``sage_fn`` (if
    given) serves :func:`sage_aggregate` — the fused one-kernel-per-layer
    bidirectional aggregation.

    An explicit call — including clearing — is a deliberate choice, so it also
    disables the one-shot TPU registration in :func:`_maybe_auto_register`.
    """
    global _SEGMENT_SUM_IMPL, _SEGMENT_SUM_SORTED_IMPL, _GATHER_IMPL, \
        _SAGE_FUSED_IMPL, _AUTO_TRIED
    _SEGMENT_SUM_IMPL = sum_fn
    _SEGMENT_SUM_SORTED_IMPL = sorted_sum_fn
    _GATHER_IMPL = gather_fn
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
        "gather_rows": "pallas_blocked" if _impl(_GATHER_IMPL) else "xla",
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


def gather_rows(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Row gather ``table[idx]`` — kept as a named op so the Pallas blocked
    gather can swap in on TPU without touching call sites."""
    _maybe_auto_register()
    gather = _impl(_GATHER_IMPL)
    if (
        gather is not None
        and table.ndim == 2
        and idx.ndim == 1
        and jnp.issubdtype(table.dtype, jnp.floating)
    ):
        return gather(table, idx)
    return jnp.take(table, idx, axis=0)
