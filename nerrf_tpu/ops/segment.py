"""Sparse neighbor-aggregation primitives.

The graph builder emits edges sorted by destination node, so aggregation is a
segment reduction over a monotone id vector.  Every op here is code the
compiler writes, on every backend: `jax.ops.segment_sum` (XLA's scatter-add),
`jnp.take`, and on a TPU one selection matmul each way for the row gather
(:func:`gather_rows`).  What can differ between two programs is a pure
function of the backend and a static shape (:func:`gather_rows_route`);
nothing is installed, registered or scoped at run time, and this module holds
no mutable state.  ``sorted_ids=True`` is XLA's ``indices_are_sorted`` hint:
ids that are not nondecreasing under it are undefined behaviour of the
scatter, so declare it only where the layout guarantees it (the builder's
sorted-by-dst edges).

docs/kernel-paths.md has the chip's sweeps behind the two constants that
route (`SELECTION_MATMUL_MAX_ROWS` here, `models.graphsage.
DENSE_ADJ_MAX_NODES`) and says where the hand-written kernels that lost them
can be found.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp


def active_impls() -> dict:
    """Which route serves each op that has more than one, in a program
    traced on this backend.  Benchmark artifacts and the training log
    record it (`kernel_path`) and the AOT cache key carries it, so a chip
    number is attributed to the ops that ran and an executable is never
    served to a backend that would have traced other ops."""
    # the route of every shipped bucket; `gather_rows_route(n)` is the
    # rule for a table of n rows
    return {"gather_rows": gather_rows_route()}


def segment_mean(
    data: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    weights: Optional[jnp.ndarray] = None,
    *,
    sorted_ids: bool = False,
) -> jnp.ndarray:
    """(Weighted) mean of the rows of ``data`` [E, F] in each of
    ``num_segments`` buckets [N, F]; an empty segment is exactly zero.

    ``sorted_ids`` is passed to `jax.ops.segment_sum` as
    ``indices_are_sorted``: a hint the ids must honour, not a route."""

    def total(x):
        return jax.ops.segment_sum(x, segment_ids, num_segments=num_segments,
                                   indices_are_sorted=sorted_ids)

    if weights is not None:
        w = weights[:, None] if weights.ndim == 1 else weights
        return total(data * w) / jnp.maximum(total(w), 1e-6)
    ones = jnp.ones((data.shape[0], 1), data.dtype)
    return total(data) / jnp.maximum(total(ones), 1e-6)


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
    """Bidirectional SAGE aggregation over pre-sorted edge views.

    Computes, for every node ``n`` of ``num_nodes``::

        out[n] = Σ_{e: dst(e)=n} wf(e) · msg[src(e)]
               + Σ_{e: src(e)=n} wr(e) · msg[dst(e)]

    Arguments carry the graph in BOTH sorted orders: ``(dst_ids,
    src_by_dst)`` is the builder's dst-sorted edge list, ``(src_ids,
    dst_by_src)`` the per-window src-sorted view, with the forward weights
    in the first order (``wf_d``) and the reverse weights in the second
    (``wr_s``).  ``dst_ids`` and ``src_ids`` must be nondecreasing (they are
    given to the scatter as ``indices_are_sorted``), and weights are expected
    pre-normalized (``w / max(Σw, ε)`` per segment), which makes the op a
    pure weighted scatter: empty segments are exactly zero and no
    normalization pass runs per layer.  ``wf_s`` / ``wr_d`` (each weight
    vector in the other order) are what `models.graphsage.fused_edge_views`
    also returns; they are not read.

    A gather, a product and a float32 `jax.ops.segment_sum` each way,
    differentiable in ``msg``: what `aggregation="fused"` runs per layer."""
    del wf_s, wr_d
    # the scope `dense_adj`'s matmul carries too: the same work under the
    # same name in a device trace, whichever route served it
    with jax.named_scope("sage_aggregate"):
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
# gather wins by 1.7x at 16384.  (The `Pallas` row is the hand-written
# one-hot kernel that served this op until PR 30 and left the tree in PR 31.)
SELECTION_MATMUL_MAX_ROWS = 8192


def gather_rows_route(num_rows: Optional[int] = None) -> str:
    """The route :func:`gather_rows` takes for a floating ``[num_rows, F]``
    table in a program traced here and now: ``xla_selection_matmul`` on a
    TPU up to `SELECTION_MATMUL_MAX_ROWS` rows (no ``num_rows``: the answer
    for every bucket the repo ships), ``xla`` (the compiler's gather)
    otherwise: past that size and off a TPU.  A function of the backend and
    a static shape, like `GraphSAGEConfig.resolved_aggregation`; nothing
    sets it, and a program over a mesh takes the route of a one-chip
    program (the matmul is a `dot_general`, which GSPMD partitions)."""
    if (jax.default_backend() == "tpu"
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
