"""`nerrf_tpu.ops.segment` against the host: every op there is code the
compiler writes, so the oracle is a numpy loop, not another XLA program.

`segment_mean` and `sage_aggregate`'s properties are here and in
test_ops_fused.py; `gather_rows` has two routes (the compiler's gather and,
on a TPU, one selection matmul each way), and a CPU test traces the TPU's
by faking `jax.default_backend`.  The chip's own parity check is
chip_smoke.py's `kernels` phase.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nerrf_tpu.ops import segment


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


def _mean_loop(data, ids, n, w=None):
    """Weighted mean of the rows of each segment, one row at a time in
    float64; an empty segment is zero."""
    data = np.asarray(data, np.float64)
    w = np.ones(len(data)) if w is None else np.asarray(w, np.float64)
    tot, den = np.zeros((n, data.shape[1])), np.zeros(n)
    for row, i, wi in zip(data, np.asarray(ids), w):
        tot[i] += wi * row
        den[i] += wi
    return tot / np.maximum(den, 1e-6)[:, None]


@pytest.mark.parametrize("E,N,F", [(37, 11, 5), (128, 128, 128), (300, 50, 33)])
@pytest.mark.parametrize("sorted_ids", [True, False])
def test_segment_mean_matches_a_numpy_loop(E, N, F, sorted_ids):
    """``sorted_ids`` is a hint to the scatter about ids that ARE sorted:
    with it or without, the same means."""
    ids = np.random.default_rng(0).integers(0, N, size=E)
    if sorted_ids:
        ids = np.sort(ids)
    data = _rand((E, F), 1)
    got = segment.segment_mean(data, jnp.asarray(ids, jnp.int32), N,
                               sorted_ids=sorted_ids)
    np.testing.assert_allclose(got, _mean_loop(data, ids, N),
                               rtol=1e-5, atol=1e-5)


def test_segment_mean_empty_segments_are_exactly_zero():
    ids = jnp.asarray([0, 0, 3], jnp.int32)
    data = jnp.asarray([[1.0] * 4, [3.0] * 4, [5.0] * 4], jnp.float32)
    out = np.asarray(segment.segment_mean(data, ids, 6, sorted_ids=True))
    np.testing.assert_allclose(out[0], 2.0)
    np.testing.assert_allclose(out[3], 5.0)
    assert not out[[1, 2, 4, 5]].any()        # 0 / max(0, eps): no residue


def test_segment_mean_with_weights():
    data = _rand((16, 5), 9)
    w = jnp.abs(_rand((16,), 10)) + 0.1
    ids = np.sort(np.random.default_rng(11).integers(0, 6, 16))
    got = segment.segment_mean(data, jnp.asarray(ids, jnp.int32), 6,
                               weights=w, sorted_ids=True)
    np.testing.assert_allclose(got, _mean_loop(data, ids, 6, w),
                               rtol=1e-5, atol=1e-5)
    # a zero weight removes its row from the mean altogether
    w0 = w.at[3].set(0.0)
    got0 = segment.segment_mean(data.at[3].set(1e6),
                                jnp.asarray(ids, jnp.int32), 6, weights=w0)
    np.testing.assert_allclose(got0, _mean_loop(data, ids, 6, w0),
                               rtol=1e-5, atol=1e-5)


def test_zero_row_inputs_return_zeros():
    out = segment.segment_mean(jnp.zeros((0, 4), jnp.float32),
                               jnp.zeros((0,), jnp.int32), 5)
    assert out.shape == (5, 4) and not np.asarray(out).any()
    g = segment.gather_rows(jnp.zeros((3, 4), jnp.float32),
                            jnp.zeros((0,), jnp.int32))
    assert g.shape == (0, 4)


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_active_impls_names_the_one_op_with_two_routes(monkeypatch, backend):
    """What the AOT key and `kernel_path` carry: the backend's route for
    `gather_rows` and nothing else, because nothing else has a choice.
    The module holds no state a call could have changed."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    want = {"gather_rows": "xla_selection_matmul" if backend == "tpu"
            else "xla"}
    assert segment.active_impls() == want
    jax.make_jaxpr(lambda t: segment.gather_rows(
        t, jnp.zeros((4,), jnp.int32)))(jnp.zeros((8, 3), jnp.float32))
    assert segment.active_impls() == want
    mutable = (dict, list, set)
    assert not [k for k, v in vars(segment).items()
                if not k.startswith("__") and isinstance(v, mutable)]


# -- ops.gather_rows: compiler-written on every backend (PR 30) ---------------


def _edge_ids(n, e, seed, hub=0):
    """[e] ids into n rows the way a padded window has them: ``hub``
    repeats of row 7, random repeats, and a padded tail on row n - 1."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, n, e)
    ids[:hub] = 7
    ids[e - e // 3:] = n - 1
    return jnp.asarray(ids, jnp.int32)


@pytest.mark.parametrize("backend, n, want", [
    ("tpu", None, "xla_selection_matmul"), ("tpu", 256, "xla_selection_matmul"),
    ("tpu", 4096, "xla_selection_matmul"),
    ("tpu", segment.SELECTION_MATMUL_MAX_ROWS, "xla_selection_matmul"),
    ("tpu", 2 * segment.SELECTION_MATMUL_MAX_ROWS, "xla"),
    ("cpu", None, "xla"), ("cpu", 4096, "xla")])
def test_gather_route_reads_the_backend_and_the_table_rows(
        monkeypatch, backend, n, want):
    """The rule of `gather_rows_route`: a function of the backend and a
    static shape.  `active_impls()` names the route of the shipped
    buckets."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert segment.gather_rows_route(n) == want
    if n is None:
        assert segment.active_impls()["gather_rows"] == want


def _traced_route(monkeypatch, backend, table, idx):
    """`ops.gather_rows` and its gradient as a program traced on
    ``backend`` would hold them (a fresh function: jax caches traces by
    identity), with the jaxpr of the two."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)

    def both(t, c):
        rows, pull = jax.vjp(lambda t0: segment.gather_rows(t0, idx), t)
        return rows, pull(c)[0]

    cot = jnp.asarray(np.random.default_rng(61).normal(
        size=(idx.shape[0], table.shape[1])), table.dtype)
    text = str(jax.make_jaxpr(both)(table, cot))
    rows, grad = jax.jit(both)(table, cot)
    return rows, grad, cot, text


@pytest.mark.parametrize("n, e", [(1024, 2048), (4096, 8192)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_selection_matmul_forward_is_take_bit_for_bit(monkeypatch, n, e,
                                                      dtype):
    """The route a TPU takes at both shipped training buckets returns
    `jnp.take`'s rows exactly, with repeated and padded indices: every
    product is by 0.0 or 1.0 and one term of each sum is not zero."""
    table = jnp.asarray(np.random.default_rng(n).normal(size=(n, 160)),
                        dtype)
    idx = _edge_ids(n, e, seed=e)
    rows, _, _, text = _traced_route(monkeypatch, "tpu", table, idx)
    assert "dot_general" in text and "pallas_call" not in text
    assert "gather" not in text and "scatter" not in text
    want = jnp.take(table, idx, axis=0)
    assert rows.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(rows, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("backend, n", [
    ("tpu", 1024), ("tpu", 2 * segment.SELECTION_MATMUL_MAX_ROWS),
    ("cpu", 1024)])
def test_gather_adjoint_sums_in_float32_on_every_route(monkeypatch, backend,
                                                       n):
    """A bf16 table with a hub node of 512 in-edges: the gradient equals
    a float32 `segment_sum` of the cotangent, cast once, on the selection
    matmul, on the compiler's gather past the crossover and off a TPU.
    Accumulating the hub's 512 rows in bf16 (what XLA derives for a bare
    `jnp.take`) misses it by tens of bf16 steps."""
    e = 2048
    table = jnp.asarray(np.random.default_rng(n).normal(size=(n, 160)),
                        jnp.bfloat16)
    idx = _edge_ids(n, e, seed=5, hub=512)
    _, grad, cot, text = _traced_route(monkeypatch, backend, table, idx)
    assert grad.dtype == jnp.bfloat16
    assert ("dot_general" in text) == (
        backend == "tpu" and n <= segment.SELECTION_MATMUL_MAX_ROWS)
    want32 = jax.ops.segment_sum(cot.astype(jnp.float32), idx,
                                 num_segments=n)
    want = np.asarray(want32.astype(jnp.bfloat16), np.float32)
    got = np.asarray(grad, np.float32)
    # float32 round-off in another order of summation can move a value
    # across one bf16 rounding boundary, never further
    step = np.maximum(np.abs(want), 1e-3) * 2.0 ** -7
    assert np.all(np.abs(got - want) <= step)
    # the case a bf16 accumulation fails, on the same data
    bare = np.asarray(jax.grad(lambda t: jnp.sum(
        jnp.take(t, idx, axis=0).astype(jnp.float32) * cot))(table),
        np.float32)
    assert np.abs(bare[7] - want[7]).max() > 4 * step[7].max()


def test_gather_rows_under_vmap_matches_take_and_its_adjoint(monkeypatch):
    """The model vmaps the heads over the window batch: both routes batch
    and differentiate there, in float32 to float32 round-off."""
    B, n, e, f = 3, 96, 200, 9
    rng = np.random.default_rng(71)
    table = jnp.asarray(rng.normal(size=(B, n, f)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (B, e)), jnp.int32)
    want_fn = jax.vmap(lambda t, i: jnp.take(t, i, axis=0))
    want_g = jax.grad(lambda t: jnp.sum(want_fn(t, idx) ** 2))(table)
    for backend in ("tpu", "cpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        fn = jax.vmap(lambda t, i: segment.gather_rows(t, i))
        np.testing.assert_array_equal(fn(table, idx), want_fn(table, idx))
        got_g = jax.grad(lambda t: jnp.sum(fn(t, idx) ** 2))(table)
        np.testing.assert_allclose(got_g, want_g, rtol=1e-5, atol=1e-5)


def test_a_traced_step_never_imports_pallas(repo_root):
    """In a fresh interpreter, with the backend read as a TPU: importing
    `nerrf_tpu.ops`, building `NerrfNet` and tracing its loss's gradient at
    a `dense_adj` bucket leaves `jax.experimental.pallas` unimported.
    Nothing in the package can install a kernel at first use any more."""
    import os
    import subprocess
    import sys

    code = """
import sys
import jax, jax.numpy as jnp
jax.default_backend = lambda: "tpu"
import nerrf_tpu.ops
from nerrf_tpu.data.sequences import SEQ_FEATURE_DIM
from nerrf_tpu.graph import EDGE_FEATURE_DIM, NODE_FEATURE_DIM
from nerrf_tpu.models import JointConfig, NerrfNet
from nerrf_tpu.ops.segment import active_impls
S = jax.ShapeDtypeStruct
n, e, s, t = 256, 512, 8, 12
args = (S((n, NODE_FEATURE_DIM), jnp.float32), S((n,), jnp.int32),
        S((n,), jnp.int32), S((n,), jnp.bool_), S((e,), jnp.int32),
        S((e,), jnp.int32), S((e, EDGE_FEATURE_DIM), jnp.float32),
        S((e,), jnp.bool_), S((s, t, SEQ_FEATURE_DIM), jnp.float32),
        S((s, t), jnp.bool_), S((s,), jnp.int32))
model = NerrfNet(JointConfig().small)
assert model.cfg.gnn.resolved_aggregation(n) == "dense_adj"
params = jax.eval_shape(
    lambda *a: model.init(jax.random.PRNGKey(0), *a)["params"], *args)
loss = lambda p, *a: sum(v.sum() for k, v in model.apply(
    {"params": p}, *a).items() if k.endswith("_logit"))
jax.make_jaxpr(jax.grad(loss))(params, *args)
assert active_impls() == {"gather_rows": "xla_selection_matmul"}
print(sorted(m for m in sys.modules if "pallas" in m))
"""
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=repo_root, capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "[]"
