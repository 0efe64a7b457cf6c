"""Pallas sparse-aggregation kernels vs the XLA reference path.

Runs in interpreter mode on the CPU mesh (tests/conftest.py); the compiled
path is exercised on a real TPU by chip_smoke.py and by the chip-gated tests
at the end (NERRF_TEST_REAL_BACKEND=1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nerrf_tpu.ops import pallas_segment, segment


@pytest.fixture(autouse=True)
def _clean_switchboard():
    yield
    pallas_segment.unregister()  # also disables the TPU auto-probe


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


@pytest.mark.parametrize("E,N,F", [(37, 11, 5), (128, 128, 128), (300, 50, 33)])
@pytest.mark.parametrize("sorted_ids", [True, False])
def test_segment_sum_matches_xla(E, N, F, sorted_ids):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, N, size=E)
    if sorted_ids:
        ids = np.sort(ids)
    ids = jnp.asarray(ids, jnp.int32)
    data = _rand((E, F), 1)

    got = pallas_segment.segment_sum(data, ids, N, True)
    want = jax.ops.segment_sum(data, ids, num_segments=N)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_segment_sum_empty_segments_are_zero():
    ids = jnp.asarray([0, 0, 3], jnp.int32)
    data = jnp.ones((3, 4), jnp.float32)
    out = pallas_segment.segment_sum(data, ids, 6, True)
    np.testing.assert_allclose(out[1], 0.0)
    np.testing.assert_allclose(out[0], 2.0)
    np.testing.assert_allclose(out[3], 1.0)
    np.testing.assert_allclose(out[4:], 0.0)


@pytest.mark.parametrize("E,N,F", [(37, 11, 5), (300, 300, 64), (512, 40, 130)])
def test_sorted_segment_sum_matches_xla(E, N, F):
    ids = jnp.asarray(
        np.sort(np.random.default_rng(21).integers(0, N, size=E)), jnp.int32)
    data = _rand((E, F), 22)
    got = pallas_segment.segment_sum_sorted(data, ids, N, True)
    want = jax.ops.segment_sum(data, ids, num_segments=N,
                               indices_are_sorted=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sorted_segment_sum_skewed_band():
    # worst-case skew: every edge lands in one segment (band spans all edge
    # tiles for that segment tile, zero band everywhere else)
    E, N, F = 400, 257, 9
    ids = jnp.full((E,), 131, jnp.int32)
    data = _rand((E, F), 23)
    got = pallas_segment.segment_sum_sorted(data, ids, N, True)
    want = jax.ops.segment_sum(data, ids, num_segments=N)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sorted_segment_sum_builder_padding_layout():
    # the builder's layout: sorted valid prefix, then padding slots pointing
    # at the last node (builder.py:474-478) — still globally nondecreasing
    N, F = 64, 12
    valid = np.sort(np.random.default_rng(24).integers(0, 50, size=90))
    ids = jnp.asarray(np.concatenate([valid, np.full(38, N - 1)]), jnp.int32)
    data = _rand((128, F), 25)
    got = pallas_segment.segment_sum_sorted(data, ids, N, True)
    want = jax.ops.segment_sum(data, ids, num_segments=N)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sorted_segment_sum_band_past_end_no_edge_padding():
    # E an exact multiple of the edge tile (no pad ids), every id far below
    # the upper segment tiles: their bands sit entirely past the last edge
    # tile and the block index must clamp into range (review finding)
    E, N, F = 128, 257, 7
    ids = jnp.asarray(np.sort(np.random.default_rng(29).integers(0, 60, E)),
                      jnp.int32)
    data = _rand((E, F), 30)
    got = pallas_segment.segment_sum_sorted(data, ids, N, True)
    want = jax.ops.segment_sum(data, ids, num_segments=N)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sorted_segment_sum_grad_is_gather():
    ids = jnp.asarray([0, 1, 1, 2], jnp.int32)
    data = _rand((4, 3), 26)

    def loss(d):
        return jnp.sum(pallas_segment.segment_sum_sorted(d, ids, 3, True) ** 2)

    g = jax.grad(loss)(data)
    want = jax.grad(
        lambda d: jnp.sum(jax.ops.segment_sum(d, ids, num_segments=3) ** 2)
    )(data)
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-5)


def test_sorted_gather_matches_take_sparse_spread():
    # nondecreasing ids whose 128-edge tiles each SPAN many segment tiles
    # (sparse ids) — the band is wide, not the ≤2 tiles of dense layouts
    N, F, E = 2000, 10, 256
    ids = jnp.asarray(
        np.sort(np.random.default_rng(33).integers(0, N, E)), jnp.int32)
    table = _rand((N, F), 34)
    got = pallas_segment._gather_sorted_call(table, ids, interpret=True)
    np.testing.assert_allclose(got, jnp.take(table, ids, axis=0),
                               rtol=1e-5, atol=1e-6)


def test_sorted_segment_sum_grad_sparse_spread():
    # backward = banded gather; sparse sorted ids exercise wide bands
    N, F, E = 2000, 6, 256
    ids = jnp.asarray(
        np.sort(np.random.default_rng(35).integers(0, N, E)), jnp.int32)
    data = _rand((E, F), 36)

    def loss(d):
        return jnp.sum(pallas_segment.segment_sum_sorted(d, ids, N, True) ** 2)

    g = jax.grad(loss)(data)
    want = jax.grad(
        lambda d: jnp.sum(jax.ops.segment_sum(d, ids, num_segments=N) ** 2)
    )(data)
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-5)


def test_switchboard_routes_sorted_calls_to_banded_kernel(monkeypatch):
    pallas_segment.register(interpret=True)
    calls = []
    real = segment._SEGMENT_SUM_SORTED_IMPL
    monkeypatch.setattr(segment, "_SEGMENT_SUM_SORTED_IMPL",
                        lambda *a: calls.append(1) or real(*a))
    data = _rand((20, 7), 27)
    ids = jnp.asarray(np.sort(np.random.default_rng(28).integers(0, 9, 20)),
                      jnp.int32)
    got = segment.segment_sum(data, ids, 9, sorted_ids=True)
    assert calls, "sorted_ids=True must route to the banded kernel"
    np.testing.assert_allclose(
        got, jax.ops.segment_sum(data, ids, num_segments=9),
        rtol=1e-5, atol=1e-5)
    calls.clear()
    segment.segment_sum(data, ids, 9, sorted_ids=False)
    assert not calls, "unsorted calls must not use the banded kernel"


def test_sorted_segment_sum_under_vmap_and_grad():
    # the model vmaps aggregation over the window batch — the banded
    # kernel (scalar-prefetch grid) must batch and differentiate there
    B, E, N, F = 3, 150, 40, 9
    rng = np.random.default_rng(31)
    ids = jnp.asarray(np.sort(rng.integers(0, N, (B, E)), axis=1), jnp.int32)
    data = jnp.asarray(rng.normal(size=(B, E, F)), jnp.float32)
    f = jax.vmap(lambda d, i: pallas_segment.segment_sum_sorted(d, i, N, True))
    want_f = jax.vmap(lambda d, i: jax.ops.segment_sum(d, i, num_segments=N))
    np.testing.assert_allclose(f(data, ids), want_f(data, ids),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda d: jnp.sum(f(d, ids) ** 2))(data)
    want_g = jax.grad(lambda d: jnp.sum(want_f(d, ids) ** 2))(data)
    np.testing.assert_allclose(g, want_g, rtol=1e-4, atol=1e-4)


def test_gather_rows_matches_take():
    table = _rand((45, 19), 2)
    idx = jnp.asarray(np.random.default_rng(3).integers(0, 45, size=130), jnp.int32)
    got = pallas_segment.gather_rows(table, idx, True)
    np.testing.assert_allclose(got, jnp.take(table, idx, axis=0), rtol=1e-5, atol=1e-6)


def test_segment_sum_grad_is_gather():
    ids = jnp.asarray([2, 0, 2, 1], jnp.int32)
    data = _rand((4, 3), 4)

    def loss(d):
        out = pallas_segment.segment_sum(d, ids, 3, True)
        return jnp.sum(out * out)

    g = jax.grad(loss)(data)
    want = jax.grad(
        lambda d: jnp.sum(jax.ops.segment_sum(d, ids, num_segments=3) ** 2)
    )(data)
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-5)


def test_gather_rows_grad_is_segment_sum():
    table = _rand((6, 3), 5)
    idx = jnp.asarray([5, 5, 0, 2], jnp.int32)

    def loss(t):
        return jnp.sum(pallas_segment.gather_rows(t, idx, True) ** 2)

    g = jax.grad(loss)(table)
    want = jax.grad(lambda t: jnp.sum(jnp.take(t, idx, axis=0) ** 2))(table)
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-5)


def test_switchboard_registration_routes_calls():
    pallas_segment.register(interpret=True)
    data = _rand((20, 7), 6)
    ids = jnp.asarray(np.sort(np.random.default_rng(7).integers(0, 9, 20)), jnp.int32)
    got = segment.segment_sum(data, ids, 9)
    want = jax.ops.segment_sum(data, ids, num_segments=9)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    table = _rand((9, 7), 8)
    np.testing.assert_allclose(
        segment.gather_rows(table, ids), jnp.take(table, ids, axis=0),
        rtol=1e-5, atol=1e-6,
    )


def test_segment_mean_through_pallas_with_weights():
    pallas_segment.register(interpret=True)
    data = _rand((16, 5), 9)
    w = jnp.abs(_rand((16,), 10)) + 0.1
    ids = jnp.asarray(np.sort(np.random.default_rng(11).integers(0, 6, 16)), jnp.int32)
    got = segment.segment_mean(data, ids, 6, weights=w)
    tot = jax.ops.segment_sum(data * w[:, None], ids, num_segments=6)
    den = jax.ops.segment_sum(w[:, None], ids, num_segments=6)
    np.testing.assert_allclose(got, tot / jnp.maximum(den, 1e-6), rtol=1e-4, atol=1e-5)


def test_zero_row_inputs_return_zeros():
    out = pallas_segment.segment_sum(jnp.zeros((0, 4), jnp.float32),
                                     jnp.zeros((0,), jnp.int32), 5, True)
    assert out.shape == (5, 4) and float(jnp.sum(out)) == 0.0
    g = pallas_segment.gather_rows(jnp.zeros((3, 4), jnp.float32),
                                   jnp.zeros((0,), jnp.int32), True)
    assert g.shape == (0, 4)


def test_first_use_inside_a_trace_installs_the_kernels(monkeypatch):
    """A jit-first process: the very first switchboard call happens while
    tracing.  Registration must happen right there, so that program is
    built from the Pallas kernels — not deferred to some later eager call
    while this trace quietly gets the XLA ops."""
    monkeypatch.setattr(segment, "_AUTO_TRIED", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    real_register = pallas_segment.register
    monkeypatch.setattr(pallas_segment, "register",
                        lambda: real_register(interpret=True))
    data = _rand((20, 7), 40)
    ids = jnp.asarray(np.sort(np.random.default_rng(41).integers(0, 9, 20)),
                      jnp.int32)

    traced = jax.make_jaxpr(
        lambda d: segment.segment_sum(d, ids, 9, sorted_ids=True))(data)
    assert "pallas_call" in str(traced)
    assert segment.active_impls() == {
        "segment_sum": "pallas_dense", "segment_sum_sorted": "pallas_banded",
        "gather_rows": "xla_selection_matmul",
        "sage_aggregate": "pallas_fused"}


def test_xla_only_scope_bypasses_registered_kernels(monkeypatch):
    """What a GSPMD-partitioned program traces under (parallel.mesh_ops):
    every op from its XLA composition, reported as such, and the
    registration (and a TPU's gather route) back in force when the scope
    ends."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pallas_segment.register(interpret=True)
    data = _rand((20, 7), 42)
    ids = jnp.asarray(np.sort(np.random.default_rng(43).integers(0, 9, 20)),
                      jnp.int32)

    def trace():  # a fresh function each time: jax caches traces by identity
        return str(jax.make_jaxpr(lambda d: segment.gather_rows(
            segment.segment_sum(d, ids, 9, sorted_ids=True), ids))(data))

    with segment.xla_only():
        assert set(segment.active_impls().values()) == {"xla"}
        assert "pallas_call" not in trace()
        assert "dot_general" not in trace()
    assert segment.active_impls()["gather_rows"] == "xla_selection_matmul"
    assert "pallas_call" in trace() and "dot_general" in trace()


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
    static shape.  `active_impls()` names the route of the shipped buckets,
    outside `xla_only()` and inside it."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert segment.gather_rows_route(n) == want
    if n is None:
        assert segment.active_impls()["gather_rows"] == want
    with segment.xla_only():
        assert segment.gather_rows_route(n) == "xla"
        assert segment.active_impls()["gather_rows"] == "xla"


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


def test_sorted_kernels_compiled_on_tpu():
    """Chip-gated: the COMPILED Mosaic lowering of the banded kernels — not
    interpret mode — must match XLA at flagship-like shapes, forward and
    backward.  Runs only where a TPU is attached
    (NERRF_TEST_REAL_BACKEND=1), skips everywhere else."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs a real TPU backend (compiled Mosaic path)")
    E, N, F = 2048, 1024, 160
    rng = np.random.default_rng(5)
    ids = jnp.asarray(np.sort(rng.integers(0, N, E)), jnp.int32)
    data = jnp.asarray(rng.normal(size=(E, F)), jnp.float32)

    got = jax.jit(
        lambda d, i: pallas_segment.segment_sum_sorted(d, i, N, False))(data, ids)
    want = jax.ops.segment_sum(data, ids, num_segments=N,
                               indices_are_sorted=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    def loss_pallas(d):
        return jnp.sum(pallas_segment.segment_sum_sorted(d, ids, N, False) ** 2)

    def loss_xla(d):
        return jnp.sum(jax.ops.segment_sum(d, ids, num_segments=N,
                                           indices_are_sorted=True) ** 2)

    gp = jax.jit(jax.grad(loss_pallas))(data)
    gx = jax.jit(jax.grad(loss_xla))(data)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gx),
                               rtol=2e-4, atol=2e-4)


def test_fused_sage_kernel_compiled_on_tpu():
    """Chip-gated twin of the test above for the fused SAGE kernel — the
    one `auto` gives the deployed 4096 bucket: compiled Mosaic, forward and
    VJP under vmap, against the XLA composition that serves off-TPU."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs a real TPU backend (compiled Mosaic path)")
    B, E, N, F = 2, 8192, 4096, 160
    rng = np.random.default_rng(9)
    dst = np.sort(rng.integers(0, N, (B, E))).astype(np.int32)
    src = rng.integers(0, N, (B, E)).astype(np.int32)
    order = np.argsort(src, axis=1)
    take = lambda a: np.take_along_axis(a, order, 1)
    wf = rng.uniform(0.1, 1.0, (B, E)).astype(np.float32)
    wr = rng.uniform(0.1, 1.0, (B, E)).astype(np.float32)
    edges = tuple(jnp.asarray(a) for a in (
        dst, src, take(src), take(dst), wf, take(wf), take(wr), wr))
    msg = jnp.asarray(rng.normal(size=(B, N, F)), jnp.float32)

    fused = jax.vmap(lambda m, *e: pallas_segment.sage_aggregate_fused(
        m, *e, N, False))
    xla = jax.vmap(lambda m, *e: segment.sage_aggregate_xla(m, *e, N))
    np.testing.assert_allclose(
        np.asarray(jax.jit(fused)(msg, *edges)),
        np.asarray(jax.jit(xla)(msg, *edges)), rtol=2e-4, atol=2e-4)
    gp = jax.jit(jax.grad(lambda m: jnp.sum(fused(m, *edges) ** 2)))(msg)
    gx = jax.jit(jax.grad(lambda m: jnp.sum(xla(m, *edges) ** 2)))(msg)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gx),
                               rtol=2e-4, atol=2e-4)
