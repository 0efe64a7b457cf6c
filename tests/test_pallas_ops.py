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
    assert set(segment.active_impls().values()) == {
        "pallas_dense", "pallas_banded", "pallas_blocked", "pallas_fused"}


def test_xla_only_scope_bypasses_registered_kernels():
    """What a GSPMD-partitioned program traces under (parallel.mesh_ops):
    every op from its XLA composition, reported as such, and the
    registration back in force when the scope ends."""
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
    assert segment.active_impls()["gather_rows"] == "pallas_blocked"
    assert "pallas_call" in trace()


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
