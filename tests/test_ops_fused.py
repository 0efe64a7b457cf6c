"""Fused bidirectional SAGE-aggregation kernel vs the XLA composition.

Interpret mode on the CPU mesh (tests/conftest.py), like test_pallas_ops.py;
the compiled Mosaic path is exercised on a real TPU by chip_smoke.py and by
test_pallas_ops.py::test_fused_sage_kernel_compiled_on_tpu.  The reference
semantics throughout:

    out[n] = Σ_{e: dst(e)=n} ŵf(e)·msg[src(e)] + Σ_{e: src(e)=n} ŵr(e)·msg[dst(e)]

with pre-normalized weights, over the builder's dst-sorted edge list and the
model's src-sorted view.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nerrf_tpu.ops import pallas_segment, segment


@pytest.fixture(autouse=True)
def _clean_switchboard():
    yield
    pallas_segment.unregister()  # also disables the TPU auto-probe


def _graph(E, N, seed, zero_frac=0.0):
    """Random graph in both sorted views + both weight vectors in both
    orders — the full sage_aggregate argument tuple (minus msg)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N, E).astype(np.int32)
    dst = np.sort(rng.integers(0, N, E)).astype(np.int32)
    w = rng.uniform(0.1, 1.0, E).astype(np.float32)
    if zero_frac:
        w[rng.random(E) < zero_frac] = 0.0  # masked edges
    order = np.argsort(src)
    wf_d = (w * rng.uniform(0.5, 2.0, E)).astype(np.float32)
    wr_d = (w * rng.uniform(0.5, 2.0, E)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (
        dst, src, src[order], dst[order],
        wf_d, wf_d[order], wr_d[order], wr_d))


def _ref(msg, edges, n):
    dst, src, src_s, dst_s, wf_d, _wf_s, wr_s, _wr_d = edges
    m = msg.astype(jnp.float32)
    fwd = jax.ops.segment_sum(wf_d[:, None] * jnp.take(m, src, axis=0),
                              dst, num_segments=n)
    rev = jax.ops.segment_sum(wr_s[:, None] * jnp.take(m, dst_s, axis=0),
                              src_s, num_segments=n)
    return fwd + rev


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


@pytest.mark.parametrize("E,N,F", [(37, 11, 5), (128, 128, 128),
                                   (300, 150, 33), (513, 257, 130)])
def test_fused_matches_xla_composition(E, N, F):
    edges = _graph(E, N, seed=E)
    msg = _rand((N, F), E + 1)
    got = pallas_segment.sage_aggregate_fused(msg, *edges, N, True)
    np.testing.assert_allclose(got, _ref(msg, edges, N),
                               rtol=1e-5, atol=1e-5)


def test_fused_masked_edges_contribute_nothing():
    # zero-weight (masked) edges must vanish even though their rows are
    # still gathered inside the kernel
    edges = _graph(200, 64, seed=3, zero_frac=0.4)
    msg = _rand((64, 20), 4)
    np.testing.assert_allclose(
        pallas_segment.sage_aggregate_fused(msg, *edges, 64, True),
        _ref(msg, edges, 64), rtol=1e-5, atol=1e-5)


def test_fused_empty_segments_are_exactly_zero():
    # every edge lands on nodes {0, 1}; all other rows must be exact zeros
    # (pre-normalized weights: no eps-division residue)
    E, N, F = 40, 50, 7
    rng = np.random.default_rng(5)
    src = rng.integers(0, 2, E).astype(np.int32)
    dst = np.sort(rng.integers(0, 2, E)).astype(np.int32)
    w = rng.uniform(0.1, 1.0, E).astype(np.float32)
    order = np.argsort(src)
    edges = tuple(jnp.asarray(a) for a in (
        dst, src, src[order], dst[order], w, w[order], w[order], w))
    out = pallas_segment.sage_aggregate_fused(_rand((N, F), 6), *edges, N, True)
    assert float(jnp.max(jnp.abs(out[2:]))) == 0.0
    np.testing.assert_allclose(out, _ref(_rand((N, F), 6), edges, N),
                               rtol=1e-5, atol=1e-5)


def test_fused_degenerate_shapes():
    out = pallas_segment.sage_aggregate_fused(
        jnp.zeros((5, 4), jnp.float32),
        *[jnp.zeros((0,), jnp.int32)] * 4,
        *[jnp.zeros((0,), jnp.float32)] * 4, 5, True)
    assert out.shape == (5, 4) and float(jnp.sum(jnp.abs(out))) == 0.0


def test_fused_vjp_matches_xla_grad():
    edges = _graph(150, 40, seed=7, zero_frac=0.2)
    msg = _rand((40, 9), 8)

    g = jax.grad(lambda m: jnp.sum(
        pallas_segment.sage_aggregate_fused(m, *edges, 40, True) ** 2))(msg)
    want = jax.grad(lambda m: jnp.sum(_ref(m, edges, 40) ** 2))(msg)
    np.testing.assert_allclose(g, want, rtol=1e-4, atol=1e-4)


def test_fused_under_vmap_and_grad():
    # the training path vmaps the model over the window batch — the fused
    # kernel (scalar-prefetch grid + VMEM scratch) must batch and
    # differentiate there
    B, E, N, F = 3, 150, 40, 9
    per = [_graph(E, N, seed=10 + b) for b in range(B)]
    edges = tuple(jnp.stack([p[i] for p in per]) for i in range(8))
    msg = _rand((B, N, F), 20)

    f = jax.vmap(lambda m, *e: pallas_segment.sage_aggregate_fused(
        m, *e, N, True))
    rf = jax.vmap(lambda m, *e: _ref(m, e, N))
    np.testing.assert_allclose(f(msg, *edges), rf(msg, *edges),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda m: jnp.sum(f(m, *edges) ** 2))(msg)
    want = jax.grad(lambda m: jnp.sum(rf(m, *edges) ** 2))(msg)
    np.testing.assert_allclose(g, want, rtol=1e-4, atol=1e-4)


def test_switchboard_routes_and_reports_pallas_fused(monkeypatch):
    pallas_segment.register(interpret=True)
    assert segment.active_impls()["sage_aggregate"] == "pallas_fused"
    calls = []
    real = segment._SAGE_FUSED_IMPL
    monkeypatch.setattr(segment, "_SAGE_FUSED_IMPL",
                        lambda *a: calls.append(1) or real(*a))
    edges = _graph(60, 30, seed=30)
    msg = _rand((30, 8), 31)
    got = segment.sage_aggregate(msg, *edges, 30)
    assert calls, "registered fused kernel must serve sage_aggregate"
    np.testing.assert_allclose(got, _ref(msg, edges, 30),
                               rtol=1e-5, atol=1e-5)

    segment.use_pallas(None)
    assert segment.active_impls()["sage_aggregate"] == "xla"
    np.testing.assert_allclose(segment.sage_aggregate(msg, *edges, 30),
                               _ref(msg, edges, 30), rtol=1e-5, atol=1e-5)


def test_graphsage_fused_mode_through_pallas_kernel():
    """The whole model in aggregation='fused' with the interpret-mode Pallas
    kernel registered must match the segment oracle — the end-to-end wiring
    (pre-normalized views, c_sum/s_f/s_r decomposition, bf16 casts), not
    just the bare op."""
    from nerrf_tpu.data import SimConfig, simulate_trace
    from nerrf_tpu.graph import GraphConfig
    from nerrf_tpu.models.graphsage import GraphSAGEConfig, GraphSAGET
    from nerrf_tpu.train.data import DatasetConfig, build_dataset

    tr = simulate_trace(SimConfig(duration_sec=60.0, attack=True,
                                  attack_start_sec=20.0, num_target_files=4,
                                  benign_rate_hz=20.0, seed=2))
    ds = build_dataset([tr], DatasetConfig(
        graph=GraphConfig(window_sec=45.0, stride_sec=20.0,
                          max_nodes=64, max_edges=128),
        seq_len=24, max_seqs=32))
    gin = ("node_feat", "node_type", "node_aux", "node_mask", "edge_src",
           "edge_dst", "edge_feat", "edge_mask")
    args = tuple(np.asarray(ds.arrays[k][0]) for k in gin)
    cfg = GraphSAGEConfig(hidden=32, num_layers=2, dropout=0.0,
                          dtype=jnp.float32, aggregation="segment")
    model_s = GraphSAGET(cfg)
    params = model_s.init(jax.random.PRNGKey(0), *args)["params"]
    want = model_s.apply({"params": params}, *args)

    pallas_segment.register(interpret=True)
    model_f = GraphSAGET(dataclasses.replace(cfg, aggregation="fused"))
    got = model_f.apply({"params": params}, *args)
    for k in ("edge_logit", "node_logit"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-2, atol=1e-3)

    # and the TRAINING path: parameter gradients through the registered
    # kernel's custom VJP (the adjoint's wf_s/wr_d weight exchange) must
    # match the segment oracle — a view-wiring bug that keeps the forward
    # right but breaks the adjoint would only ever surface here
    def loss(model):
        return lambda p: jnp.sum(
            model.apply({"params": p}, *args)["node_logit"] ** 2)

    g_fused = jax.grad(loss(model_f))(params)
    pallas_segment.unregister()
    g_seg = jax.grad(loss(model_s))(params)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g_seg, g_fused)
    assert max(jax.tree_util.tree_leaves(errs)) < 1e-3, errs
