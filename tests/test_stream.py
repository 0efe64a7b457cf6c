"""Long-context path: ring attention correctness and StreamNet training on a
dp×sp mesh (8 virtual CPU devices via conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nerrf_tpu.data import SimConfig, build_stream, build_streams, simulate_trace
from nerrf_tpu.models import StreamConfig, StreamNet, stream_loss
from nerrf_tpu.parallel import (
    MeshConfig,
    make_mesh,
    make_stream_train_step,
    ring_self_attention,
)
from nerrf_tpu.parallel.ring import _attention_local
from nerrf_tpu.train.loop import TrainConfig


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshConfig(dp=2, tp=1, sp=4))


def _qkv(b=2, t=64, h=2, d=8, seed=0):
    r = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(r.normal(size=(b, t, h, d)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_local(mesh, causal):
    q, k, v = _qkv()
    want = _attention_local(q, k, v, causal)
    got = ring_self_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_ring_attention_no_mesh_is_local():
    q, k, v = _qkv(seed=1)
    got = ring_self_attention(q, k, v, None, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_attention_local(q, k, v, True)),
        rtol=1e-6, atol=1e-6,
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t", [1100, 2048])
def test_blockwise_local_matches_dense(causal, t):
    """The flash-style local path (scan over key blocks, incl. ragged final
    block) is exact — identical to materialized attention."""
    from nerrf_tpu.parallel.ring import _attention_dense

    q, k, v = _qkv(b=1, t=t, h=2, d=8, seed=3)
    want = _attention_dense(q, k, v, causal)
    got = _attention_local(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_streamnet_sharded_forward_matches_unsharded(mesh):
    trace = simulate_trace(SimConfig(num_target_files=5, duration_sec=40.0, seed=3))
    sb = build_stream(trace, max_len=128)
    # batch must divide dp (2): tile segments to an even count
    tiled = sb.tile_to_multiple(2)
    feat, mask = jnp.asarray(tiled["feat"]), jnp.asarray(tiled["mask"])

    cfg = StreamConfig(dim=32, num_heads=2, num_layers=2, dropout=0.0)
    rng = jax.random.PRNGKey(0)
    params = StreamNet(cfg, mesh=None).init(rng, feat, mask)["params"]

    out_local = StreamNet(cfg, mesh=None).apply({"params": params}, feat, mask)
    with mesh:
        out_ring = StreamNet(cfg, mesh=mesh).apply({"params": params}, feat, mask)
    np.testing.assert_allclose(
        np.asarray(out_ring["event_logits"]),
        np.asarray(out_local["event_logits"]),
        rtol=5e-2, atol=5e-2,  # bf16 compute; structure must match, bits won't
    )


def test_stream_training_step_runs_and_improves(mesh):
    traces = [
        simulate_trace(SimConfig(num_target_files=4, duration_sec=30.0, seed=s))
        for s in (1, 2)
    ]
    sb = build_streams(traces, max_len=128)
    batch = sb.tile_to_multiple(2)

    cfg = StreamConfig(dim=32, num_heads=2, num_layers=2, dropout=0.0)
    model = StreamNet(cfg, mesh=mesh)
    init_fn, step_fn, place = make_stream_train_step(
        model, mesh, TrainConfig(learning_rate=3e-3, warmup_steps=2,
                                 num_steps=8))
    rng = jax.random.PRNGKey(0)
    with mesh:
        placed = place(batch)
        state = init_fn(rng, placed)
        losses = []
        for _ in range(8):
            state, loss, rng = step_fn(state, placed, rng)
            losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_build_stream_segments_and_labels():
    trace = simulate_trace(SimConfig(num_target_files=4, duration_sec=30.0, seed=5))
    sb = build_stream(trace, max_len=64)
    n_events = int(
        (trace.events.valid & (trace.events.syscall != 12)).sum()
    )
    assert sb.mask.sum() == n_events
    assert sb.feat.shape[1:] == (64, sb.feat.shape[2])
    assert ((sb.label == 0) | (sb.label == 1)).all()
    assert sb.label[~sb.mask].sum() == 0  # no labels on padding
    assert sb.label.sum() > 0  # the attack is in there


def test_blockwise_local_grads_match_dense():
    """Backward through the remat'd flash scan is exact (the r1 bench OOM fix
    must not change gradients)."""
    from nerrf_tpu.parallel.ring import _attention_dense

    q, k, v = _qkv(b=1, t=1100, h=2, d=8, seed=7)

    def loss_local(q, k, v):
        return (_attention_local(q, k, v, True) ** 2).sum()

    def loss_dense(q, k, v):
        return (_attention_dense(q, k, v, True) ** 2).sum()

    g_local = jax.grad(loss_local, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gl, gd in zip(g_local, g_dense):
        np.testing.assert_allclose(np.asarray(gl), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4)


def test_stream_train_step_at_bench_seq_len():
    """A full fwd+bwd step at the bench sequence length (T=4096, the shape
    whose residuals OOM'd BENCH_r01's stream leg).  On CPU this checks the
    remat path compiles and runs; HBM fit is verified on-chip by bench.py."""
    mesh1 = make_mesh(MeshConfig(dp=1, tp=1, sp=1), devices=jax.devices()[:1])
    r = np.random.default_rng(0)
    t = 4096
    batch = {
        "feat": r.normal(size=(1, t, 12)).astype(np.float32),
        "mask": np.ones((1, t), np.bool_),
        "label": (r.random((1, t)) < 0.1).astype(np.float32),
    }
    cfg = StreamConfig(dim=32, num_heads=2, num_layers=2, dropout=0.0)
    model = StreamNet(cfg, mesh=mesh1)
    init_fn, step_fn, place = make_stream_train_step(model, mesh1)
    with mesh1:
        placed = place(batch)
        state = init_fn(jax.random.PRNGKey(0), placed)
        state, loss, _ = step_fn(state, placed, jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
