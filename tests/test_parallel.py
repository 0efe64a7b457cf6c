"""Multi-chip sharding tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from nerrf_tpu.data import make_corpus
from nerrf_tpu.graph import GraphConfig
from nerrf_tpu.models import GraphSAGEConfig, JointConfig, LSTMConfig, NerrfNet
from nerrf_tpu.parallel import (
    MeshConfig,
    init_sharded_state,
    make_mesh,
    make_sharded_train_step,
    shard_batch,
)
from nerrf_tpu.parallel.mesh import param_sharding
from nerrf_tpu.train import TrainConfig, build_dataset
from nerrf_tpu.train.data import DatasetConfig


def _dataset():
    corpus = make_corpus(4, attack_fraction=0.5, base_seed=3, duration_sec=60.0,
                         num_target_files=4, benign_rate_hz=15.0)
    return build_dataset(corpus, DatasetConfig(
        graph=GraphConfig(window_sec=45.0, stride_sec=30.0, max_nodes=32, max_edges=64),
        seq_len=16, max_seqs=16,
    ))


def test_mesh_construction():
    assert len(jax.devices()) == 8, "conftest must force 8 virtual devices"
    mesh = make_mesh(MeshConfig(dp=-1, tp=2))
    assert mesh.shape == {"dp": 4, "tp": 2, "sp": 1}
    mesh = make_mesh(MeshConfig(dp=2, tp=2, sp=2))
    assert mesh.shape == {"dp": 2, "tp": 2, "sp": 2}
    with pytest.raises(ValueError):
        make_mesh(MeshConfig(dp=3, tp=3))


def test_param_sharding_rules():
    mesh = make_mesh(MeshConfig(dp=4, tp=2))
    model = NerrfNet(JointConfig(
        gnn=GraphSAGEConfig(hidden=128, num_layers=2),
        lstm=LSTMConfig(hidden=128, num_layers=1),
    ))
    ds = _dataset()
    one = {k: jnp.asarray(v[0]) for k, v in ds.arrays.items()}
    from nerrf_tpu.train.loop import model_inputs
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), *model_inputs(one))
    )["params"]
    shardings = param_sharding(mesh, shapes)
    flat = jax.tree_util.tree_flatten_with_path(shardings)[0]
    tp_sharded = [kp for kp, s in flat if s.spec == P(None, "tp")]
    replicated = [kp for kp, s in flat if s.spec == P()]
    assert len(tp_sharded) > 10  # big kernels + embeddings
    assert len(replicated) > 5   # biases, layernorms, small heads


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_semantics():
    """One dp×tp-sharded step on the virtual mesh: runs, loss finite, and the
    sharded loss matches the single-device loss for identical params/batch."""
    ds = _dataset()
    n = (len(ds) // 8) * 8 or 8
    idx = np.arange(n) % len(ds)
    batch_np = {k: v[idx] for k, v in ds.arrays.items()}

    cfg = TrainConfig(
        model=JointConfig(
            gnn=GraphSAGEConfig(hidden=32, num_layers=2, dropout=0.0),
            lstm=LSTMConfig(hidden=32, num_layers=1, dropout=0.0),
        ),
        batch_size=n, num_steps=2, learning_rate=1e-3, warmup_steps=1,
    )
    model = NerrfNet(cfg.model)
    mesh = make_mesh(MeshConfig(dp=4, tp=2))
    state = init_sharded_state(model, cfg, ds.arrays, mesh)
    step = make_sharded_train_step(model, cfg, mesh)
    batch = shard_batch(mesh, batch_np)

    # reference loss on one device with the same (gathered) params
    from nerrf_tpu.train.loop import make_loss_fn
    params_host = jax.device_get(state.params)
    loss_ref, _ = make_loss_fn(model, cfg)(
        jax.tree.map(jnp.asarray, params_host),
        {k: jnp.asarray(v) for k, v in batch_np.items()},
        jax.random.PRNGKey(1),  # dropout 0 → rng irrelevant
    )

    state2, loss, aux, rng2 = step(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(loss))
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=2e-2)
    # step 0 runs at lr=0 (warmup); take a second step so params actually move
    state2, loss2, _, _ = step(state2, batch, rng2)
    assert np.isfinite(float(loss2))
    # params actually updated
    delta = jax.tree_util.tree_reduce(
        lambda a, p: a + float(jnp.abs(p).sum()),
        jax.tree.map(lambda a, b: a - b, state2.params, jax.tree.map(jnp.asarray, params_host)),
        0.0,
    )
    assert delta > 0


# -- one-chip and sharded steps are the same ops -------------------------------


def _row_gather_prims(text):
    """Primitive names under `gnn_heads/row_gather` in a lowered module."""
    from tests.conftest import scope_paths

    return {p.rsplit("/", 1)[1] for p in scope_paths(text)
            if "/gnn_heads/row_gather/" in p}


def test_sharded_and_one_device_steps_trace_the_same_ops_on_a_tpu(
        monkeypatch):
    """With the backend read as a TPU, the step over the 8-device mesh and
    the one-device step are keyed by the same `active_impls()` and hold the
    same route for the heads' row gather (the selection matmul: a
    `dot_general`, which GSPMD partitions): no scope swaps a mesh program's
    ops for others any more."""
    from nerrf_tpu.ops.segment import active_impls
    from nerrf_tpu.train import loop

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ds = _dataset()
    cfg = TrainConfig(
        model=JointConfig(
            gnn=GraphSAGEConfig(hidden=32, num_layers=2, dropout=0.0),
            lstm=LSTMConfig(hidden=32, num_layers=1, dropout=0.0)),
        batch_size=8, num_steps=2)
    model = NerrfNet(cfg.model)
    idx = np.arange(8) % len(ds)
    batch_np = {k: v[idx] for k, v in ds.arrays.items()}
    mesh = make_mesh(MeshConfig(dp=4, tp=2))
    rng = jax.random.PRNGKey(0)

    state = init_sharded_state(model, cfg, ds.arrays, mesh)
    sharded = make_sharded_train_step(model, cfg, mesh).lower(
        state, shard_batch(mesh, batch_np), rng).as_text(debug_info=True)
    one_state = loop.init_state(model, cfg, ds.arrays, rng)
    one = loop.make_train_step(model, cfg).lower(
        one_state, {k: jnp.asarray(v) for k, v in batch_np.items()},
        rng).as_text(debug_info=True)

    for text in (sharded, one):
        assert "custom_call" not in text.replace("@Sharding", "")
    assert "dot_general" in _row_gather_prims(one)
    assert _row_gather_prims(sharded) == _row_gather_prims(one)
    assert not {"gather", "scatter-add"} & _row_gather_prims(sharded)
    assert (loop.step_key_extra(cfg, "train_step_sharded")["ops"]
            == loop.step_key_extra(cfg, "train_step")["ops"]
            == repr(sorted(active_impls().items()))
            == "[('gather_rows', 'xla_selection_matmul')]")


def test_stream_step_traces_the_same_ops_on_any_mesh_on_a_tpu(monkeypatch):
    """`make_stream_train_step` over dp x sp and over one device, with the
    backend read as a TPU: `active_impls()` is the same before, between and
    after the two traces (tracing installs nothing), and every primitive of
    both programs is the compiler's."""
    from nerrf_tpu.models import StreamConfig, StreamNet
    from nerrf_tpu.ops.segment import active_impls
    from nerrf_tpu.parallel import make_stream_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    r = np.random.default_rng(0)
    batch = {"feat": r.normal(size=(2, 64, 12)).astype(np.float32),
             "mask": np.ones((2, 64), np.bool_),
             "label": (r.random((2, 64)) < 0.1).astype(np.float32)}
    cfg = StreamConfig(dim=32, num_heads=2, num_layers=2, dropout=0.0)

    def prims(mesh):
        model = StreamNet(cfg, mesh=mesh)
        init_fn, step_fn, place = make_stream_train_step(model, mesh)
        with mesh:
            placed = place(batch)
            state = init_fn(jax.random.PRNGKey(0), placed)
            closed = jax.make_jaxpr(step_fn)(state, placed,
                                             jax.random.PRNGKey(1))
        found = set()

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                found.add(eqn.primitive.name)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(closed.jaxpr)
        return found

    want = {"gather_rows": "xla_selection_matmul"}
    assert active_impls() == want
    over_mesh = prims(make_mesh(MeshConfig(dp=2, tp=1, sp=4)))
    assert active_impls() == want
    one = prims(make_mesh(MeshConfig(dp=1, tp=1, sp=1),
                          devices=jax.devices()[:1]))
    assert active_impls() == want
    # what the mesh adds is the ring's own (the one hand-written
    # collective); no program holds a kernel call
    assert {"shard_map", "ppermute"} <= over_mesh - one
    assert not {p for p in over_mesh | one
                if "pallas" in p or "custom_call" in p}
