"""What the aggregation owes the model, whichever of the three routes serves
it, and what `NerrfNet`'s seq -> node scatter owes its sequences.

Every route is an XLA composition on every backend (docs/kernel-paths.md),
so these run the code a TPU runs; the routes' numbers against each other at
the ladder's buckets are in test_models.py, the bare ops against numpy loops
in test_ops_segment.py and test_ops_fused.py.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nerrf_tpu.data.sequences import SEQ_FEATURE_DIM
from nerrf_tpu.graph import EDGE_FEATURE_DIM, NODE_FEATURE_DIM
from nerrf_tpu.models import (
    GraphSAGEConfig,
    GraphSAGET,
    JointConfig,
    LSTMConfig,
    NerrfNet,
)
from nerrf_tpu.models import joint

MODES = ("segment", "dense_adj", "fused")
N, E, REAL_N, REAL_E = 24, 48, 18, 30
LONER = 17          # a real node that no real edge touches


def _window(seed=0):
    """One padded window the way the builder lays it out: real edges first,
    sorted by dst, among the real nodes but never on `LONER`; then masked
    slots that point at the last node."""
    rng = np.random.default_rng(seed)
    src = np.full(E, N - 1, np.int32)
    dst = np.full(E, N - 1, np.int32)
    src[:REAL_E] = rng.integers(0, LONER, REAL_E)
    dst[:REAL_E] = np.sort(rng.integers(0, LONER, REAL_E))
    edge_feat = rng.normal(size=(E, EDGE_FEATURE_DIM)).astype(np.float32)
    edge_feat[:, 12] = rng.uniform(0.0, 1.0, E)      # the causality weight
    return dict(
        node_feat=rng.normal(size=(N, NODE_FEATURE_DIM)).astype(np.float32),
        node_type=rng.integers(0, 4, N).astype(np.int32),
        node_aux=rng.integers(0, 8, N).astype(np.int32),
        node_mask=np.arange(N) < REAL_N,
        edge_src=src, edge_dst=dst, edge_feat=edge_feat,
        edge_mask=np.arange(E) < REAL_E)


def _model(mode):
    return GraphSAGET(GraphSAGEConfig(hidden=16, num_layers=2, dropout=0.0,
                                      dtype=jnp.float32, aggregation=mode))


def _params(model, w):
    return model.init(jax.random.PRNGKey(3), *w.values())["params"]


def _apply(model, params, w):
    out = model.apply({"params": params}, *w.values())
    return np.asarray(out["node_logit"]), np.asarray(out["edge_logit"])


@pytest.mark.parametrize("mode", MODES)
def test_masked_edges_change_no_real_logit(mode):
    """Whatever a masked slot holds (other endpoints, other features), no
    real node's and no real edge's logit moves."""
    w = _window()
    model = _model(mode)
    params = _params(model, w)
    node, edge = _apply(model, params, w)
    rng = np.random.default_rng(9)
    other = dict(w)
    other["edge_src"] = w["edge_src"].copy()
    other["edge_src"][REAL_E:] = rng.integers(0, REAL_N, E - REAL_E)
    other["edge_dst"] = w["edge_dst"].copy()
    other["edge_dst"][REAL_E:] = LONER          # still nondecreasing
    other["edge_feat"] = w["edge_feat"].copy()
    other["edge_feat"][REAL_E:] = rng.normal(
        size=(E - REAL_E, EDGE_FEATURE_DIM)) * 50.0
    node2, edge2 = _apply(model, params, other)
    np.testing.assert_allclose(node2[:REAL_N], node[:REAL_N],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(edge2[:REAL_E], edge[:REAL_E],
                               rtol=1e-5, atol=1e-5)
    assert (edge2[REAL_E:] == -30.0).all()


@pytest.mark.parametrize("mode", MODES)
def test_a_node_with_no_real_edge_gets_the_self_path_only(mode):
    """`LONER`'s aggregate is exactly zero in every layer, so its logit is
    what it is in a window with no edges at all: nothing leaks in from an
    empty segment's normalisation."""
    w = _window()
    model = _model(mode)
    params = _params(model, w)
    node, _ = _apply(model, params, w)
    bare = dict(w, edge_mask=np.zeros(E, bool))
    node_bare, _ = _apply(model, params, bare)
    np.testing.assert_allclose(node[LONER], node_bare[LONER],
                               rtol=1e-6, atol=1e-6)
    # and a node that has edges does hear from them
    assert np.abs(node[:LONER] - node_bare[:LONER]).max() > 1e-4


@pytest.mark.parametrize("mode", MODES)
def test_a_window_with_every_edge_masked(mode):
    """Finite, every edge logit at the masked value, and the node logits a
    function of the nodes alone."""
    w = dict(_window(), edge_mask=np.zeros(E, bool))
    model = _model(mode)
    params = _params(model, w)
    node, edge = _apply(model, params, w)
    assert np.isfinite(node).all() and (edge == -30.0).all()
    rng = np.random.default_rng(4)
    other = dict(w, edge_src=rng.integers(0, N, E).astype(np.int32),
                 edge_dst=np.sort(rng.integers(0, N, E)).astype(np.int32))
    node2, _ = _apply(model, params, other)
    np.testing.assert_allclose(node2, node, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("mode", MODES)
def test_a_batch_of_windows_under_vmap_and_grad(mode):
    """The trainer vmaps the model over the window batch: the batched
    logits are the windows' own, and the parameter gradient of a summed
    loss is the sum of the windows' gradients."""
    ws = [_window(seed) for seed in (0, 1, 2)]
    batch = {k: jnp.stack([w[k] for w in ws]) for k in ws[0]}
    model = _model(mode)
    params = _params(model, ws[0])

    def loss_one(p, *args):
        out = model.apply({"params": p}, *args)
        return jnp.sum(out["node_logit"] ** 2) + jnp.sum(
            jnp.where(args[-1], out["edge_logit"], 0.0) ** 2)

    def loss_batch(p):
        return jnp.sum(jax.vmap(lambda *a: loss_one(p, *a))(*batch.values()))

    got = jax.grad(loss_batch)(params)
    want = jax.tree_util.tree_map(
        lambda *g: sum(g),
        *[jax.grad(loss_one)(params, *w.values()) for w in ws])
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (1.0 + jnp.max(jnp.abs(b)))),
        got, want)
    assert max(jax.tree_util.tree_leaves(errs)) < 1e-4, errs
    node = jax.vmap(lambda *a: model.apply({"params": params}, *a)[
        "node_logit"])(*batch.values())
    for b, w in enumerate(ws):
        np.testing.assert_allclose(node[b], _apply(model, params, w)[0],
                                   rtol=1e-5, atol=1e-5)


def test_dense_adj_parameter_gradients_match_segment():
    """`dense_adj` TRAINS like the oracle, not only infers like it: every
    parameter's gradient, in f32 (`test_gnn_fused_mode_gradient_parity`'s
    twin for the route every TPU bucket takes)."""
    w = _window(5)
    m_s, m_d = _model("segment"), _model("dense_adj")
    params = _params(m_s, w)

    def loss(model):
        def of(p):
            out = model.apply({"params": p}, *w.values())
            return jnp.sum(out["node_logit"] ** 2) + jnp.sum(
                jnp.where(w["edge_mask"], out["edge_logit"], 0.0) ** 2)
        return of

    g_s, g_d = jax.grad(loss(m_s))(params), jax.grad(loss(m_d))(params)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g_s, g_d)
    assert max(jax.tree_util.tree_leaves(errs)) < 1e-3, errs
    assert min(float(jnp.max(jnp.abs(g)))
               for g in jax.tree_util.tree_leaves(g_s)) > 0.0


# -- NerrfNet's seq -> node scatter (models/joint.py) -------------------------


class _NodesIn(nn.Module):
    """Stands in for `GraphSAGET` inside `NerrfNet`: hands back the node
    features the GNN was given, which is where the scatter's sum lands."""

    cfg: GraphSAGEConfig

    def __call__(self, node_feat, *rest, deterministic=True):
        return {"nodes_in": node_feat}


S = 6               # sequences in the window


@pytest.fixture
def scatter_net(monkeypatch):
    """(run, expect): `NerrfNet` with the GNN replaced by `_NodesIn`, and
    the numpy loop that says what ``nodes_in - node_feat`` must be."""
    monkeypatch.setattr(joint, "GraphSAGET", _NodesIn)
    w = _window()
    rng = np.random.default_rng(8)
    seq_feat = rng.normal(size=(S, 10, SEQ_FEATURE_DIM)).astype(np.float32)
    seq_mask = np.ones((S, 10), bool)
    model = NerrfNet(JointConfig(lstm=LSTMConfig(
        hidden=8, num_layers=1, dropout=0.0, dtype=jnp.float32)))
    params = model.init(jax.random.PRNGKey(0), *w.values(), seq_feat,
                        seq_mask, np.zeros(S, np.int32))["params"]

    def run(p, idx):
        out = model.apply({"params": p}, *w.values(), seq_feat, seq_mask,
                          idx)
        return out["nodes_in"] - w["node_feat"], out["seq_emb"]

    def expect(p, seq_emb, idx):
        dense = p["seq_to_node"]
        h_seq = (np.asarray(seq_emb, np.float64)
                 @ np.asarray(dense["kernel"], np.float64)
                 + np.asarray(dense["bias"], np.float64))
        want = np.zeros((N, NODE_FEATURE_DIM))
        for s, node in enumerate(np.asarray(idx)):
            if node >= 0:
                want[node] += h_seq[s]
        return want

    return run, expect, params


def test_scatter_a_sequence_with_index_minus_one_adds_nothing(scatter_net):
    run, expect, params = scatter_net
    idx = np.array([3, -1, 5, -1, -1, 0], np.int32)
    got, seq_emb = run(params, idx)
    np.testing.assert_allclose(got, expect(params, seq_emb, idx),
                               rtol=1e-5, atol=1e-5)
    untouched = np.setdiff1d(np.arange(N), [3, 5, 0])
    assert not np.asarray(got)[untouched].any()      # exact zeros
    none, _ = run(params, np.full(S, -1, np.int32))
    assert not np.asarray(none).any()


def test_scatter_two_sequences_on_one_node_add(scatter_net):
    run, expect, params = scatter_net
    idx = np.array([4, 4, 4, N - 1, 2, N - 1], np.int32)
    got, seq_emb = run(params, idx)
    np.testing.assert_allclose(got, expect(params, seq_emb, idx),
                               rtol=1e-5, atol=1e-5)
    alone = [run(params, np.where(np.arange(S) == s, idx, -1).astype(np.int32))[0]
             for s in range(3)]
    np.testing.assert_allclose(got[4], sum(a[4] for a in alone),
                               rtol=1e-5, atol=1e-5)


def test_scatter_gradient_in_the_sequence_rows_is_the_row_gather(scatter_net):
    """The cotangent row of each sequence is its node's row (none for -1):
    read off `seq_to_node`, whose bias gradient is the sum of those rows and
    whose kernel gradient is `seq_emb^T` times them."""
    run, _, params = scatter_net
    idx = np.array([3, -1, 5, 3, -1, 0], np.int32)
    cot = np.random.default_rng(2).normal(
        size=(N, NODE_FEATURE_DIM)).astype(np.float32)
    g = jax.grad(lambda p: jnp.sum(run(p, idx)[0] * cot))(params)
    rows = np.where((idx >= 0)[:, None], cot[np.maximum(idx, 0)], 0.0)
    _, seq_emb = run(params, idx)
    np.testing.assert_allclose(g["seq_to_node"]["bias"], rows.sum(0),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g["seq_to_node"]["kernel"],
                               np.asarray(seq_emb).T @ rows,
                               rtol=1e-4, atol=1e-4)


def test_scatter_over_a_batch_under_vmap(scatter_net):
    run, expect, params = scatter_net
    idx = np.array([[3, -1, 5, 3, -1, 0], [-1, -1, -1, -1, -1, -1],
                    [N - 1, 1, 1, 1, 2, -1]], np.int32)
    got, seq_emb = jax.vmap(lambda i: run(params, i))(idx)
    for b in range(len(idx)):
        np.testing.assert_allclose(got[b], expect(params, seq_emb[b], idx[b]),
                                   rtol=1e-5, atol=1e-5)
