import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nerrf_tpu.data import SimConfig, simulate_trace
from nerrf_tpu.data.sequences import SEQ_FEATURE_DIM, build_file_sequences
from nerrf_tpu.graph import GraphConfig
from nerrf_tpu.models import (
    GraphSAGEConfig,
    GraphSAGET,
    ImpactLSTM,
    JointConfig,
    LSTMConfig,
    NerrfNet,
)
from nerrf_tpu.models.graphsage import count_params
from nerrf_tpu.train.data import DatasetConfig, build_dataset
from nerrf_tpu.train.loop import model_inputs


def _trace():
    return simulate_trace(
        SimConfig(duration_sec=90.0, attack=True, attack_start_sec=30.0,
                  num_target_files=5, min_file_bytes=64 * 1024,
                  max_file_bytes=96 * 1024, chunk_bytes=32 * 1024,
                  benign_rate_hz=20.0, seed=1)
    )


def _dataset():
    cfg = DatasetConfig(
        graph=GraphConfig(window_sec=45.0, stride_sec=20.0, max_nodes=64, max_edges=128),
        seq_len=24, max_seqs=32,
    )
    return build_dataset([_trace()], cfg)


def test_graphsage_forward_shapes_and_masking():
    ds = _dataset()
    a = ds.arrays
    model = GraphSAGET(GraphSAGEConfig(hidden=32, num_layers=3))
    args = (a["node_feat"][0], a["node_type"][0], a["node_aux"][0], a["node_mask"][0],
            a["edge_src"][0], a["edge_dst"][0], a["edge_feat"][0], a["edge_mask"][0])
    params = model.init(jax.random.PRNGKey(0), *args)["params"]
    out = model.apply({"params": params}, *args)
    assert out["edge_logit"].shape == (128,)
    assert out["node_logit"].shape == (64,)
    assert out["node_emb"].shape == (64, 32)
    # masked slots forced to large-negative logits
    em = np.asarray(a["edge_mask"][0])
    assert np.all(np.asarray(out["edge_logit"])[~em] == -30.0)
    assert np.isfinite(np.asarray(out["edge_logit"])).all()


def test_graphsage_rev_view_matches_unsorted_path():
    """The src-sorted reverse-aggregation view is a pure reordering: node
    outputs must match the unsorted segment path up to float summation
    order (it exists so both directions declare sorted ids)."""
    from nerrf_tpu.models.graphsage import SageBlock

    ds = _dataset()
    a = ds.arrays
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    e_emb = jnp.asarray(rng.normal(size=(128, 16)), jnp.float32)
    src = a["edge_src"][0]
    dst = a["edge_dst"][0]
    w = jnp.asarray(rng.uniform(0.1, 1.0, 128), jnp.float32)

    block = SageBlock(16, dtype=jnp.float32)
    params = block.init(jax.random.PRNGKey(1), h, e_emb, src, dst, w, 64)["params"]
    plain = block.apply({"params": params}, h, e_emb, src, dst, w, 64)

    order = jnp.argsort(src)
    rev_view = (jnp.take(src, order), jnp.take(dst, order),
                jnp.take(e_emb, order, axis=0), jnp.take(w, order))
    viewed = block.apply({"params": params}, h, e_emb, src, dst, w, 64,
                         rev_view=rev_view)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(viewed),
                               rtol=1e-4, atol=1e-5)


def test_graphsage_param_count_matches_spec():
    """Spec: ~28 layers, ~2M params (architecture.mdx:52)."""
    ds = _dataset()
    a = ds.arrays
    model = GraphSAGET(GraphSAGEConfig())  # full-size config
    args = (a["node_feat"][0], a["node_type"][0], a["node_aux"][0], a["node_mask"][0],
            a["edge_src"][0], a["edge_dst"][0], a["edge_feat"][0], a["edge_mask"][0])
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), *args)
    )["params"]
    n = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    assert 1_800_000 <= n <= 2_600_000, n
    assert GraphSAGEConfig().num_layers == 28


@pytest.mark.parametrize("impl", ["fused", "rnn"])
def test_lstm_padding_invariance(impl):
    """Left-padding must not change the prediction for the same events, on
    either implementation (dropout off: the comparison is of two forwards)."""
    rng = np.random.default_rng(0)
    T, F = 16, SEQ_FEATURE_DIM
    ev = rng.normal(size=(1, 6, F)).astype(np.float32)
    short = np.zeros((1, T, F), np.float32)
    short[:, T - 6:] = ev
    mask_short = np.zeros((1, T), np.bool_)
    mask_short[:, T - 6:] = True
    longpad = np.zeros((1, T + 8, F), np.float32)
    longpad[:, T + 8 - 6:] = ev
    mask_long = np.zeros((1, T + 8), np.bool_)
    mask_long[:, T + 8 - 6:] = True

    model = ImpactLSTM(LSTMConfig(hidden=16, num_layers=1, dropout=0.0,
                                  impl=impl))
    params = model.init(jax.random.PRNGKey(1), jnp.asarray(short), jnp.asarray(mask_short))["params"]
    o1 = model.apply({"params": params}, jnp.asarray(short), jnp.asarray(mask_short))
    o2 = model.apply({"params": params}, jnp.asarray(longpad), jnp.asarray(mask_long))
    np.testing.assert_allclose(
        np.asarray(o1["seq_logit"]), np.asarray(o2["seq_logit"]), rtol=2e-2, atol=2e-2
    )


def test_sequences_builder():
    tr = _trace()
    seqs = build_file_sequences(tr, labels=tr.labels, seq_len=24)
    assert seqs.feat.shape[1:] == (24, SEQ_FEATURE_DIM)
    assert len(seqs) == len(np.unique(seqs.inode))
    # attacked files labelled
    assert seqs.label.max() == 1.0 and seqs.label.min() == 0.0
    # left padding: mask is a suffix
    for i in range(len(seqs)):
        m = seqs.mask[i]
        first = np.argmax(m)
        assert m[first:].all()
    # no feature mass on padded steps
    assert np.abs(seqs.feat[~seqs.mask]).sum() == 0.0


def test_nerrfnet_joint_forward():
    ds = _dataset()
    a = {k: jnp.asarray(v[0]) for k, v in ds.arrays.items()}
    model = NerrfNet(JointConfig().small)
    params = model.init(jax.random.PRNGKey(0), *model_inputs(a))["params"]
    out = model.apply({"params": params}, *model_inputs(a))
    assert set(out) >= {"edge_logit", "node_logit", "seq_logit", "seq_emb", "node_emb"}
    assert out["seq_logit"].shape == (32,)
    assert np.isfinite(np.asarray(out["seq_logit"])).all()


def test_nerrfnet_jit_recompile_free():
    """Different windows, same shapes → one compilation."""
    ds = _dataset()
    model = NerrfNet(JointConfig().small)
    a0 = {k: jnp.asarray(v[0]) for k, v in ds.arrays.items()}
    params = model.init(jax.random.PRNGKey(0), *model_inputs(a0))["params"]
    fwd = jax.jit(lambda p, *args: model.apply({"params": p}, *args))
    fwd(params, *model_inputs(a0))
    n0 = fwd._cache_size()
    for i in range(1, min(4, len(ds))):
        ai = {k: jnp.asarray(v[i]) for k, v in ds.arrays.items()}
        fwd(params, *model_inputs(ai))
    assert fwd._cache_size() == n0 == 1


def test_gnn_aggregation_paths_parity():
    """All three aggregation shapes — dense_adj (one [N,N] matmul per
    layer), fused (one sage_aggregate call per layer) and segment
    (gather + weighted segment-mean) — must compute the same aggregation on
    the same param tree: the bench times dense/fused, training checkpoints
    must load into any of them."""
    import dataclasses

    import jax

    from nerrf_tpu.models.graphsage import GraphSAGEConfig, GraphSAGET

    ds = _dataset()
    gin = ("node_feat", "node_type", "node_aux", "node_mask", "edge_src",
           "edge_dst", "edge_feat", "edge_mask")
    args = tuple(np.asarray(ds.arrays[k][1]) for k in gin)
    cfg_s = GraphSAGEConfig(hidden=32, num_layers=4, dropout=0.0,
                            aggregation="segment")
    gs = GraphSAGET(cfg_s)
    p = gs.init(jax.random.PRNGKey(0), *args)["params"]
    os_ = gs.apply({"params": p}, *args)
    for mode in ("dense_adj", "fused"):
        gm = GraphSAGET(dataclasses.replace(cfg_s, aggregation=mode))
        pm = gm.init(jax.random.PRNGKey(0), *args)["params"]
        assert (jax.tree_util.tree_structure(p)
                == jax.tree_util.tree_structure(pm)), mode
        om = gm.apply({"params": p}, *args)
        for k in ("edge_logit", "node_logit"):
            err = np.max(np.abs(np.asarray(om[k], np.float32)
                                - np.asarray(os_[k], np.float32)))
            assert err < 0.15, (mode, k, err)  # bf16 reorder noise, 4 layers


def test_gnn_fused_mode_gradient_parity():
    """The fused path must TRAIN identically, not just infer: parameter
    gradients through the fused-mode wiring (pre-normalized views over
    `ops.sage_aggregate`) must match the segment oracle in f32."""
    import dataclasses

    import jax

    from nerrf_tpu.models.graphsage import GraphSAGEConfig, GraphSAGET

    ds = _dataset()
    gin = ("node_feat", "node_type", "node_aux", "node_mask", "edge_src",
           "edge_dst", "edge_feat", "edge_mask")
    args = tuple(np.asarray(ds.arrays[k][1]) for k in gin)
    cfg = GraphSAGEConfig(hidden=16, num_layers=2, dropout=0.0,
                          dtype=jnp.float32, aggregation="segment")
    m_s = GraphSAGET(cfg)
    m_f = GraphSAGET(dataclasses.replace(cfg, aggregation="fused"))
    p = m_s.init(jax.random.PRNGKey(1), *args)["params"]

    def loss(model):
        return lambda pp: jnp.sum(
            model.apply({"params": pp}, *args)["node_logit"] ** 2)

    gseg = jax.grad(loss(m_s))(p)
    gfus = jax.grad(loss(m_f))(p)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), gseg, gfus)
    worst = max(jax.tree_util.tree_leaves(errs))
    assert worst < 1e-3, errs


def _ragged_lstm_case(lengths, T=20, F=12, hidden=16, num_layers=2):
    """Left-padded float32 sequences of the given lengths, the `fused`
    model and its parameters with every bias moved to 0.3: a step taken on
    padding then leaves a non-zero state behind, so a direction that walks
    into its padding shows."""
    rng = np.random.default_rng(0)
    B = len(lengths)
    feat = rng.normal(size=(B, T, F)).astype(np.float32)
    mask = np.zeros((B, T), bool)
    for i, n in enumerate(lengths):
        if n:
            mask[i, T - n:] = True  # left-padded: valid suffix
    feat = feat * mask[..., None]

    mf = ImpactLSTM(LSTMConfig(hidden=hidden, num_layers=num_layers,
                               dropout=0.0, dtype=jnp.float32, impl="fused"))
    p = mf.init(jax.random.PRNGKey(0), feat, mask)["params"]
    p = jax.tree_util.tree_map_with_path(
        lambda path, v: v + 0.3 if path[-1].key == "bias" else v, p)
    return mf, p, jnp.asarray(feat), jnp.asarray(mask)


def _weighted_loss(model, mask, seed=1):
    """A scalar of both outputs with fixed random weights, so that every
    sequence and every embedding column has a gradient of its own."""
    rng = np.random.default_rng(seed)
    B = mask.shape[0]
    w = jnp.asarray(rng.normal(size=(B,)).astype(np.float32))
    we = jnp.asarray(
        rng.normal(size=(B, model.cfg.hidden)).astype(np.float32))

    def loss(p, x):
        out = model.apply({"params": p}, x, mask)
        return (out["seq_logit"] * w).sum() + (out["seq_emb"] * we).sum()

    return loss


def test_lstm_impl_paths_parity():
    """fused (one scan, both directions, hoisted input projections, the
    reverse direction a masked scan over the statically reversed input) and
    rnn (flax RNN/OptimizedLSTMCell + seq_lengths) must agree in f32 on
    shared params — outputs AND gradients (parameters and `seq_feat`) —
    over ragged lengths that include 0, 1 and T."""
    import dataclasses

    mf, p, feat, mask = _ragged_lstm_case([20, 13, 7, 2, 1, 0, 19])
    mr = ImpactLSTM(dataclasses.replace(mf.cfg, impl="rnn"))
    pr = mr.init(jax.random.PRNGKey(0), feat, mask)["params"]
    assert (jax.tree_util.tree_structure(p)
            == jax.tree_util.tree_structure(pr))
    of = mf.apply({"params": p}, feat, mask)
    orr = mr.apply({"params": p}, feat, mask)
    for k in ("seq_logit", "seq_emb"):
        err = np.max(np.abs(np.asarray(of[k]) - np.asarray(orr[k])))
        assert err < 1e-4, (k, err)

    gf = jax.grad(_weighted_loss(mf, mask), argnums=(0, 1))(p, feat)
    gr = jax.grad(_weighted_loss(mr, mask), argnums=(0, 1))(p, feat)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), gf, gr)
    worst = max(jax.tree_util.tree_leaves(errs))
    assert worst < 1e-4, errs
    # the gradients are not trivially equal: every leaf is alive, and no
    # input gradient lands on a padded step
    for leaf in jax.tree_util.tree_leaves(gf[0]):
        assert float(jnp.max(jnp.abs(leaf))) > 1e-3
    assert float(jnp.max(jnp.abs(gf[1] * ~mask[..., None]))) == 0.0
    assert float(jnp.max(jnp.abs(gf[1]))) > 1e-3


def test_lstm_fused_program_holds_no_gather_and_no_scatter():
    """The mechanism's witness off the chip: the reverse direction is a
    static reversal plus a mask, so neither the forward nor the gradient
    program of the `fused` path holds a gather or, as a gather's
    transpose, a scatter, whatever the lengths are."""
    mf, p, feat, mask = _ragged_lstm_case([20, 13, 7, 2, 1, 0, 19])
    fwd = jax.jit(lambda p, x: mf.apply({"params": p}, x, mask))
    grad = jax.jit(jax.grad(_weighted_loss(mf, mask), argnums=(0, 1)))
    for program in (fwd, grad):
        text = program.lower(p, feat).as_text()
        assert "stablehlo.while" in text  # the scan is in this text
        assert "gather" not in text and "scatter" not in text, [
            line for line in text.splitlines()
            if "gather" in line or "scatter" in line][:4]


def test_lstm_fused_bilayer_masks_the_reverse_direction_and_takes_no_batch():
    """`_fused_bilayer` alone, prefix-first input: the reverse direction's
    hidden outputs on padded steps are exactly zero (its state is held at
    zero until its first real event, though the biases are not), on real
    steps they are what the sequence cut to its length gives, and an
    unbatched `[T, F]` input gives its row of the batch."""
    mf, p, feat, mask = _ragged_lstm_case(
        [20, 13, 7, 2, 1, 0, 19], F=16, num_layers=1)
    x = jnp.flip(feat, axis=-2)                     # prefix-first
    valid = jnp.flip(mask, axis=-1).astype(jnp.float32)

    class Bilayer(ImpactLSTM):
        @nn.compact
        def __call__(self, x, valid):
            return self._fused_bilayer(x, valid, 0)

    def bilayer(x, valid):
        return Bilayer(mf.cfg).apply({"params": p}, x, valid)

    fwd, bwd = bilayer(x, valid)
    assert fwd.shape == bwd.shape == (7, 20, 16)
    pad = np.asarray(valid) == 0
    assert pad.any() and np.all(np.asarray(bwd)[pad] == 0.0)
    assert np.all(np.abs(np.asarray(bwd)[~pad]) > 0.0)
    # direction 0 is not masked here: its padding follows its real steps
    assert np.all(np.abs(np.asarray(fwd)[pad]) > 0.0)
    for row, n in ((1, 13), (3, 2), (4, 1)):
        _, cut = bilayer(x[row, :n], valid[row, :n])
        np.testing.assert_allclose(np.asarray(bwd[row, :n]), np.asarray(cut),
                                   rtol=0, atol=1e-6)
    f1, b1 = bilayer(x[1], valid[1])                # unbatched [T, F]
    assert f1.shape == b1.shape == (20, 16)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(fwd[1]),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(b1), np.asarray(bwd[1]),
                               rtol=0, atol=1e-6)


_RAGGED = [20, 13, 7, 2, 1, 0, 19]
_FULL = [20] * 7


def _recurrence_case(lengths, lead=(), T=20, H=16, dtype=jnp.float32):
    """Arguments of `lstm.bilstm_recurrence` as `_fused_bilayer` builds them
    (`xs_fwd`, `xs_bwd` ``[T, *lead, B, 4H]``, `keep` whose direction 1
    follows the reversed validity of prefix-first sequences of ``lengths``,
    `wh`, `bias` in float32) and fixed random weights for every element of
    `hs`; ``lengths`` an int: one unbatched sequence."""
    rng = np.random.default_rng(3)
    batch = lead + (() if isinstance(lengths, int) else (len(lengths),))
    n = np.broadcast_to(np.asarray(lengths), batch)
    valid = np.arange(T).reshape((T,) + (1,) * len(batch)) < n   # [T,*batch]
    keep_bwd = valid[::-1].astype(np.float32)[..., None]
    keep = np.stack([np.ones_like(keep_bwd), keep_bwd], axis=1)

    def normal(shape, scale=1.0):
        return (scale * rng.normal(size=shape)).astype(np.float32)

    args = (jnp.asarray(normal((T,) + batch + (4 * H,)), dtype),
            jnp.asarray(normal((T,) + batch + (4 * H,)), dtype),
            jnp.asarray(keep, dtype),
            jnp.asarray(normal((2, H, 4 * H), 0.3)),
            jnp.asarray(normal((2, 4 * H), 0.3)))
    return args, jnp.asarray(normal((T, 2) + batch + (H,)))


def _recurrence_grads(fn, args, w, argnums=(0, 1, 3, 4)):
    def loss(*a):
        return (fn(*a).astype(jnp.float32) * w).sum()

    return jax.grad(loss, argnums=argnums)(*args)


@pytest.mark.parametrize("lengths,lead", [
    (_RAGGED, ()), (_FULL, ()), (13, ()), (_RAGGED, (3,))],
    ids=["ragged", "full", "unbatched", "two_batch_axes"])
def test_lstm_recurrence_rule_matches_autodiff(lengths, lead):
    """The hand-written reverse rule against `jax.grad` of the same
    recurrence without it (the un-decorated function), float32: the same
    `hs` and every cotangent (`xs_fwd`, `xs_bwd`, `wh`, `bias`) to 1e-5,
    whatever the batch rank; `keep` gets zeros."""
    from nerrf_tpu.models import lstm

    args, w = _recurrence_case(lengths, lead)
    np.testing.assert_allclose(np.asarray(lstm.bilstm_recurrence(*args)),
                               np.asarray(lstm._recurrence(*args)),
                               rtol=0, atol=1e-6)
    rule = _recurrence_grads(lstm.bilstm_recurrence, args, w)
    auto = _recurrence_grads(lstm._recurrence, args, w)
    for name, a, b in zip(("xs_fwd", "xs_bwd", "wh", "bias"), rule, auto):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert float(jnp.max(jnp.abs(b))) > 1e-3, name      # alive
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < 1e-5, (name, err)
    dkeep, = _recurrence_grads(lstm.bilstm_recurrence, args, w, argnums=(2,))
    assert float(jnp.max(jnp.abs(dkeep))) == 0.0


@pytest.mark.parametrize("lengths", [_RAGGED, _FULL], ids=["ragged", "full"])
def test_lstm_recurrence_rule_under_grad_of_vmap(lengths):
    """`train/loop.py` takes `jax.grad` of a `jax.vmap` over the windows
    with the parameters closed over: the rule's weight and bias gradients
    then come out a window and are summed by `custom_vjp`'s batching rule.
    They must be what autodiff gives, and what the windows give one by
    one."""
    from nerrf_tpu.models import lstm

    (xs_fwd, xs_bwd, keep, wh, bias), w = _recurrence_case(lengths, (3,))

    def grads(fn):
        def loss(wh, bias):
            hs = jax.vmap(lambda a, b, k: fn(a, b, k, wh, bias),
                          in_axes=(1, 1, 2), out_axes=2)(xs_fwd, xs_bwd, keep)
            return (hs * w).sum()

        return jax.grad(loss, argnums=(0, 1))(wh, bias)

    rule, auto = grads(lstm.bilstm_recurrence), grads(lstm._recurrence)
    one_by_one = [
        _recurrence_grads(lstm.bilstm_recurrence,
                          (xs_fwd[:, i], xs_bwd[:, i], keep[:, :, i], wh,
                           bias), w[:, :, i], argnums=(3, 4))
        for i in range(3)]
    for k, name in enumerate(("wh", "bias")):
        assert rule[k].shape == auto[k].shape, name
        err = float(jnp.max(jnp.abs(rule[k] - auto[k])))
        assert err < 1e-5, (name, err)
        summed = sum(g[k] for g in one_by_one)
        err = float(jnp.max(jnp.abs(rule[k] - summed)))
        assert err < 1e-5, (name, err)


@pytest.mark.parametrize("lengths", [_RAGGED, _FULL], ids=["ragged", "full"])
def test_lstm_recurrence_rule_bf16_within_autodiff_spread(lengths):
    """bf16 loops, float32 parameters, as both cells train: the rule's
    parameter gradients lie no further from the float32 rule's than
    autodiff's bf16 gradients do (the weight gradient is one product
    accumulated in float32 after the loop, where autodiff adds T bf16
    products inside it)."""
    from nerrf_tpu.models import lstm

    args32, w = _recurrence_case(lengths)
    args16 = tuple(a.astype(jnp.bfloat16) if i < 3 else a
                   for i, a in enumerate(args32))
    # the float32 yardstick reads the same (bf16-rounded) inputs
    args32 = tuple(a.astype(jnp.float32) for a in args16)
    want = _recurrence_grads(lstm.bilstm_recurrence, args32, w, (3, 4))
    rule = _recurrence_grads(lstm.bilstm_recurrence, args16, w, (3, 4))
    auto = _recurrence_grads(lstm._recurrence, args16, w, (3, 4))

    def gap(got, ref):
        return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))

    for name, r, a, ref in zip(("wh", "bias"), rule, auto, want):
        assert r.dtype == ref.dtype == jnp.float32, name
        assert gap(r, ref) < 0.05, (name, gap(r, ref))      # bf16, not wrong
        assert gap(r, ref) <= 1.1 * gap(a, ref), (
            name, gap(r, ref), gap(a, ref))


@pytest.mark.parametrize("lengths", [_RAGGED, _FULL], ids=["ragged", "full"])
def test_lstm_fused_gradients_with_and_without_the_rule(lengths, monkeypatch):
    """The whole `fused` model, two layers: parameter and input gradients
    through the rule equal those of autodiff through the un-decorated
    recurrence to 1e-5."""
    from nerrf_tpu.models import lstm

    mf, p, feat, mask = _ragged_lstm_case(lengths)
    grad = jax.grad(_weighted_loss(mf, mask), argnums=(0, 1))
    rule = grad(p, feat)
    monkeypatch.setattr(lstm, "bilstm_recurrence", lstm._recurrence)
    auto = grad(p, feat)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), rule, auto)
    assert max(jax.tree_util.tree_leaves(errs)) < 1e-5, errs
    for leaf in jax.tree_util.tree_leaves(rule[0]):
        assert float(jnp.max(jnp.abs(leaf))) > 1e-3


def _eqns_of(jaxpr):
    """Every equation of a jaxpr, those of the sub-jaxprs that equations
    hold (pjit, custom_vjp_call, a scan's body) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_of(sub)


def _scans_of(fn, *args):
    return [e for e in _eqns_of(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "scan"]


def test_lstm_fused_gradient_program_keeps_h_c_and_the_gates_only():
    """The reverse rule's witness off the chip.  In the gradient program of
    the `fused` route a layer's forward loop stacks at most three tensors
    (`hs`, the cell states, the activated gates as one) where `jax.grad` of
    `lax.scan` stacked thirteen; its backward loop stacks one (`dgates`,
    which IS the cotangent of the hoisted projections) and holds no product
    that yields the weight gradient `[2, H, 4H]`: that is one product after
    the loop.  The forward-only program (serve, evaluation) keeps one
    stacked output a layer and no residual."""
    mf, p, feat, mask = _ragged_lstm_case(_RAGGED)
    H, layers = mf.cfg.hidden, mf.cfg.num_layers
    fwd = jax.jit(lambda p, x: mf.apply({"params": p}, x, mask))
    grad = jax.jit(jax.grad(_weighted_loss(mf, mask), argnums=(0, 1)))

    def stacked(eqn):
        return len(eqn.outvars) - eqn.params["num_carry"]

    scans = _scans_of(grad, p, feat)
    forward = [e for e in scans if not e.params["reverse"]]
    backward = [e for e in scans if e.params["reverse"]]
    assert len(forward) == len(backward) == layers
    assert all(stacked(e) <= 3 for e in forward), [stacked(e) for e in forward]
    assert all(stacked(e) == 1 for e in backward)
    assert all(e.params["num_carry"] == 3 for e in backward)  # dh, dc, c_t
    for eqn in scans:
        products = [e.outvars[0].aval.shape
                    for e in _eqns_of(eqn.params["jaxpr"].jaxpr)
                    if e.primitive.name == "dot_general"]
        assert products, "the recurrent product is in the loop"
        assert not {(2, H, 4 * H), (2, 4 * H, H)} & set(products), products
    text = grad.lower(p, feat).as_text()
    assert text.count("stablehlo.while") == 2 * layers
    assert text.count("dynamic_update_slice") <= 4 * layers
    assert f"tensor<2x{H}x{4 * H}xf32>" in text       # ... and after it

    only = _scans_of(fwd, p, feat)
    assert len(only) == layers and all(stacked(e) == 1 for e in only)
    assert fwd.lower(p, feat).as_text().count(
        "dynamic_update_slice") == layers


def test_dense_adj_aggregate_is_scoped_like_the_fused_route():
    """On `dense_adj` the aggregate is ``adj @ msg``: that product and its
    transpose carry the `sage_aggregate` scope the fused route's op carries
    (one name for the same work in a device trace), and nothing else does —
    the `c_sum` / `dir_bias` terms lie outside it on both routes."""
    from nerrf_tpu.graph import EDGE_FEATURE_DIM, NODE_FEATURE_DIM
    from tests.conftest import scope_paths

    n, e, layers = 16, 32, 2
    rng = np.random.default_rng(0)
    args = (rng.normal(size=(n, NODE_FEATURE_DIM)).astype(np.float32),
            rng.integers(0, 4, n).astype(np.int32),
            rng.integers(0, 8, n).astype(np.int32), np.ones(n, bool),
            rng.integers(0, n, e).astype(np.int32),
            np.sort(rng.integers(0, n, e)).astype(np.int32),
            rng.normal(size=(e, EDGE_FEATURE_DIM)).astype(np.float32),
            np.ones(e, bool))
    model = GraphSAGET(GraphSAGEConfig(hidden=8, num_layers=layers,
                                       dropout=0.0, aggregation="dense_adj"))
    params = model.init(jax.random.PRNGKey(0), *args)["params"]

    def loss(p):
        out = model.apply({"params": p}, *args)
        return out["edge_logit"].sum() + out["node_logit"].sum()

    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    paths = scope_paths(text)
    scoped = [p for p in paths if "sage_aggregate" in p]
    for i in range(layers):
        here = f"/gnn_layer_{i}/block_{i}/sage_aggregate/dot_general"
        assert any(p.startswith("jit(loss)/jvp(") and p.endswith(here)
                   for p in scoped), (i, scoped)
        assert any(p.startswith("jit(loss)/transpose(jvp(")
                   and p.endswith(here) for p in scoped), (i, scoped)
    # the product and the layout change of its transpose, nothing else: no
    # add (c_sum, dir_bias), no multiply, no broadcast
    assert {p.rsplit("/", 1)[1] for p in scoped} <= {"dot_general",
                                                     "transpose"}
    # the per-forward precompute and the encoders have scopes of their own
    # that no roofline group matches
    for name in ("agg_views", "encoders"):
        own = [p for p in paths if f"/{name}/" in p]
        assert own and not any("sage_aggregate" in p or "gnn_layer_" in p
                               for p in own), name


# -- the `auto` rule: a function of the backend and the padded node bucket ----


def _past_crossover():
    from nerrf_tpu.models.graphsage import DENSE_ADJ_MAX_NODES

    return 2 * DENSE_ADJ_MAX_NODES  # the ladder's next power-of-two rung


@pytest.mark.parametrize("nodes, want", [
    (256, "dense_adj"), (1024, "dense_adj"), (2048, "dense_adj"),
    (4096, "dense_adj"), ("past", "segment"), (None, "segment")])
def test_auto_routes_every_shipped_bucket_to_the_matmul_on_a_tpu(
        monkeypatch, nodes, want):
    """Every rung the repo ships (`pipeline._GRAPH_WARMUP_RUNGS`, both
    experiment files) lies under the crossover measured on the chip
    (benchmarks/results/kernel_bench_v5e.json); the first bucket past it,
    and a caller that names no bucket, get `segment`, which compiles and
    runs at any size."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if nodes == "past":
        nodes = _past_crossover()
    assert GraphSAGEConfig().resolved_aggregation(nodes) == want


@pytest.mark.parametrize("nodes", [256, 512, 1024, 2048, 4096])
def test_no_kernel_in_a_nerrfnet_step_at_any_ladder_bucket_on_a_tpu(
        monkeypatch, nodes):
    """`NerrfNet()` as both experiments train it (`auto`, 28 x 160, bf16),
    forward and backward, traced the way a TPU would trace it: every op of
    the program is one the compiler writes.  The aggregate is `adj @ msg`
    (PR 27), the heads' edge-row gathers selection matmuls under
    `gnn_heads/row_gather` (PR 30), and the seq -> node scatter of
    `joint.py` XLA's scatter-add with a gather as its transpose (PR 31)."""
    from nerrf_tpu.graph import EDGE_FEATURE_DIM, NODE_FEATURE_DIM
    from nerrf_tpu.ops import segment
    from tests.conftest import scope_paths

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert segment.active_impls() == {"gather_rows": "xla_selection_matmul"}
    S = jax.ShapeDtypeStruct
    edges, seqs, seq_len = 2 * nodes, 128, 100     # both experiments' dataset
    args = (S((nodes, NODE_FEATURE_DIM), jnp.float32),
            S((nodes,), jnp.int32), S((nodes,), jnp.int32),
            S((nodes,), jnp.bool_), S((edges,), jnp.int32),
            S((edges,), jnp.int32),
            S((edges, EDGE_FEATURE_DIM), jnp.float32),
            S((edges,), jnp.bool_),
            S((seqs, seq_len, SEQ_FEATURE_DIM), jnp.float32),
            S((seqs, seq_len), jnp.bool_), S((seqs,), jnp.int32))
    model = NerrfNet(JointConfig())
    assert model.cfg.gnn.resolved_aggregation(nodes) == "dense_adj"
    params = jax.eval_shape(
        lambda *a: model.init(jax.random.PRNGKey(0), *a)["params"], *args)

    def loss(p, *a):
        out = model.apply({"params": p}, *a)
        return (out["edge_logit"].sum() + out["node_logit"].sum()
                + out["seq_logit"].sum())

    text = jax.jit(jax.grad(loss)).lower(params, *args).as_text(
        debug_info=True)
    assert "custom_call" not in text and "pallas" not in text
    paths = set(scope_paths(text))
    gathers = [p for p in paths if "/gnn_heads/row_gather/" in p]
    for stage in ("jit(loss)/jvp(", "jit(loss)/transpose(jvp("):
        own = [p for p in gathers if p.startswith(stage)]
        assert sum(p.endswith("/dot_general") for p in own) >= 1, (stage,
                                                                   gathers)
    # the selection matmuls and what builds their one-hots, nothing else:
    # no XLA gather or scatter either
    assert not [p for p in gathers
                if p.rsplit("/", 1)[1] in ("gather", "scatter-add",
                                           "scatter_add")]
    # joint.py's scatter sits beside the modules, not under one
    outside = [p for p in paths
               if "/gnn/" not in p and "/lstm/" not in p]
    assert any(p.startswith("jit(loss)/jvp(")
               and p.rsplit("/", 1)[1] in ("scatter-add", "scatter_add")
               for p in outside), outside


@pytest.mark.parametrize("nodes", [256, 4096, "past"])
def test_auto_is_segment_off_a_tpu(monkeypatch, nodes):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    if nodes == "past":
        nodes = _past_crossover()
    assert GraphSAGEConfig().resolved_aggregation(nodes) == "segment"


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_routing_table_and_explicit_aggregation_outrank_the_auto_rule(
        monkeypatch, backend):
    """Precedence is unchanged: an explicit `aggregation` first, then the
    `nerrf tune` table (smallest covering rung), then the constant."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    table = ((1024, "segment"), (4096, "fused"))
    tuned = GraphSAGEConfig(routing=table)
    assert tuned.resolved_aggregation(512) == "segment"
    assert tuned.resolved_aggregation(4096) == "fused"
    past_table = {"tpu": "dense_adj", "cpu": "segment"}[backend]
    assert GraphSAGEConfig(routing=((1024, "segment"),)
                           ).resolved_aggregation(2048) == past_table
    for mode in ("fused", "dense_adj", "segment"):
        cfg = GraphSAGEConfig(aggregation=mode, routing=table)
        for n in (256, 4096, _past_crossover(), None):
            assert cfg.resolved_aggregation(n) == mode


@pytest.mark.parametrize("n", [256, 512, 1024, 2048, 4096])
def test_dense_adj_matches_segment_at_every_ladder_bucket(n):
    """The route `auto` takes on a TPU at every bucket of the ladder (e =
    2n) against the portable oracle (XLA on this CPU), in f32, with about
    half of the edge slots masked as a padded window has them: the logits,
    and the loss's gradient with respect to every parameter and to the node
    features every layer's `msg` is made from."""
    import dataclasses

    from nerrf_tpu.graph import EDGE_FEATURE_DIM, NODE_FEATURE_DIM

    e = 2 * n
    real = n * 3020 // 4096      # joint-dense's mean share of real nodes
    rng = np.random.default_rng(27)
    edge_feat = rng.normal(size=(e, EDGE_FEATURE_DIM)).astype(np.float32)
    edge_feat[:, 12] = rng.uniform(0.0, 1.0, e)  # the causality weight
    edge_mask = np.arange(e) < e // 2 + 37
    node_mask = np.arange(n) < real
    args = (rng.normal(size=(n, NODE_FEATURE_DIM)).astype(np.float32),
            rng.integers(0, 4, n).astype(np.int32),
            rng.integers(0, 8, n).astype(np.int32), node_mask,
            rng.integers(0, real, e).astype(np.int32),
            np.sort(rng.integers(0, real, e)).astype(np.int32),
            edge_feat, edge_mask)
    cfg = GraphSAGEConfig(hidden=16, num_layers=2, dropout=0.0,
                          dtype=jnp.float32, aggregation="segment")
    m_s = GraphSAGET(cfg)
    m_d = GraphSAGET(dataclasses.replace(cfg, aggregation="dense_adj"))
    params = m_s.init(jax.random.PRNGKey(2), *args)["params"]

    def run(model):
        def loss(p, node_feat):
            out = model.apply({"params": p}, node_feat, *args[1:])
            live = (jnp.sum(jnp.where(node_mask, out["node_logit"], 0.0) ** 2)
                    + jnp.sum(jnp.where(edge_mask, out["edge_logit"], 0.0)
                              ** 2))
            return live / n, out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(params, args[0])

    (_, out_s), grads_s = run(m_s)
    (_, out_d), grads_d = run(m_d)
    for k in ("edge_logit", "node_logit"):
        err = np.max(np.abs(np.asarray(out_d[k]) - np.asarray(out_s[k])))
        assert err < 1e-3, (k, err)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), grads_s, grads_d)
    assert max(jax.tree_util.tree_leaves(errs)) < 1e-3, errs
    norms = [float(jnp.max(jnp.abs(g)))
             for g in jax.tree_util.tree_leaves(grads_s)]
    assert min(norms) > 0.0  # every leaf and the features get a gradient
