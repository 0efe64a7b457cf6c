"""The plain reference against the program at a small size on the CPU, the
benchmark's own weights and optimizer, the required-work counts, and the
lower-precision control of the comparison that decides `correct`."""

import copy
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, rehearse
from chipbench.reference import adamw
from chipbench.reference import nerrfnet as ref
from chipbench.reference import params as rparams
from chipbench.traffic import train_resident as tr
from chipbench.work import nerrfnet as work

ROOT = pathlib.Path(__file__).resolve().parents[2]


def toy_config(**model_over):
    config = json.loads((ROOT / "chipbench/configs/joint-100h.json").read_text())
    config = rehearse.merge(config, rehearse.TOY["config"])
    return rehearse.merge(config, {"train": {"model": model_over}})


@pytest.fixture(scope="module")
def toy_arrays():
    from chipbench import datagen

    return datagen.make_windows(toy_config(), seed=2_200_000_011,
                                num_traces=2, num_windows=8)


def program_loss_and_grad(config, params, batch, key):
    from nerrf_tpu.config import from_dict
    from nerrf_tpu.models.joint import NerrfNet
    from nerrf_tpu.train.loop import TrainConfig, make_loss_fn

    cfg = from_dict(TrainConfig, config["train"])
    loss_fn = make_loss_fn(NerrfNet(cfg.model), cfg)
    (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, key)
    return loss, grads


def test_shapes_in_the_config_files_are_the_programs_constants():
    from nerrf_tpu.data.sequences import SEQ_FEATURE_DIM
    from nerrf_tpu.graph.builder import (AUX_VOCAB, EDGE_FEATURE_DIM,
                                         NODE_FEATURE_DIM)

    for name in ("joint-100h", "joint-dense"):
        config = json.loads(
            (ROOT / f"chipbench/configs/{name}.json").read_text())
        source = json.loads((ROOT / f"configs/{name}.json").read_text())
        assert config["shapes"] == {
            "node_feature_dim": NODE_FEATURE_DIM,
            "edge_feature_dim": EDGE_FEATURE_DIM,
            "seq_feature_dim": SEQ_FEATURE_DIM, "aux_vocab": AUX_VOCAB}
        for block in ("corpus", "dataset", "train"):
            assert config[block] == source[block], (name, block)


def test_own_param_tree_is_the_programs(toy_arrays):
    from nerrf_tpu.config import from_dict
    from nerrf_tpu.models.joint import NerrfNet
    from nerrf_tpu.train.loop import TrainConfig, model_inputs

    config = toy_config()
    cfg = from_dict(TrainConfig, config["train"])
    one = {k: jnp.asarray(v[0]) for k, v in toy_arrays.items()}
    theirs = NerrfNet(cfg.model).init(
        jax.random.PRNGKey(0), *model_inputs(one), deterministic=True)["params"]
    theirs = jax.tree_util.tree_map(lambda x: tuple(x.shape), theirs)
    assert theirs == rparams.param_shapes(config)
    ours = rparams.make_params(config, jax.random.PRNGKey(3))
    assert rparams.count_params(config) == sum(
        x.size for x in jax.tree_util.tree_leaves(ours))
    full = json.loads((ROOT / "chipbench/configs/joint-dense.json").read_text())
    assert rparams.count_params(full) == 4_675_291


@pytest.mark.parametrize("aggregation,lstm_impl", [
    ("segment", "rnn"), ("dense_adj", "fused"), ("fused", "fused")])
def test_reference_matches_program_forward_loss_and_gradients(
        toy_arrays, aggregation, lstm_impl):
    """Loss and every gradient leaf, with the dropout masks the timed step
    draws (two sites, one key shared across the vmapped batch)."""
    config = toy_config(gnn={"aggregation": aggregation},
                        lstm={"impl": lstm_impl})
    params = rparams.make_params(config, jax.random.PRNGKey(1))
    batch = {k: jnp.asarray(v[:6]) for k, v in toy_arrays.items()
             if k != "node_key"}
    key = jax.random.PRNGKey(7)
    loss_p, grads_p = program_loss_and_grad(config, params, batch, key)
    model_cfg = {k: v for k, v in config["train"]["model"].items()}
    fn = ref.make_loss_and_grad(model_cfg, config["train"])
    loss_r, grads_r = ref.loss_and_grad(fn, params, batch, key, 3)
    assert abs(float(loss_p) - float(loss_r)) < 1e-5 * abs(float(loss_r))
    for (path, gp), gr in zip(jax.tree_util.tree_leaves_with_path(grads_p),
                              jax.tree_util.tree_leaves(grads_r)):
        err = float(jnp.linalg.norm(gp - gr))
        assert err <= 1e-4 * float(jnp.linalg.norm(gr)) + 1e-7, (
            jax.tree_util.keystr(path), err)
    # without the masks the two disagree: the comparison sees dropout
    loss_nodrop, _ = ref.loss_and_grad(fn, params, batch, None, 3)
    assert abs(float(loss_nodrop) - float(loss_p)) > 1e-4


def test_reference_optimizer_is_the_trainers():
    """Clip + AdamW + warm-up/cosine against the program's `make_tx`, across
    the warm-up boundary."""
    import optax
    from nerrf_tpu.config import from_dict
    from nerrf_tpu.train.loop import TrainConfig, make_tx

    opt = dict(toy_config()["train"], warmup_steps=2, num_steps=6)
    tx = make_tx(from_dict(TrainConfig, {k: v for k, v in opt.items()}))
    rng = np.random.default_rng(0)
    p_ref = {"a": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    p_prog, s_prog, s_ref = p_ref, tx.init(p_ref), adamw.init(p_ref)
    for k in range(5):
        g = jax.tree_util.tree_map(
            lambda x: jnp.asarray(rng.normal(size=x.shape) * (3.0 if k else 0.1),
                                  jnp.float32), p_ref)
        upd, s_prog = tx.update(g, s_prog, p_prog)
        p_prog = optax.apply_updates(p_prog, upd)
        p_ref, s_ref, _ = adamw.update(p_ref, g, s_ref, opt)
        for x, y in zip(jax.tree_util.tree_leaves(p_prog),
                        jax.tree_util.tree_leaves(p_ref)):
            np.testing.assert_allclose(x, y, rtol=2e-6, atol=1e-7)


def test_required_work_against_hand_counts():
    # one SageBlock at N=1024, E=2048, H=160:
    #   w_msg 2*1024*160*160, w_self 2*1024*320*160, aggregate 2*2*2048*160
    assert work.sage_block_flops(1024, 2048, 160) == (
        52_428_800 + 104_857_600 + 1_310_720)
    # one BiLSTM layer at S=128, T=100, H=256: per direction input and
    # recurrent gates 2*12800*256*1024 each, merge 2*12800*512*256
    assert work.lstm_layer_flops(128, 100, 256, 256) == (
        2 * 2 * 6_710_886_400 + 3_355_443_200)
    for name, gnn_gf in (("joint-100h", 4.44071936),
                         ("joint-dense", 17.76287744)):
        config = json.loads(
            (ROOT / f"chipbench/configs/{name}.json").read_text())
        fwd = work.forward_flops(config)
        assert fwd["gnn_layers"] == round(gnn_gf * 1e9)
        assert fwd["lstm"] == 60_476_686_336
        train = work.train_flops(config)
        assert train["total"] == 3 * sum(fwd.values())
        # dense_adj's 2*N^2*H adjacency product is not required work
        n = config["dataset"]["graph"]["max_nodes"]
        assert fwd["gnn_layers"] < 28 * 2 * n * n * 160 + 28 * 6 * n * 160 * 160


def test_norm_gap_measures_against_the_larger_of_leaf_and_median():
    ref_norms = np.array([1.0, 2.0, 1e-9])
    prog = np.array([1.1, 2.0, 1e-3])
    gap, leaf, mean = compare.norm_gap(prog, ref_norms)
    assert leaf == 0 and gap == pytest.approx(0.1)
    assert mean == pytest.approx((0.1 + 0.0 + 1e-3) / 3)
    # the all-but-zero leaf is held against the median leaf, not itself
    assert compare.norm_gap(np.array([1.0, 2.0, 0.5]), ref_norms)[0] == \
        pytest.approx(0.5)
    keep = compare.moving_leaves(ref_norms)
    assert keep.tolist() == [True, True, False]
    ok, table, rest = compare.verdict(
        {"loss_gap.1": 0.01, "grad_gap": 0.5, "update_gap": 9.0},
        {"loss_gap": 0.05, "grad_gap": 0.2})
    assert not ok and table["loss_gap.1"] == [0.01, 0.05]
    # a number the cell gives no limit is reported, not compared
    assert rest == {"update_gap": 9.0} and "update_gap" not in table
    keep = np.array([True, False, True])
    assert compare.norm_gap(prog, ref_norms, keep)[1] == 0


def test_lower_precision_control_comes_out_not_correct(toy_arrays):
    """The control: the reference put in the program's place and computed
    in fp8 (the step below the configuration's bf16) fails the limits that
    the sound path passes."""
    config = toy_config()
    cell = copy.deepcopy(rehearse.TOY["cell"])
    idx = tr.make_idx_table(5, cell["table_rows"], 8, cell["batch"])
    sound = tr.follow_reference(config, cell, toy_arrays, idx, seed=5)
    bf16 = tr.follow_reference(config, cell, toy_arrays, idx, seed=5,
                               precision="bf16")
    ctrl = tr.follow_reference(config, cell, toy_arrays, idx, seed=5,
                               precision="fp8")
    gaps_bf16 = compare.compare_training(bf16, sound)
    gaps_ctrl = compare.compare_training(ctrl, sound)
    # a limit between the two readings passes bf16 and fails fp8
    for name in ("grad_gap_mean", "update_gap_mean"):
        assert gaps_ctrl[name] > 3 * gaps_bf16[name], name
    limits = {"grad_gap_mean": 2 * gaps_bf16["grad_gap_mean"],
              "update_gap_mean": 2 * gaps_bf16["update_gap_mean"]}
    assert compare.verdict(gaps_bf16, limits)[0]
    assert not compare.verdict(gaps_ctrl, limits)[0]
