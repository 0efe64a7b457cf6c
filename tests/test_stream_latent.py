"""The stream encoder's latent-attention kinds (``mla_dense``, ``mla_moe``)
and its multi-token-prediction module outside the benchmark: a saved model
reloads, an edit to their fields costs a fresh compile, the experiment trains
through the normal path with the correction bias held fixed, and the two
other stream stacks' steps lower to the programs they lowered to before the
kinds came."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nerrf_tpu.models.stream import StreamConfig, StreamNet

TOY = StreamConfig(
    dim=32, num_heads=2, num_layers=3,
    kinds=("mla_dense", "mla_moe", "mla_moe"), vocab_size=64, dropout=0.0,
    dtype=jnp.float32, mlp_dim=64, rope_theta=1e4, q_lora_rank=16,
    kv_lora_rank=12, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=10,
    num_experts=8, experts_per_token=2, expert_dim=16, first_expert=2,
    held_experts=4, router_scale=1.8, shared_dim=16, rms_eps=1e-5,
    tie_head=False, mtp_layers=1, mtp_loss_weight=0.3)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    from nerrf_tpu.ops import mla, moe

    monkeypatch.setattr(mla, "QUERY_BLOCK", 32)
    monkeypatch.setattr(mla, "KEY_SPAN", 32)
    monkeypatch.setattr(moe, "TILE", 8)


def test_stream_checkpoint_carries_the_latent_fields(tmp_path):
    from nerrf_tpu.train.checkpoint import (load_stream_checkpoint,
                                            save_stream_checkpoint)

    tok = jnp.zeros((1, 32), jnp.int32)
    params = StreamNet(TOY).init(jax.random.PRNGKey(0), tok,
                                 jnp.ones_like(tok))["params"]
    save_stream_checkpoint(tmp_path / "m", params, TOY)
    got, cfg, _ = load_stream_checkpoint(tmp_path / "m")
    assert cfg == TOY and cfg.stack == ("mla_dense", "mla_moe", "mla_moe")
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_dim,
            cfg.mtp_layers, cfg.routed_layers) == (16, 12, 4, 1, 3)
    assert jax.tree_util.tree_map(np.shape, got) == \
        jax.tree_util.tree_map(np.shape, jax.device_get(params))
    # a dense layer, an expert layer with its buffer, the module's parts
    assert "gate" in got["layer_0"] and "router" not in got["layer_0"]
    assert got["layer_1"]["router_bias"].shape == (8,)
    assert {"mtp_block", "mtp_eh_proj", "mtp_enorm", "mtp_hnorm",
            "mtp_norm", "lm_head"} <= set(got)
    assert got["mtp_eh_proj"]["kernel"].shape == (64, 32)


@pytest.mark.parametrize("field, value", [
    ("q_lora_rank", 8), ("kv_lora_rank", 8), ("qk_nope_dim", 4),
    ("qk_rope_dim", 8), ("v_head_dim", 8), ("router_scale", 2.5),
    ("shared_dim", 8), ("mtp_layers", 0), ("mtp_loss_weight", 0.1),
    ("kinds", ("mla_dense", "mla_dense", "mla_moe"))])
def test_an_edit_to_a_latent_field_changes_the_aot_key(field, value):
    from nerrf_tpu.train.stream import stream_key_extra

    other = dataclasses.replace(TOY, **{field: value})
    assert stream_key_extra(other) != stream_key_extra(TOY)


def test_the_experiment_trains_through_the_normal_path():
    """`train_stream` (what `train.run` calls for a stream experiment) on a
    toy of the kinds: the loss falls, the loop's syncs feed the registry,
    and no step moves a correction bias."""
    from nerrf_tpu.observability import DEFAULT_REGISTRY as reg
    from nerrf_tpu.train.loop import TrainConfig
    from nerrf_tpu.train.stream import init_stream_state, train_stream

    rng = np.random.default_rng(0)
    tokens = np.tile(rng.integers(0, 64, (1, 16)), (4, 4)).astype(np.int32)
    arrays = {"tokens": tokens, "segments": np.ones_like(tokens)}
    held = reg.value("moe_assignments_total", labels={"held": "true"})
    targets = reg.value("mtp_targets_total")
    cfg = TrainConfig(batch_size=2, num_steps=30, learning_rate=3e-3,
                      warmup_steps=2, weight_decay=0.1, eval_every=10)
    res = train_stream(arrays, TOY, cfg, log=lambda _: None)
    assert res.history[-1]["loss"] < 0.7 * res.history[0]["loss"]
    held = reg.value("moe_assignments_total", labels={"held": "true"}) - held
    # 30 steps x 2 sequences x 64 tokens x 2 experts x 3 routed blocks
    assert 0.2 < held / (30 * 2 * 64 * 2 * 3) < 0.8
    # every position but a document's last two carries an MTP target
    assert reg.value("mtp_targets_total") - targets == 30 * 2 * 62
    assert 0 < reg.value("stream_mtp_loss_share") < 0.5
    # the same key makes the same first state: the biases did not move,
    # their routers did
    key = jax.random.split(jax.random.PRNGKey(cfg.seed))[1]
    first = init_stream_state(StreamNet(TOY), cfg, {
        k: v[:2] for k, v in arrays.items()}, key).params
    for name in ("layer_1", "layer_2", "mtp_block"):
        np.testing.assert_array_equal(
            np.asarray(res.state.params[name]["router_bias"]),
            np.asarray(first[name]["router_bias"]))
        assert float(jnp.abs(first[name]["router_bias"]).max()) > 0
        assert not np.array_equal(
            np.asarray(res.state.params[name]["router"]["kernel"]),
            np.asarray(first[name]["router"]["kernel"]))


def test_a_stack_may_end_in_a_dense_latent_layer_without_the_module():
    cfg = dataclasses.replace(TOY, num_layers=1, kinds=("mla_dense",),
                              mtp_layers=0)
    tok = jnp.zeros((1, 32), jnp.int32)
    model = StreamNet(cfg)
    params = model.init(jax.random.PRNGKey(0), tok, jnp.ones_like(tok))
    out = model.apply(params, tok, jnp.ones_like(tok))
    assert set(out) == {"hidden"} and "final_norm" in params["params"]


# the toy steps of the two other stream stacks as they lowered at the parent
# of the PR that added the latent kinds (PR 35), jax 0.9.0.  A change to
# `models/stream.py`, `train/stream.py`, `ops/moe.py` or `ops/dsa.py` that
# moves either has changed what those cells' programs compute or how: find
# out which before recording a new digest
KEYE = StreamConfig(
    dim=32, num_heads=2, num_kv_heads=1, head_dim=16, num_layers=2,
    kinds=("dsa_moe",) * 2, vocab_size=64, dropout=0.0, dtype=jnp.bfloat16,
    rope_theta=1e4, index_heads=2, index_head_dim=8, index_topk=16,
    num_experts=8, experts_per_token=2, expert_dim=16, first_expert=2,
    held_experts=4, tie_head=False)
PHI4 = StreamConfig(
    dim=32, num_heads=4, num_kv_heads=2, head_dim=8, mlp_dim=64, window=16,
    d_state=4, d_conv=4, expand=2, dt_rank=4, num_layers=6,
    kinds=("mamba", "swa", "mamba", "full", "gmu", "cross"),
    published_layers=(0, 1, 16, 17, 18, 19), vocab_size=64, dropout=0.0,
    dtype=jnp.bfloat16)
RECORDED = {
    "keye": "ceec57eb367b3f01a1290539e172f6d39230570cd34582b577d6fcdb4d7c657c",
    "phi4": "46eaf9999218d977f91ea5f7b9c3a26d3f62a55bbb99196ab0da8b2c7ecee697",
}


@pytest.mark.parametrize("name, cfg", [("keye", KEYE), ("phi4", PHI4)])
def test_the_other_stream_stacks_steps_lower_as_before(monkeypatch, name,
                                                       cfg):
    """Loss + gradient of the toy step, as StableHLO: the same text as
    before the latent kinds, so neither cell's program changed."""
    from nerrf_tpu.ops import dsa, moe
    from nerrf_tpu.train.stream import make_stream_loss_fn

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were recorded under jax 0.9.0")
    # the digests were taken at the ops' own block sizes
    monkeypatch.undo()
    model = StreamNet(cfg)
    tok = jnp.zeros((1, 64), jnp.int32)
    seg = jnp.ones((1, 64), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tok, seg)["params"])
    text = jax.jit(jax.value_and_grad(
        make_stream_loss_fn(model), has_aux=True)).lower(
            params, {"tokens": tok, "segments": seg},
            jax.random.PRNGKey(1)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == RECORDED[name]
    # and the optimizer is the trainer's own: no masked wrapper
    from nerrf_tpu.train.loop import TrainConfig, make_tx
    from nerrf_tpu.train.stream import make_stream_tx

    w = {"w": jnp.ones(3)}
    assert jax.tree_util.tree_structure(
        make_stream_tx(TrainConfig(), cfg).init(w)) == \
        jax.tree_util.tree_structure(make_tx(TrainConfig()).init(w))
