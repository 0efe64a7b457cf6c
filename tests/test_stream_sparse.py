"""The stream encoder's ``dsa_moe`` kind outside the benchmark: a saved
model of this kind reloads, an edit to its fields costs a fresh compile,
and the experiment trains through the normal path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nerrf_tpu.models.stream import StreamConfig, StreamNet

TOY = StreamConfig(
    dim=32, num_heads=2, num_kv_heads=1, head_dim=16, num_layers=2,
    kinds=("dsa_moe",) * 2, vocab_size=64, dropout=0.0, dtype=jnp.float32,
    rope_theta=1e4, index_heads=2, index_head_dim=8, index_topk=16,
    num_experts=8, experts_per_token=2, expert_dim=16, first_expert=2,
    held_experts=4, tie_head=False)


def test_stream_checkpoint_carries_the_sparse_fields(tmp_path):
    from nerrf_tpu.train.checkpoint import (load_stream_checkpoint,
                                            save_stream_checkpoint)

    tok = jnp.zeros((1, 32), jnp.int32)
    params = StreamNet(TOY).init(jax.random.PRNGKey(0), tok,
                                 jnp.ones_like(tok))["params"]
    save_stream_checkpoint(tmp_path / "m", params, TOY)
    got, cfg, _ = load_stream_checkpoint(tmp_path / "m")
    assert cfg == TOY and cfg.stack == ("dsa_moe",) * 2
    assert (cfg.first_expert, cfg.held_experts, cfg.index_topk,
            cfg.tie_head) == (2, 4, 16, False)
    assert jax.tree_util.tree_map(np.shape, got) == \
        jax.tree_util.tree_map(np.shape, jax.device_get(params))
    assert "lm_head" in got and "w_gate" in got["layer_1"]


@pytest.mark.parametrize("field, value", [
    ("index_topk", 8), ("first_expert", 4), ("held_experts", 2),
    ("experts_per_token", 4), ("rope_theta", 1e6), ("tie_head", True),
    ("index_loss_weight", 0.5)])
def test_an_edit_to_a_sparse_field_changes_the_aot_key(field, value):
    from nerrf_tpu.train.stream import stream_key_extra

    other = dataclasses.replace(TOY, **{field: value})
    assert stream_key_extra(other) != stream_key_extra(TOY)


def test_the_experiment_trains_through_the_normal_path(monkeypatch):
    """`train_stream` (what `train.run` calls for a stream experiment) on a
    toy of the kind: the loss falls, and the loop's syncs feed the
    registry."""
    from nerrf_tpu.observability import DEFAULT_REGISTRY as reg
    from nerrf_tpu.ops import dsa, moe
    from nerrf_tpu.train.loop import TrainConfig
    from nerrf_tpu.train.stream import train_stream

    monkeypatch.setattr(dsa, "QUERY_BLOCK", 32)
    monkeypatch.setattr(dsa, "KEY_SPAN", 32)
    monkeypatch.setattr(moe, "TILE", 8)
    rng = np.random.default_rng(0)
    tokens = np.tile(rng.integers(0, 64, (1, 16)), (4, 4)).astype(np.int32)
    arrays = {"tokens": tokens, "segments": np.ones_like(tokens)}
    before = reg.value("moe_assignments_total", labels={"held": "true"})
    cfg = TrainConfig(batch_size=2, num_steps=30, learning_rate=3e-3,
                      warmup_steps=2, weight_decay=0.0, eval_every=10)
    res = train_stream(arrays, TOY, cfg, log=lambda _: None)
    assert res.history[-1]["loss"] < 0.7 * res.history[0]["loss"]
    held = reg.value("moe_assignments_total", labels={"held": "true"}) - before
    # 30 steps x 2 sequences x 64 tokens x 2 experts x 2 layers, 4 of 8 held
    assert 0.2 < held / (30 * 2 * 64 * 2 * 2) < 0.8
    assert 0 < reg.value("dsa_selected_share") < 1
