"""The stream encoder's ``dsa_moe`` kind outside the benchmark: a saved
model of this kind reloads, an edit to its fields costs a fresh compile,
the experiment trains through the normal path, and the attention's fused
route (`ops/dsa.py::attention_route`) is the XLA route."""

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
    assert stream_key_extra(other, 32) != stream_key_extra(TOY, 32)


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


# --------------------------------------------------------------------------
# the chosen-set attention's two routes (`ops/dsa.py::attention_route`)
# --------------------------------------------------------------------------

@pytest.fixture
def small_tiles(monkeypatch):
    """Blocks of 64 queries, key spans of 256, the fused kernels' tiles 128
    x 128: a 512-token toy then has every kind of tile."""
    from nerrf_tpu.ops import dsa

    for name, value in (("QUERY_BLOCK", 64), ("KEY_SPAN", 256),
                        ("FLASH_BLOCK_Q", 128), ("FLASH_BLOCK_K", 128)):
        monkeypatch.setattr(dsa, name, value)


@pytest.fixture
def tpu_routes(small_tiles, monkeypatch):
    """Trace what a TPU traces, on the CPU: the backend says "tpu"."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("backend, t, hq, hk, d, route", [
    ("tpu", 8192, 32, 4, 128, "pallas_flash"),   # the published widths
    ("tpu", 2048, 8, 8, 256, "pallas_flash"),
    ("cpu", 8192, 32, 4, 128, "xla_blocked"),
    ("gpu", 8192, 32, 4, 128, "xla_blocked"),
    ("tpu", 8192, 32, 4, 64, "xla_blocked"),     # not whole lanes
    ("tpu", 8192, 30, 4, 128, "xla_blocked"),    # no whole groups
    ("tpu", 8192 + 256, 32, 4, 128, "xla_blocked"),   # not whole spans
    ("tpu", 16384, 32, 4, 128, "xla_blocked"),   # dk, dv rows do not fit
    ("tpu", 8192, 256, 4, 128, "xla_blocked"),   # a block's heads do not fit
    ("tpu", 32, 2, 1, 16, "xla_blocked"),        # the toy of this file
])
def test_attention_route_is_a_function_of_backend_and_shapes(
        monkeypatch, backend, t, hq, hk, d, route):
    from nerrf_tpu.ops import dsa

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert dsa.attention_route(t, hq, hk, d) == route


TOPK = 96


def _packed_case(dtype, t=512, hq=4, hk=2, d=128, j=2, e=8):
    """Grouped heads (two query heads a key-value head), documents of 300
    and 180 tokens and 32 of padding, 96 keys a query: the second span's
    blocks rank their keys, a block's key tiles lie above its diagonal
    (skipped), on it, and below it with a document's edge inside."""
    rng = np.random.default_rng(0)
    draw = lambda *s, dt=dtype: jnp.asarray(rng.normal(size=s), dt)
    seg = np.repeat([1, 2, 0], [300, 180, t - 480]).astype(np.int32)
    return (draw(t, hq, d), draw(t, hk, d), draw(t, hk, d),
            draw(t, j, e, dt=jnp.float32), draw(t, e, dt=jnp.float32),
            draw(t, j, dt=jnp.float32), jnp.asarray(seg),
            draw(t, hq, d, dt=jnp.float32))


def _core(q, k, v, qi, ki, wi, seg, w):
    """-> ((loss, (o, kl, pairs)), the six gradients)."""
    from nerrf_tpu.ops import dsa

    def loss(q, k, v, qi, ki, wi):
        o, kl, pairs = dsa.sparse_attention(q, k, v, qi, ki, wi, seg,
                                            topk=TOPK)
        return jnp.sum(o.astype(jnp.float32) * w) + 0.5 * kl, (o, kl, pairs)
    return jax.value_and_grad(loss, argnums=tuple(range(6)), has_aux=True)(
        q, k, v, qi, ki, wi)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_fused_route_is_the_xla_route(small_tiles, monkeypatch, dtype):
    """``o``, ``kl``, ``pairs`` and all six gradients: in float32 to the
    last digits, in bfloat16 to the roundings both routes make; the chosen
    set is the same set, being the same code.  The fused kernels run in
    Pallas' interpreter."""
    from jax.experimental.pallas import tpu as pltpu
    from nerrf_tpu.ops import dsa

    case = _packed_case(dtype)
    assert dsa.attention_route(512, 4, 2, 128) == "xla_blocked"
    want = _core(*case)
    chose = dsa.selection(*case[3:7], topk=TOPK)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dsa.attention_route(512, 4, 2, 128) == "pallas_flash"
    with pltpu.force_tpu_interpret_mode():
        got = _core(*case)
        np.testing.assert_array_equal(dsa.selection(*case[3:7], topk=TOPK),
                                      chose)
    assert int(got[0][1][2]) == int(want[0][1][2]) == int(
        (chose & (np.asarray(case[6]) > 0)[:, None]).sum())
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
        else:
            assert np.abs(a - b).mean() <= 2e-3 * np.abs(b).mean()


def test_a_remat_that_keeps_the_residuals_runs_each_forward_kernel_once(
        tpu_routes):
    """A layer under `save_only_these_names(dsa.SAVED)`: its gradient holds
    the forward scan once (one forward kernel for each of its two key
    lengths) and one backward kernel; under a policy that keeps nothing the
    forward scan is there twice."""
    from test_stream_latent import _kernel_calls

    from nerrf_tpu.ops import dsa

    q, k, v, qi, ki, wi, seg, w = _packed_case(jnp.float32)

    def layer(q, k, v, qi, ki, wi):
        o, kl, _ = dsa.sparse_attention(q, k, v, qi, ki, wi, seg, topk=TOPK)
        return jnp.sum(jnp.tanh(o) * w) + kl

    def calls(policy):
        jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(
            layer, policy=policy), argnums=tuple(range(6))))(
                q, k, v, qi, ki, wi).jaxpr
        return (_kernel_calls(jaxpr, "dsa_flash_fwd"),
                _kernel_calls(jaxpr, "dsa_flash_bwd"))

    keep = jax.checkpoint_policies.save_only_these_names(dsa.SAVED)
    assert calls(keep) == (2, 1)
    assert calls(jax.checkpoint_policies.nothing_saveable) == (4, 1)


def test_the_route_rides_the_sparse_steps_key(monkeypatch):
    """`stream_kernel_path` and the AOT key of a ``dsa_moe`` stack carry
    ``dsa_attention``: another route where the backend traces another, the
    same configuration text."""
    from nerrf_tpu.train.stream import stream_kernel_path, stream_key_extra

    wide = dataclasses.replace(TOY, num_heads=4, num_kv_heads=2,
                               head_dim=128)
    keys = {}
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        keys[backend] = stream_key_extra(wide, 8192)
        # the toy's widths take the XLA route on any backend
        assert stream_kernel_path(TOY, 8192) == {
            "dsa_attention": "xla_blocked"}
    assert keys["cpu"]["dsa_attention"] == "xla_blocked"
    assert keys["tpu"]["dsa_attention"] == "pallas_flash"
    assert keys["cpu"]["stream_cfg"] == keys["tpu"]["stream_cfg"]
    assert set(keys["tpu"]) == {"stream_cfg", "dsa_attention"}
    with pytest.raises(ValueError):
        stream_key_extra(wide)


def test_train_stream_names_the_attention_route(monkeypatch):
    """`train_stream`'s start-up lines say which route served the run."""
    from nerrf_tpu.ops import dsa, moe
    from nerrf_tpu.train.loop import TrainConfig
    from nerrf_tpu.train.stream import train_stream

    monkeypatch.setattr(dsa, "QUERY_BLOCK", 32)
    monkeypatch.setattr(dsa, "KEY_SPAN", 32)
    monkeypatch.setattr(moe, "TILE", 8)
    tokens = np.zeros((2, 32), np.int32)
    lines = []
    train_stream({"tokens": tokens, "segments": np.ones_like(tokens)}, TOY,
                 TrainConfig(batch_size=2, num_steps=1, warmup_steps=1),
                 log=lines.append)
    assert "kernel_path: dsa_attention: xla_blocked" in lines
