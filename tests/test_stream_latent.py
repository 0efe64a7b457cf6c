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
    assert stream_key_extra(other, 32) != stream_key_extra(TOY, 32)


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


# --------------------------------------------------------------------------
# the latent core's two routes (`ops/mla.py::attention_route`)
# --------------------------------------------------------------------------

@pytest.fixture
def tpu_routes(monkeypatch):
    """Trace what a TPU traces, on the CPU: the backend says "tpu"; the
    fused kernels' tiles are 128 x 128."""
    from nerrf_tpu.ops import mla

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mla, "FLASH_BLOCK_Q", 128)
    monkeypatch.setattr(mla, "FLASH_BLOCK_K", 128)


@pytest.fixture
def fused_route(tpu_routes):
    """... and run it: the kernels in Pallas' interpreter."""
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.mark.parametrize("backend, t, d, dv, route", [
    ("tpu", 8192, 256, 256, "pallas_flash"),
    ("tpu", 1024, 128, 128, "pallas_flash"),
    ("cpu", 8192, 256, 256, "xla_blocked"),
    ("gpu", 8192, 256, 256, "xla_blocked"),
    ("tpu", 8192, 192, 128, "xla_blocked"),    # DeepSeek-V3's widths
    ("tpu", 8192, 192, 192, "xla_blocked"),    # not whole lanes
    ("tpu", 8192 + 256, 256, 256, "xla_blocked"),   # not whole tiles
    ("tpu", 32768, 256, 256, "xla_blocked"),   # a head's dq rows do not fit
    ("tpu", 64, 12, 10, "xla_blocked"),        # the toy of this file
])
def test_attention_route_is_a_function_of_backend_and_shapes(
        monkeypatch, backend, t, d, dv, route):
    from nerrf_tpu.ops import mla

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert mla.attention_route(t, d, dv) == route


def _packed_case(dtype, t=512, heads=2, nope=64, rot=64, dv=128):
    """Documents of 300, 100 and 80 tokens and 32 of padding: with tiles of
    128 the first document fills a tile below the diagonal (no mask), and
    there are tiles above the diagonal (skipped), on it, and below it with
    a document's edge inside."""
    rng = np.random.default_rng(0)
    draw = lambda *s: jnp.asarray(rng.normal(size=s), dtype)
    seg = np.repeat([1, 2, 3, 0], [300, 100, 80, t - 480]).astype(np.int32)
    return (draw(t, heads, nope + rot), draw(t, rot),
            draw(t, heads, nope + dv), jnp.asarray(seg),
            jnp.asarray(rng.normal(size=(t, heads, dv)), jnp.float32))


def _core_loss(attend, nope=64):
    from nerrf_tpu.ops import dsa, mla

    def loss(q, k_r, kv, seg, w):
        o = attend(*mla.assemble(q, k_r, kv, dsa.doc_positions(seg),
                                 nope=nope, theta=1e4), seg)
        return jnp.sum(o.astype(jnp.float32) * w)
    return jax.value_and_grad(loss, argnums=(0, 1, 2))


def _brute_force(q, k, v, seg):
    t = q.shape[0]
    logits = jnp.einsum("qhd,khd->hqk", q, k) * q.shape[-1] ** -0.5
    valid = (seg[:, None] == seg[None, :]) & (
        jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])
    prob = jax.nn.softmax(jnp.where(valid, logits, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", prob, v)


def test_the_fused_route_is_the_xla_route_and_brute_force(fused_route):
    """Forward and the three gradients (``q``; ``k_r``, summed over the
    heads it was broadcast to; ``kv``) in float32 against brute force, and
    in bfloat16 against the XLA route, whose roundings the kernels keep."""
    from nerrf_tpu.ops import mla

    blocked = lambda *a: mla._attention(
        *(jnp.swapaxes(x, 0, 1) for x in a[:3]), a[3], 128, 128
    ).swapaxes(0, 1)
    case = _packed_case(jnp.float32)
    assert mla.attention_route(512, 128, 128) == "pallas_flash"
    got = _core_loss(mla.attention)(*case)
    for other in (_brute_force, blocked):
        want = _core_loss(other)(*case)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    case = _packed_case(jnp.bfloat16)
    got, want = _core_loss(mla.attention)(*case), _core_loss(blocked)(*case)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).mean() < 2e-3 * np.abs(b).mean()


def _kernel_calls(jaxpr, name):
    """How many `pallas_call`s named ``name`` a jaxpr holds, in it and in
    every jaxpr its equations carry."""
    from jax.extend import core as jex

    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and name in str(
                eqn.params.get("name_and_src_info", eqn.params.get("name"))):
            count += 1
        for sub in jax.tree_util.tree_leaves(
                eqn.params, is_leaf=lambda x: isinstance(
                    x, (jex.Jaxpr, jex.ClosedJaxpr))):
            if isinstance(sub, jex.ClosedJaxpr):
                sub = sub.jaxpr
            if isinstance(sub, jex.Jaxpr):
                count += _kernel_calls(sub, name)
    return count


def test_a_remat_that_keeps_the_residuals_runs_the_forward_kernel_once(
        tpu_routes):
    """A layer under `save_only_these_names(mla.SAVED)`: its gradient holds
    one forward kernel (the primal pass's) and one backward kernel; under a
    policy that keeps nothing the forward kernel runs twice."""
    from nerrf_tpu.ops import dsa, mla

    q, k_r, kv, seg, w = _packed_case(jnp.float32)

    def layer(q, k_r, kv):
        o = mla.attention(*mla.assemble(q, k_r, kv, dsa.doc_positions(seg),
                                        nope=64, theta=1e4), seg)
        return jnp.sum(jnp.tanh(o) * w)

    def calls(policy):
        jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(
            layer, policy=policy), argnums=(0, 1, 2)))(q, k_r, kv).jaxpr
        return (_kernel_calls(jaxpr, "mla_flash_fwd"),
                _kernel_calls(jaxpr, "mla_flash_bwd"))

    keep = jax.checkpoint_policies.save_only_these_names(mla.SAVED)
    assert calls(keep) == (1, 1)
    assert calls(jax.checkpoint_policies.nothing_saveable) == (2, 1)


def test_the_route_rides_a_latent_steps_key_and_no_other(monkeypatch):
    """`stream_key_extra` of a latent configuration differs between a
    backend that traces the fused route and one that traces the XLA route;
    the two other stream stacks' key material carries no latent route."""
    from nerrf_tpu.train.stream import stream_kernel_path, stream_key_extra

    wide = dataclasses.replace(TOY, qk_nope_dim=64, qk_rope_dim=64,
                               v_head_dim=128)
    keys = {}
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        keys[backend] = stream_key_extra(wide, 8192)
        assert stream_key_extra(PHI4) == {"stream_cfg": repr(PHI4)}
        assert stream_key_extra(PHI4, 8192) == stream_key_extra(PHI4)
        # the chosen-set stack's key names ITS core's route, not this one's
        assert stream_key_extra(KEYE, 8192) == {
            "stream_cfg": repr(KEYE), "dsa_attention": "xla_blocked"}
        # the toy's widths take the XLA route on any backend
        assert stream_kernel_path(TOY, 8192) == {
            "mla_attention": "xla_blocked"}
    assert keys["cpu"]["mla_attention"] == "xla_blocked"
    assert keys["tpu"]["mla_attention"] == "pallas_flash"
    assert keys["cpu"]["stream_cfg"] == keys["tpu"]["stream_cfg"]
    # the module alone is a latent layer too
    only_mtp = dataclasses.replace(PHI4, mtp_layers=1, qk_nope_dim=64,
                                   qk_rope_dim=64, v_head_dim=128)
    assert stream_kernel_path(only_mtp, 8192) == {
        "mla_attention": "pallas_flash"}
    with pytest.raises(ValueError):
        stream_key_extra(wide)
