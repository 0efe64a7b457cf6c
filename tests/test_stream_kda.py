"""The stream encoder's delta-rule kinds (``kda_dense``, ``kda_moe``) and the
latent layer's two switches (a direct query projection, nothing rotated)
outside the benchmark: the chunked delta rule against the sequential
recurrence, forward and every gradient, across documents, chunk sizes and
extreme decays; the routes a step reports; the other stream stacks' steps
lowering as before; the experiment trained through `train.run`.  (Program
against reference: tests/chipbench/test_chipbench_kimilinear.py.)"""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference.kimilinear import delta_rule
from nerrf_tpu.models.stream import Rotary, StreamConfig, StreamNet
from nerrf_tpu.ops import kda

TOY = StreamConfig(
    dim=32, num_heads=2, num_layers=3,
    kinds=("kda_dense", "mla_moe", "kda_moe"), vocab_size=64, dropout=0.0,
    dtype=jnp.float32, mlp_dim=64, q_lora_rank=0, kv_lora_rank=12,
    qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, mla_rope=False, head_dim=8,
    d_conv=4, num_experts=8,
    experts_per_token=2, expert_dim=16, first_expert=2, held_experts=4,
    router_scale=2.446, shared_dim=16, rms_eps=1e-5, tie_head=False)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    from nerrf_tpu.ops import mla, moe

    monkeypatch.setattr(mla, "QUERY_BLOCK", 32)
    monkeypatch.setattr(mla, "KEY_SPAN", 32)
    monkeypatch.setattr(moe, "TILE", 8)


def _inputs(seed, t, seg, h=2, dk=8, dv=6, decay=None):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    g = -jnp.exp(n(t, h, dk)) if decay is None else decay(n(t, h, dk))
    return (unit(n(t, h, dk)) * dk ** -0.5, unit(n(t, h, dk)), n(t, h, dv),
            g, jax.nn.sigmoid(n(t, h)), jnp.asarray(seg, jnp.int32))


# documents of 5, 11, 16 and 10 tokens, then padding: with chunks of 4 and
# 8 one starts on a chunk's first token (16, 32) and others inside a chunk
DOCS = [1] * 5 + [2] * 11 + [3] * 16 + [4] * 10 + [0] * 6


@pytest.mark.parametrize("chunk", [4, 8, 7, 16, 64])
def test_the_chunked_rule_is_the_sequential_recurrence(chunk):
    """Forward and the gradients of all five inputs against the reference's
    token-at-a-time recurrence, with chunks that do (4, 8, 16) and do not
    (7, 64) divide the 48 steps."""
    args = _inputs(0, len(DOCS), DOCS)
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        (len(DOCS), 2, 6)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        got = kda.chunk_delta(*args, chunk=chunk)
        want = delta_rule(*args)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        loss = lambda fn: lambda *a: jnp.sum(fn(*a, args[-1]) * cot)
        g_got = jax.grad(loss(partial_chunk(chunk)), argnums=range(5))(
            *args[:-1])
        g_want = jax.grad(loss(delta_rule), argnums=range(5))(*args[:-1])
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


def partial_chunk(chunk):
    return lambda *a: kda.chunk_delta(*a, chunk=chunk)


def test_the_state_restarts_at_every_document():
    """The output of a document is what it is alone: each of the packed
    documents run on its own gives the same rows."""
    args = _inputs(2, len(DOCS), DOCS)
    packed = kda.chunk_delta(*args, chunk=8)
    seg = np.asarray(DOCS)
    for doc in (2, 3, 4):
        rows = np.flatnonzero(seg == doc)
        alone = kda.chunk_delta(*(x[rows] for x in args[:-1]),
                                jnp.ones(len(rows), jnp.int32), chunk=8)
        np.testing.assert_allclose(packed[rows], alone, rtol=1e-5,
                                   atol=1e-6)


def test_extreme_decays_stay_finite_and_exact():
    """A_log = log 16 and pre-activations of +20: -320 a step, a chunk's
    decays summing to -20,000; no positive cumulative log-decay is ever
    exponentiated, so forward and gradients are finite and equal the
    recurrence's."""
    hard = lambda x: -16.0 * jax.nn.softplus(20.0 + x)
    args = _inputs(3, len(DOCS), DOCS, decay=hard)
    assert float(jnp.min(jnp.sum(args[3][:16], axis=0))) < -5000
    with jax.default_matmul_precision("highest"):
        got = kda.chunk_delta(*args, chunk=16)
        np.testing.assert_allclose(got, delta_rule(*args), rtol=1e-5,
                                   atol=1e-6)
        grads = jax.grad(lambda *a: jnp.sum(kda.chunk_delta(
            *a, args[-1], chunk=16) ** 2), argnums=range(5))(*args[:-1])
        want = jax.grad(lambda *a: jnp.sum(delta_rule(*a, args[-1]) ** 2),
                        argnums=range(5))(*args[:-1])
    for a, b in zip(grads, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_a_remat_that_keeps_the_residuals_walks_the_chunks_once_forward():
    """Under `jax.checkpoint` with the policy the layer's remat uses, the
    backward pass reads the kept chunk-start states: the forward walk over
    the chunks is traced once, not again for the backward pass."""
    args = _inputs(4, 64, [1] * 64)
    policy = jax.checkpoint_policies.save_only_these_names(kda.SAVED)
    f = jax.checkpoint(lambda *a: kda.chunk_delta(*a, args[-1], chunk=8),
                       policy=policy)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(f(*a) ** 2)))(
        *args[:-1]))
    # one forward scan, one reverse scan: no recomputed forward
    assert text.count("scan[") == 2, text.count("scan[")


def test_a_step_reports_the_latent_cores_route_and_keys_the_delta_fields():
    """The step reports both routes of the stack: the delta rule's and the
    latent core's (here the CPU's); the latent core of Kimi-Linear's widths
    (192-wide keys, 128-wide values) takes the blocked XLA form on a TPU."""
    from nerrf_tpu.ops import mla
    from nerrf_tpu.train.stream import stream_key_extra, stream_kernel_path

    assert stream_kernel_path(TOY, 64) == {"kda_core": "xla_chunked",
                                           "mla_attention": "xla_blocked"}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        assert mla.attention_route(8192, 192, 128) == "xla_blocked"
    # an edit to a delta-rule field changes the AOT key
    other = dataclasses.replace(TOY, head_dim=4)
    assert stream_key_extra(other, 64) != stream_key_extra(TOY, 64)


@pytest.mark.parametrize("backend, t, d_k, d_v, route", [
    ("tpu", 8192, 128, 128, "pallas"),        # Kimi-Linear's heads
    ("tpu", 8192 + 5, 128, 128, "pallas"),    # padded to whole chunks
    ("tpu", 8192, 96, 128, "xla_chunked"),    # keys not whole lanes
    ("tpu", 8192, 128, 64, "xla_chunked"),    # values not whole lanes
    ("tpu", 48, 8, 6, "xla_chunked"),         # this file's toy
    ("cpu", 8192, 128, 128, "xla_chunked"),
])
def test_delta_route_is_a_function_of_backend_and_shapes(
        monkeypatch, backend, t, d_k, d_v, route):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kda.delta_route(t, d_k, d_v) == route


def test_only_a_delta_stack_reports_the_delta_rules_route(monkeypatch):
    """`stream_kernel_path` on a TPU for every published stream stack at
    8192 tokens: Kimi-Linear's reports ``kda_core: pallas`` beside its
    latent core; the others report what they reported before the fused
    route existed (their AOT keys do not move)."""
    from nerrf_tpu.config import EXPERIMENTS
    from nerrf_tpu.train.stream import stream_kernel_path

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    want = {
        "stream-phi4-mini-flash": {"ssm_scan": "pallas"},
        "stream-keye-vl2-30b-a3b": {"dsa_attention": "pallas_flash"},
        "stream-glm-4.7-flash": {"mla_attention": "pallas_flash"},
        "stream-laguna-s-2.1": {"gqa_full_attention": "pallas_flash",
                                "gqa_window_attention": "pallas_flash"},
        "stream-kimi-linear-48b-a3b": {"kda_core": "pallas",
                                       "mla_attention": "xla_blocked"},
    }
    for name, path in want.items():
        assert stream_kernel_path(EXPERIMENTS[name].stream, 8192) == path
    for cfg in (GLM, LAGUNA):
        assert "kda_core" not in stream_kernel_path(cfg, 64)


@pytest.fixture
def fused_delta(monkeypatch):
    """What a TPU traces, on the CPU, and run: the kernels in Pallas'
    interpreter."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        yield kda


# 150 steps (padded to three kernel chunks of 64): documents start on a
# chunk's first step (64, 128) and inside a chunk (29, 100, 140)
WIDE_DOCS = ([1] * 29 + [2] * 35 + [3] * 36 + [4] * 28 + [5] * 12
             + [6] * 10)


def delta_value_and_grads(args, cot):
    """``o`` and the five gradients of ``sum(o * cot)``, the rule under
    `vmap` as the ``kda_*`` kinds call it (a batch of one)."""
    *ins, seg = (x[None] for x in args)

    def loss(*ins):
        o = jax.vmap(kda.chunk_delta)(*ins, seg)
        return jnp.sum(o * cot[None]), o[0]

    (_, o), grads = jax.value_and_grad(loss, argnums=range(5),
                                       has_aux=True)(*ins)
    return [o] + [x[0] for x in grads]


@pytest.mark.parametrize("case", ["documents", "extreme_decays"])
def test_the_fused_kernels_are_the_chunked_rule(fused_delta, case):
    """``o`` and the gradients of ``q``, ``k``, ``v``, ``g``, ``beta`` on
    the fused route (two heads of 128 x 128, three chunks, the last padded)
    against the XLA route: documents that start on a chunk's first step
    and inside chunks; and the extreme decays of
    `test_extreme_decays_stay_finite_and_exact` (-320 a step), at that
    test's tolerances, every gradient finite."""
    hard = lambda x: -16.0 * jax.nn.softplus(20.0 + x)
    t = len(WIDE_DOCS)
    args = _inputs(7, t, WIDE_DOCS, dk=128, dv=128,
                   decay=hard if case == "extreme_decays" else None)
    cot = jnp.asarray(np.random.default_rng(8).standard_normal(
        (t, 2, 128)).astype(np.float32))
    assert kda.delta_route(t, 128, 128) == "pallas"
    with jax.default_matmul_precision("highest"):
        got = delta_value_and_grads(args, cot)
        with pytest.MonkeyPatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "cpu")
            want = delta_value_and_grads(args, cot)
    atol = 1e-6 if case == "extreme_decays" else 1e-5
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=atol)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got[1:], want[1:]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.all(jnp.isfinite(a))), name
        scale = 1.0 if case == "extreme_decays" else float(
            jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * scale,
                                   err_msg=name)


def test_a_remat_that_keeps_the_residuals_runs_the_forward_kernel_once(
        monkeypatch):
    """The fused route under `jax.checkpoint` with the policy the layer's
    remat uses: the gradient holds the forward kernel once and the backward
    kernel once (it reads the kept chunk-start states), and no scan."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = _inputs(4, 128, [1] * 128, dk=128, dv=128)
    policy = jax.checkpoint_policies.save_only_these_names(kda.SAVED)
    f = jax.checkpoint(lambda *a: kda.chunk_delta(*a, args[-1]),
                       policy=policy)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(f(*a) ** 2),
                                       argnums=range(5)))(*args[:-1]))
    # one forward kernel, one backward kernel: no recomputed forward
    assert text.count("pallas_call[") == 2
    assert text.count("kda_fwd") == 1 and text.count("kda_bwd") == 1
    assert "scan[" not in text


def test_the_toy_stack_trains_alike_on_both_delta_routes(fused_delta):
    """A toy ``kda_dense`` + ``kda_moe`` stack with one head of 128 x 128
    over 128 steps (two kernel chunks, three documents) through the
    layer's `vmap`: the loss and every parameter's gradient on the fused
    route are the XLA route's.  Without the layers' remat: the interpreter
    runs a kernel as a host callback, which remat cannot replay (the test
    above counts the kernels under remat)."""
    from nerrf_tpu.models.stream import next_token_loss

    cfg = dataclasses.replace(TOY, num_heads=1, head_dim=128, num_layers=2,
                              kinds=("kda_dense", "kda_moe"), remat=False)
    model = StreamNet(cfg)
    rng = np.random.default_rng(9)
    tok = jnp.asarray(rng.integers(0, 64, (1, 128)), jnp.int32)
    seg = jnp.asarray([[1] * 50 + [2] * 40 + [3] * 38], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tok, seg)["params"]
    assert kda.delta_route(128, 128, 128) == "pallas"

    def loss(p):
        return next_token_loss(
            cfg, p, model.apply({"params": p}, tok, seg)["hidden"], tok, seg)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(loss)(params)
        with pytest.MonkeyPatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "cpu")
            want = jax.value_and_grad(loss)(params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6)


def test_the_latent_layer_projects_the_query_directly_and_rotates_nothing():
    """``q_lora_rank`` 0: one ``wq`` and no query latent; ``mla_rope``
    false: the same output at every position shift of a document, since no
    position enters (a document moved inside its sequence attends alike)."""
    cfg = dataclasses.replace(TOY, num_layers=1, kinds=("mla_moe",))
    model = StreamNet(cfg)
    tok = jnp.asarray(np.random.default_rng(5).integers(0, 64, (1, 32)),
                      jnp.int32)
    seg = jnp.ones((1, 32), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tok, seg)["params"]
    assert "wq" in params["layer_0"] and "wq_a" not in params["layer_0"]
    shifted = jnp.concatenate([jnp.zeros((1, 8), jnp.int32), tok[:, :24]], 1)
    seg2 = jnp.concatenate([jnp.ones((1, 8), jnp.int32),
                            2 * jnp.ones((1, 24), jnp.int32)], 1)
    hidden = jax.jit(lambda t, s: model.apply({"params": params}, t, s)[
        "hidden"][0])
    np.testing.assert_allclose(hidden(tok, seg)[:24],
                               hidden(shifted, seg2)[8:], rtol=1e-5,
                               atol=1e-5)
    # with the rotation the same shift moves the output
    turned = StreamNet(dataclasses.replace(cfg, mla_rope=True))
    hidden = jax.jit(lambda t, s: turned.apply({"params": params}, t, s)[
        "hidden"][0])
    moved = hidden(tok, seg)[:24] - hidden(shifted, seg)[8:]
    assert float(jnp.max(jnp.abs(moved))) > 1e-3


GLM = StreamConfig(
    dim=32, num_heads=2, num_layers=3,
    kinds=("mla_dense", "mla_moe", "mla_moe"), vocab_size=64, dropout=0.0,
    dtype=jnp.bfloat16, mlp_dim=64, rope_theta=1e4, q_lora_rank=16,
    kv_lora_rank=12, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=10,
    num_experts=8, experts_per_token=2, expert_dim=16, first_expert=2,
    held_experts=4, router_scale=1.8, shared_dim=16, rms_eps=1e-5,
    tie_head=False, mtp_layers=1, mtp_loss_weight=0.3)
LAGUNA = StreamConfig(
    dim=32, num_heads=4, window_heads=6, num_kv_heads=2, head_dim=8,
    window=16, num_layers=3,
    kinds=("gqa_full_dense", "gqa_swa_moe", "gqa_full_moe"), vocab_size=64,
    dropout=0.0, dtype=jnp.bfloat16, mlp_dim=64, num_experts=8,
    experts_per_token=2, expert_dim=16, first_expert=2, held_experts=4,
    router_scale=2.5, shared_dim=16, rms_eps=1e-6, tie_head=False,
    rope_full=Rotary(theta=5e5, fraction=0.5, yarn_factor=128.0,
                     yarn_original=8192, attention_factor=1.4852),
    rope_window=Rotary(theta=1e4))
# the toy steps' StableHLO before the delta-rule kinds and the latent
# layer's switches existed
RECORDED = {
    "glm": "38a73038802b05a714154239184aca81fce6d234d0a4d555b68a86ea95405b30",
    "laguna":
        "c0b7d7e1fe011d8b24e3e3d4354b677630fb963eabec77c6aac6bc091d371b26",
}


@pytest.mark.parametrize("name, cfg", [("glm", GLM), ("laguna", LAGUNA)])
def test_the_latent_and_grouped_stacks_lower_as_before(monkeypatch, name,
                                                       cfg):
    """Loss + gradient of the toy step, as StableHLO, at the ops' own block
    sizes: the same text as before, so neither cell's program changed (the
    other two stacks: tests/test_stream_latent.py)."""
    from nerrf_tpu.train.stream import make_stream_loss_fn

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were recorded under jax 0.9.0")
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


def test_no_step_moves_a_delta_layers_correction_bias():
    """The trainer's optimizer for the toy (`make_stream_tx`): after two
    updates with weight decay (the first at the warm-up's zero rate), the
    sigmoid routers' correction biases of the ``kda_moe`` and ``mla_moe``
    layers are where they were, their routers are not."""
    import optax

    from nerrf_tpu.train.loop import TrainConfig
    from nerrf_tpu.train.stream import make_stream_tx

    tok = jnp.zeros((1, 16), jnp.int32)
    params = jax.jit(lambda key: StreamNet(TOY).init(
        key, tok, jnp.ones_like(tok))["params"])(jax.random.PRNGKey(0))
    tx = make_stream_tx(TrainConfig(learning_rate=3e-3, warmup_steps=1,
                                    weight_decay=0.1), TOY)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    moved, state = params, tx.init(params)
    step = jax.jit(lambda p, st: (lambda u, st: (
        optax.apply_updates(p, u), st))(*tx.update(grads, st, p)))
    for _ in range(2):
        moved, state = step(moved, state)
    for name in ("layer_1", "layer_2"):
        np.testing.assert_array_equal(moved[name]["router_bias"],
                                      params[name]["router_bias"])
        assert float(jnp.abs(params[name]["router_bias"]).max()) > 0
        assert not np.array_equal(moved[name]["router"]["kernel"],
                                  params[name]["router"]["kernel"])


def test_train_run_trains_the_experiment(tmp_path):
    """`python -m nerrf_tpu.train.run --experiment <file>` on a toy copy of
    `configs/stream-kimi-linear-48b-a3b.json`: corpus -> tokens -> packed ->
    the cached, traced, scheduled step; the loss falls, the loop's syncs
    count the delta rule's tokens and resets, and a checkpoint that names
    the kinds is written."""
    from nerrf_tpu.compilecache import CompileCache
    from nerrf_tpu.config import EXPERIMENTS, CorpusConfig, Experiment
    from nerrf_tpu.data.stream import PackConfig
    from nerrf_tpu.observability import DEFAULT_REGISTRY as reg
    from nerrf_tpu.train.run import run_experiment

    exp = Experiment.load("configs/stream-kimi-linear-48b-a3b.json")
    assert exp == EXPERIMENTS["stream-kimi-linear-48b-a3b"]
    toy = dataclasses.replace(
        exp, name="stream-kda-toy",
        corpus=CorpusConfig(num_traces=2, duration_sec=60.0,
                            num_target_files=10, benign_rate_hz=30.0,
                            eval_fraction=0.0),
        train=dataclasses.replace(exp.train, num_steps=12, warmup_steps=2,
                                  learning_rate=3e-3, eval_every=4),
        stream=dataclasses.replace(TOY, vocab_size=512),
        stream_data=PackConfig(seq_len=256, num_seqs=4, doc_median=64.0,
                               doc_min=16))
    path = toy.save(tmp_path / "stream-kda-toy.json")
    names = ("kda_tokens_total", "kda_resets_total")
    before = {k: reg.value(k) for k in names}
    report = run_experiment(str(path), tmp_path / "out",
                            compile_cache=CompileCache(
                                root=str(tmp_path / "aot")))
    assert report["gates"] == {"loss_fell": True}
    assert report["loss"]["last"] < report["loss"]["first"]
    grew = {k: reg.value(k) - before[k] for k in names}
    # two delta-rule layers, 256 tokens a step, a few documents a sequence
    assert 0 < grew["kda_resets_total"] < grew["kda_tokens_total"] / 16
    assert grew["kda_tokens_total"] <= 12 * 2 * 256
    meta = json.loads((tmp_path / "out" / "model" /
                       "stream_config.json").read_text())
    assert meta["stream"]["kinds"] == list(TOY.kinds)
    assert meta["stream"]["mla_rope"] is False
