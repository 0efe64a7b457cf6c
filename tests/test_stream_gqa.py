"""The stream encoder's grouped-query kinds (``gqa_full_dense``,
``gqa_full_moe``, ``gqa_swa_moe``) outside the benchmark: the attention core
with a window and grouped heads against a plain masked softmax on both
routes, the partial and YaRN rotary, the routes a step reports, and the
experiment trained through `train.run`.  (Program against reference:
tests/chipbench/test_chipbench_laguna.py.)"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nerrf_tpu.models.stream import Rotary, StreamConfig

TOY = StreamConfig(
    dim=32, num_heads=4, window_heads=6, num_kv_heads=2, head_dim=8,
    window=16, num_layers=3,
    kinds=("gqa_full_dense", "gqa_swa_moe", "gqa_full_moe"), vocab_size=64,
    dropout=0.0, dtype=jnp.float32, mlp_dim=64, num_experts=8,
    experts_per_token=2, expert_dim=16, first_expert=2, held_experts=4,
    router_scale=2.5, shared_dim=16, rms_eps=1e-6, tie_head=False,
    rope_full=Rotary(theta=5e5, fraction=0.5, yarn_factor=128.0,
                     yarn_original=8192, attention_factor=1.4852),
    rope_window=Rotary(theta=1e4))


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    from nerrf_tpu.ops import mla, moe

    monkeypatch.setattr(mla, "QUERY_BLOCK", 64)
    monkeypatch.setattr(mla, "KEY_SPAN", 128)
    monkeypatch.setattr(moe, "TILE", 8)


@pytest.fixture
def route(request, monkeypatch):
    """``xla``: the blocked form; ``fused``: the route a TPU traces, its
    kernels in Pallas' interpreter at tiles of 128."""
    from nerrf_tpu.ops import mla

    if request.param == "xla":
        yield "xla_blocked"
        return
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mla, "FLASH_BLOCK_Q", 128)
    monkeypatch.setattr(mla, "FLASH_BLOCK_K", 128)
    with pltpu.force_tpu_interpret_mode():
        yield "pallas_flash"


def _inputs(seed=0, t=512, h=6, hk=2, d=128):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    seg = jnp.asarray(np.concatenate(
        [np.full(100, 1), np.full(250, 2), np.full(130, 3),
         np.zeros(t - 480)]).astype(np.int32))
    return n(t, h, d), n(t, hk, d), n(t, hk, d), seg, n(t, h, d)


def _plain(q, k, v, seg, window):
    """Softmax over an explicit mask, every query head reading its group's
    key-value head: no blocks, no kernels."""
    t, h, d = q.shape
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    i = jnp.arange(t)
    mask = (i[None] <= i[:, None]) & (seg[:, None] == seg[None])
    if window is not None:
        mask = mask & (i[:, None] - i[None] < window)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    return jnp.einsum("hqk,khd->qhd",
                      jax.nn.softmax(jnp.where(mask, s, -1e30), -1), v)


@pytest.mark.parametrize("route", ["xla", "fused"], indirect=True)
@pytest.mark.parametrize("window", [None, 32, 96, 200])
def test_windowed_grouped_core_equals_a_plain_masked_softmax(route, window):
    """`ops/mla.py::attention` with 6 query heads over 2 key-value heads and
    a window that cuts inside the 250-token document, forward and the three
    gradients (the shared heads' as sums over their groups)."""
    from nerrf_tpu.ops import mla

    q, k, v, seg, cot = _inputs()
    assert mla.attention_route(512, 128, 128) == route
    core = lambda q, k, v: mla.attention(q, k, v, seg, window=window,
                                         scope="gqa_test")
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(core(q, k, v)),
                                   np.asarray(_plain(q, k, v, seg, window)),
                                   rtol=2e-5, atol=2e-5)
        got = jax.grad(lambda *a: jnp.sum(core(*a) * cot), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(_plain(*a, seg, window) * cot),
                        (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5


@pytest.mark.parametrize("route", ["xla", "fused"], indirect=True)
def test_no_window_is_a_window_of_the_whole_sequence(route):
    """``window=None`` (what the latent stack calls) and a window of at
    least T give the same core, forward and backward."""
    from nerrf_tpu.ops import mla

    q, k, v, seg, cot = _inputs(1)

    def both(window):
        f = lambda q, k, v: mla.attention(q, k, v, seg, window=window)
        return (f(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(f(*a) * cot), (0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        for a, b in zip(both(None), both(512)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


def test_the_fused_walks_visit_every_tile_a_window_reaches():
    """`_forward_walk` / `_backward_walk` at 8192 tokens and tiles of 512:
    no window walks every tile; a window of 512 two key tiles a query tile
    and two query tiles a key tile, from the first the window reaches."""
    from nerrf_tpu.ops import mla

    assert mla._forward_walk(8192, None) == (16, None)
    steps, first = mla._forward_walk(8192, 512)
    assert steps == 2 and [int(first(i)) for i in (0, 1, 15)] == [0, 0, 14]
    steps, first, last = mla._backward_walk(8192, 512)
    assert steps == 2
    assert [(int(first(j)), int(last(j))) for j in (0, 14, 15)] == [
        (0, 1), (14, 15), (15, 15)]
    # a window that is not a whole tile reaches one more
    assert mla._forward_walk(8192, 600)[0] == 3


def test_a_partial_yarn_rotary_turns_the_first_dimensions_alone():
    from nerrf_tpu.ops import dsa

    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (40, 3, 16)).astype(np.float32))
    pos = jnp.arange(40)
    # half of 16: the pairs (i, i + 4) of the first 8 turn, the last 8 pass
    turned = TOY.rope_full.apply(x, pos)
    np.testing.assert_array_equal(np.asarray(turned[..., 8:]),
                                  np.asarray(x[..., 8:]))
    assert not np.allclose(np.asarray(turned[1:, :, :8]),
                           np.asarray(x[1:, :, :8]))
    # each turned pair keeps its length times the attention factor
    pair = lambda y: jnp.sqrt(y[..., :4] ** 2 + y[..., 4:8] ** 2)
    np.testing.assert_allclose(np.asarray(pair(turned)),
                               1.4852 * np.asarray(pair(x)), rtol=1e-5)
    # at the defaults it is the plain rotary the other stacks use
    np.testing.assert_array_equal(
        np.asarray(Rotary(theta=1e4).apply(x, pos)),
        np.asarray(dsa.rope(x, pos, 1e4)))
    # YaRN turns the slow frequencies 128 x slower
    freq = dsa.yarn_frequencies(64, 5e5, 128.0, 8192, 32.0, 1.0)
    plain = 5e5 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(freq[:9], plain[:9], rtol=1e-5)
    np.testing.assert_allclose(freq[18:], plain[18:] / 128, rtol=1e-5)


def test_a_step_reports_each_grouped_cores_route(monkeypatch):
    """`stream_kernel_path`: a scope a grouped kind, the route of the core
    at that kind's window; a rotary field rides the AOT key."""
    from nerrf_tpu.train.stream import stream_key_extra, stream_kernel_path

    for backend, route in (("cpu", "xla_blocked"), ("tpu", "pallas_flash")):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        wide = dataclasses.replace(TOY, head_dim=128)
        assert stream_kernel_path(wide, 8192) == {
            "gqa_full_attention": route, "gqa_window_attention": route}
    full_only = dataclasses.replace(TOY, num_layers=1,
                                    kinds=("gqa_full_dense",))
    assert set(stream_kernel_path(full_only, 64)) == {"gqa_full_attention"}
    other = dataclasses.replace(TOY, rope_full=dataclasses.replace(
        TOY.rope_full, attention_factor=1.0))
    assert stream_key_extra(other, 64) != stream_key_extra(TOY, 64)


def test_train_run_trains_the_experiment(tmp_path):
    """`python -m nerrf_tpu.train.run --experiment <file>` on a toy copy of
    `configs/stream-laguna-s-2.1.json`: corpus -> tokens -> packed -> the
    cached, traced, scheduled step; the loss falls, the loop's syncs count
    both kinds' pairs, and a checkpoint that names the kinds is written."""
    from nerrf_tpu.compilecache import CompileCache
    from nerrf_tpu.config import EXPERIMENTS, CorpusConfig, Experiment
    from nerrf_tpu.data.stream import PackConfig
    from nerrf_tpu.observability import DEFAULT_REGISTRY as reg
    from nerrf_tpu.train.run import run_experiment

    exp = Experiment.load("configs/stream-laguna-s-2.1.json")
    assert exp == EXPERIMENTS["stream-laguna-s-2.1"]
    toy = dataclasses.replace(
        exp, name="stream-gqa-toy",
        corpus=CorpusConfig(num_traces=2, duration_sec=60.0,
                            num_target_files=10, benign_rate_hz=30.0,
                            eval_fraction=0.0),
        train=dataclasses.replace(exp.train, num_steps=12, warmup_steps=2,
                                  learning_rate=3e-3, eval_every=4),
        stream=dataclasses.replace(TOY, vocab_size=512),
        stream_data=PackConfig(seq_len=256, num_seqs=4, doc_median=64.0,
                               doc_min=16))
    path = toy.save(tmp_path / "stream-gqa-toy.json")
    before = {k: reg.value("attention_pairs_total", labels={"kind": k})
              for k in ("window", "full")}
    report = run_experiment(str(path), tmp_path / "out",
                            compile_cache=CompileCache(
                                root=str(tmp_path / "aot")))
    assert report["gates"] == {"loss_fell": True}
    assert report["loss"]["last"] < report["loss"]["first"]
    grew = {k: reg.value("attention_pairs_total", labels={"kind": k})
            - before[k] for k in ("window", "full")}
    # one window layer and two full ones, every step of one sequence
    assert 0 < grew["window"] < grew["full"] / 2
    meta = json.loads((tmp_path / "out" / "model" /
                       "stream_config.json").read_text())
    assert meta["stream"]["kinds"] == list(TOY.kinds)
    assert meta["stream"]["rope_full"]["yarn_factor"] == 128.0
