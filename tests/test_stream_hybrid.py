"""The stream encoder's decoder-hybrid-decoder kinds (docs/stream-backbone.md)
at a toy width on the CPU: the stack's shape, the masks of the local
attention path, the selective scan, document boundaries, the vocabulary
slices, the tokenizer and the packer, and the experiment's path through
`train.run`.  (Program against reference: tests/chipbench/.)"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nerrf_tpu.data import SimConfig, simulate_trace
from nerrf_tpu.data.stream import (PackConfig, build_packed_streams,
                                   cut_documents, pack_documents,
                                   tokenize_events)
from nerrf_tpu.models.stream import (HYBRID_KINDS, StreamConfig, StreamNet,
                                     lam_init_of, layer_kinds,
                                     next_token_loss, next_token_targets)
from nerrf_tpu.ops.ssm import causal_conv1d, selective_scan
from nerrf_tpu.parallel import MeshConfig, make_mesh, ring_self_attention
from nerrf_tpu.parallel.ring import _attention_dense, _attention_local

TOY = dict(dim=64, num_heads=4, num_kv_heads=2, head_dim=16, mlp_dim=128,
           window=32, dt_rank=4, num_layers=6, kinds=layer_kinds(6),
           published_layers=(0, 1, 16, 17, 18, 19), vocab_size=512,
           dropout=0.0, dtype=jnp.float32)
T = 256


def toy_batch():
    """Two packed sequences of three and two documents, padded tails."""
    r = np.random.default_rng(0)
    tok = jnp.asarray(r.integers(0, 512, (2, T)), jnp.int32)
    seg = jnp.asarray(np.stack([
        np.repeat([1, 2, 3, 0], [100, 80, 60, 16]),
        np.repeat([1, 2, 0], [30, 200, 26])]), jnp.int32)
    return tok, seg


@pytest.fixture(scope="module")
def toy():
    cfg = StreamConfig(**TOY)
    model = StreamNet(cfg)
    tok, seg = toy_batch()
    params = model.init(jax.random.PRNGKey(0), tok, seg)["params"]
    return cfg, model, params, tok, seg


def logits_of(model, params, tok, seg):
    hidden = model.apply({"params": params}, tok, seg)["hidden"]
    return hidden @ params["tok_embed"]["embedding"].T


# --- the stack ---------------------------------------------------------------

def test_layer_kinds_published_and_cut():
    full = layer_kinds(32)
    assert [full.count(k) for k in HYBRID_KINDS] == [9, 8, 1, 7, 7]
    assert full[16] == "mamba" and full[17] == "full"
    assert set(full[:16:2]) == {"mamba"} and set(full[1:16:2]) == {"swa"}
    assert full[18:] == ("gmu", "cross") * 7
    cut = layer_kinds(6)
    assert set(cut) == set(HYBRID_KINDS)
    # a sub-list of the published stack, in its order
    published = (0, 1, 16, 17, 18, 19)
    assert tuple(full[i] for i in published) == cut
    with pytest.raises(ValueError):
        layer_kinds(12)
    assert lam_init_of(0) == pytest.approx(0.2)
    assert lam_init_of(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))


def test_the_stack_fields_have_to_agree():
    """`num_layers`, `kinds` and `published_layers` say one thing."""
    with pytest.raises(ValueError, match="kinds names 2 layers"):
        StreamConfig(num_layers=6, kinds=("mamba", "swa"))
    with pytest.raises(ValueError, match="published_layers names 2"):
        StreamConfig(**{**TOY, "published_layers": (0, 1)})


def test_default_configuration_is_the_original_block_stack():
    cfg = StreamConfig()
    assert cfg.stack == ("block",) * 4 and cfg.vocab_size == 0
    model = StreamNet(cfg)
    feat = jnp.zeros((1, 16, 12))
    params = model.init(jax.random.PRNGKey(0), feat,
                        jnp.ones((1, 16), bool))["params"]
    assert sorted(params) == ["block_0", "block_1", "block_2", "block_3",
                              "embed", "final_ln", "head"]
    assert sorted(params["block_0"]) == ["attn_ln", "mlp_in", "mlp_ln",
                                         "mlp_out", "proj", "qkv"]


def test_hybrid_parameters_by_kind(toy):
    _, _, params, _, _ = toy
    assert sorted(params["layer_0"]) == ["mamba", "mix_ln", "mlp"]
    assert sorted(params["layer_3"]["attn"]) == [
        "lk1", "lk2", "lq1", "lq2", "subln", "wk", "wo", "wq", "wv"]
    # the cross layer owns W_q and W_o only
    assert sorted(params["layer_5"]["attn"]) == [
        "lk1", "lk2", "lq1", "lq2", "subln", "wo", "wq"]
    assert sorted(params["layer_4"]) == ["gmu_in", "gmu_out", "mix_ln", "mlp"]
    assert params["layer_0"]["mamba"]["A_log"].shape == (128, 16)
    assert "bias" not in params["layer_1"]["mlp"]["gate"]


# --- document boundaries -------------------------------------------------------

def test_a_document_boundary_resets_scan_convolution_and_attention(toy):
    """Other tokens in document 1 leave document 2's and 3's logits
    bit-equal: nothing crosses a boundary, in any of the six kinds."""
    _, model, params, tok, seg = toy
    base = logits_of(model, params, tok, seg)
    other = tok.at[0, :100].set((tok[0, :100] + 7) % 512)
    moved = logits_of(model, params, other, seg)
    assert not np.array_equal(np.asarray(base[0, :100]),
                              np.asarray(moved[0, :100]))
    np.testing.assert_array_equal(np.asarray(base[0, 100:240]),
                                  np.asarray(moved[0, 100:240]))
    np.testing.assert_array_equal(np.asarray(base[1]), np.asarray(moved[1]))


def test_remat_carries_the_hand_downs(toy):
    """With and without per-layer rematerialization: same loss, same
    gradients (the memory and the keys and values are layer outputs)."""
    import dataclasses

    cfg, model, params, tok, seg = toy
    plain = StreamNet(dataclasses.replace(cfg, remat=False))

    def loss(m):
        return lambda p: next_token_loss(
            cfg, p, m.apply({"params": p}, tok, seg)["hidden"], tok, seg)

    la, ga = jax.value_and_grad(loss(model))(params)
    lb, gb = jax.value_and_grad(loss(plain))(params)
    assert float(la) == pytest.approx(float(lb), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6)


def test_next_token_targets_skip_first_tokens_and_padding():
    tok = jnp.arange(8)[None]
    seg = jnp.asarray([[1, 1, 1, 2, 2, 0, 0, 0]])
    nxt, w = next_token_targets(tok, seg)
    assert w.tolist() == [[1, 1, 0, 1, 0, 0, 0, 0]]
    assert nxt[0, :4].tolist() == [1, 2, 3, 4]


def test_vocabulary_slices_concatenate_to_the_uncut_head(toy):
    """Eight chips each hold an eighth of the tied embedding's rows: their
    logits side by side are the uncut head's, and the loss over a slice is
    `next_token_loss` with that slice as the embedding."""
    cfg, model, params, tok, seg = toy
    hidden = model.apply({"params": params}, tok, seg)["hidden"]
    emb = params["tok_embed"]["embedding"]
    whole = hidden @ emb.T
    parts = [hidden @ emb[i * 64:(i + 1) * 64].T for i in range(8)]
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, -1)),
                               np.asarray(whole), rtol=1e-6, atol=1e-6)
    # the chunked loss equals the plain cross-entropy over all logits,
    # in eight chunks and in one
    nxt, w = next_token_targets(tok, seg)
    nll = jax.nn.logsumexp(whole, -1) - jnp.take_along_axis(
        whole, nxt[..., None], -1)[..., 0]
    want = jnp.sum(nll * w) / jnp.sum(w)
    for chunk in (64, 1024):
        got = next_token_loss(cfg, params, hidden, tok, seg, chunk=chunk)
        assert float(got) == pytest.approx(float(want), rel=1e-5)


# --- the local attention path's masks -------------------------------------------

@pytest.mark.parametrize("window", [None, 512, 700])
@pytest.mark.parametrize("segmented", [False, True])
def test_blockwise_window_and_segments_match_dense(window, segmented):
    """Blockwise path (t > 2 blocks, ragged last block, values wider than
    keys) against materialized attention, forward and gradients."""
    r = np.random.default_rng(0)
    b, t, h, d, dv = 2, 1300, 2, 8, 16
    q, k = (jnp.asarray(r.normal(size=(b, t, h, d)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(r.normal(size=(b, t, h, dv)), jnp.float32)
    seg = None
    if segmented:
        seg = jnp.asarray(np.stack([np.repeat([1, 2, 3], [600, 100, 600]),
                                    np.repeat([1, 2, 0], [40, 1200, 60])]))
    kw = dict(window=window, q_seg=seg, k_seg=seg)
    want = _attention_dense(q, k, v, True, **kw)
    got = _attention_local(q, k, v, True, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    grad = lambda fn: jax.grad(lambda *a: (fn(*a, True, **kw) ** 2).sum(),
                               argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(grad(_attention_local), grad(_attention_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4,
                                   atol=1e-4)


def test_window_keeps_exactly_the_last_w_keys():
    r = np.random.default_rng(1)
    q, k, v = (jnp.asarray(r.normal(size=(1, 40, 1, 4)), jnp.float32)
               for _ in range(3))
    got = ring_self_attention(q, k, v, None, causal=True, window=8)
    # by hand: query 20 sees keys 13..20
    s = (q[0, 20, 0] @ k[0, 13:21, 0].T) / 2.0
    want = jax.nn.softmax(s) @ v[0, 13:21, 0]
    np.testing.assert_allclose(np.asarray(got[0, 20, 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_ring_refuses_the_new_kinds_loudly():
    mesh = make_mesh(MeshConfig(dp=2, tp=1, sp=4))
    q = jnp.zeros((2, 64, 2, 8))
    seg = jnp.ones((2, 64), jnp.int32)
    with pytest.raises(NotImplementedError, match="no window"):
        ring_self_attention(q, q, q, mesh, window=16)
    with pytest.raises(NotImplementedError, match="packed documents"):
        ring_self_attention(q, q, q, mesh, q_seg=seg, k_seg=seg)


# --- the selective scan -----------------------------------------------------------

def naive_scan(x, dt, a, b, c, d, first):
    s = np.zeros(a.shape)
    out = np.zeros(x.shape)
    for t in range(len(x)):
        if first[t]:
            s[:] = 0.0
        s = np.exp(dt[t][:, None] * a) * s + (dt[t] * x[t])[:, None] * b[t]
        out[t] = s @ c[t] + d * x[t]
    return out


def scan_inputs(t=96, width=12, n=4, seed=0):
    r = np.random.default_rng(seed)
    first = np.zeros(t, bool)
    first[[0, 17, 64, 65]] = True
    return (r.normal(size=(t, width)), r.uniform(0.01, 0.2, (t, width)),
            -r.uniform(0.5, 2.0, (width, n)), r.normal(size=(t, n)),
            r.normal(size=(t, n)), r.normal(size=(width,)), first)


def test_selective_scan_matches_the_recurrence_and_resets():
    args = scan_inputs()
    want = naive_scan(*args)
    for chunk in (96, 32, 8):
        got = selective_scan(*map(jnp.asarray, args), chunk=chunk)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)
    with pytest.raises(ValueError, match="whole chunks"):
        selective_scan(*map(jnp.asarray, args), chunk=36)


def test_selective_scan_gradients_do_not_depend_on_the_chunk():
    args = [jnp.asarray(a) for a in scan_inputs(seed=1)]

    def grads(chunk):
        f = lambda x, dt, a, b, c, d: jnp.sum(
            selective_scan(x, dt, a, b, c, d, args[6], chunk=chunk) ** 2)
        return jax.grad(f, argnums=(0, 1, 2, 3, 4, 5))(*args[:6])

    for g1, g2 in zip(grads(96), grads(16)):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                                   atol=1e-5)


def test_causal_conv_reads_zero_across_a_document_start():
    x = jnp.arange(1.0, 9.0)[:, None]
    w = jnp.ones((4, 1))
    seg = jnp.asarray([1, 1, 1, 1, 1, 2, 2, 2])
    got = causal_conv1d(x, w, jnp.zeros((1,)), seg)[:, 0]
    assert got.tolist() == [1, 3, 6, 10, 14, 6, 13, 21]


# --- tokens and packing ---------------------------------------------------------------

@pytest.fixture(scope="module")
def trace():
    return simulate_trace(SimConfig(duration_sec=60.0, num_target_files=10,
                                    benign_rate_hz=30.0, seed=3))


def test_tokenizer_ids_lie_in_the_held_slice(trace):
    ids = tokenize_events(trace, 25008)
    assert ids.dtype == np.int32 and len(ids) > 500
    assert ids.min() >= 0 and ids.max() < 25008
    assert len(np.unique(ids)) > 20
    assert (ids == tokenize_events(trace, 25008)).all()
    small = tokenize_events(trace, 512)
    assert small.max() < 512


def test_documents_are_heavy_tailed_and_clipped():
    rng = np.random.default_rng(0)
    docs = cut_documents(np.arange(400_000), rng, median=1024, sigma=1.0,
                         shortest=64, longest=8192)
    lens = np.array([len(d) for d in docs])
    assert lens.min() >= 64 and lens.max() <= 8192
    assert 800 < np.median(lens) < 1300 and lens.max() > 4 * np.median(lens)
    # consecutive: nothing lost between documents
    assert np.concatenate(docs)[:1000].tolist() == list(range(1000))


def test_first_fit_packing_and_its_counters():
    from nerrf_tpu.observability import DEFAULT_REGISTRY
    from nerrf_tpu.tracing import DEFAULT_TRACER

    docs = [np.full(n, i + 1, np.int32)
            for i, n in enumerate([60, 50, 40, 30, 100, 10])]
    before = DEFAULT_REGISTRY.value("stream_tokens_total")
    tokens, segments, waste = pack_documents(docs, 100, 3)
    # first fit: 60+40 | 50+30+10 | 100
    assert [sorted(set(r[r > 0].tolist())) for r in tokens] == [
        [1, 3], [2, 4, 6], [5]]
    assert segments[0].tolist() == [1] * 60 + [2] * 40
    assert segments[1].tolist() == [1] * 50 + [2] * 30 + [3] * 10 + [0] * 10
    assert waste == pytest.approx(10 / 300)
    assert DEFAULT_REGISTRY.value("stream_pack_waste_fraction") == \
        pytest.approx(10 / 300)
    assert DEFAULT_REGISTRY.value("stream_tokens_total") - before == 290
    span = [s for s in DEFAULT_TRACER.records() if s.name == "stream_pack"][-1]
    assert span.args["documents"] == 6
    assert span.args["waste"] == pytest.approx(10 / 300)
    with pytest.raises(ValueError, match="sequences"):
        pack_documents(docs, 100, 4)


def test_build_packed_streams_is_seeded(trace):
    pack = PackConfig(seq_len=256, num_seqs=4, doc_median=64.0, doc_min=16,
                      seed=5)
    a, waste = build_packed_streams([trace], 512, pack)
    b, _ = build_packed_streams([trace], 512, pack)
    assert a["tokens"].shape == a["segments"].shape == (4, 256)
    assert (a["tokens"] == b["tokens"]).all() and 0 <= waste < 0.2
    # documents are numbered 1, 2, ... along a sequence; padding is 0, last
    row = a["segments"][0]
    steps = np.diff(row[row > 0])
    assert set(steps.tolist()) <= {0, 1}
    assert (row[np.argmax(row == 0):] == 0).all() or (row > 0).all()


# --- the experiment's path -----------------------------------------------------------------

def test_train_run_trains_a_stream_experiment(tmp_path):
    """`python -m nerrf_tpu.train.run --experiment <file>` on a toy copy of
    the stream experiment: corpus -> tokens -> packed -> the cached, traced,
    scheduled step with `make_tx`; the loss falls; a checkpoint that names
    its kinds is written."""
    import dataclasses

    from nerrf_tpu.compilecache import CompileCache
    from nerrf_tpu.config import EXPERIMENTS, CorpusConfig, Experiment
    from nerrf_tpu.tracing import DEFAULT_TRACER
    from nerrf_tpu.train.run import run_experiment

    exp = EXPERIMENTS["stream-phi4-mini-flash"]
    assert exp.stream.kinds == layer_kinds(6) and exp.stream.dim == 2560
    toy = dataclasses.replace(
        exp, name="stream-toy",
        corpus=CorpusConfig(num_traces=2, duration_sec=60.0,
                            num_target_files=10, benign_rate_hz=30.0,
                            eval_fraction=0.0),
        train=dataclasses.replace(exp.train, num_steps=12, warmup_steps=2,
                                  learning_rate=3e-3, eval_every=4),
        stream=StreamConfig(**TOY),
        stream_data=PackConfig(seq_len=256, num_seqs=4, doc_median=64.0,
                               doc_min=16))
    path = toy.save(tmp_path / "stream-toy.json")
    assert Experiment.load(path) == toy
    calls = len([s for s in DEFAULT_TRACER.records()
                 if s.name == "train_step_call"])
    report = run_experiment(str(path), tmp_path / "out",
                            compile_cache=CompileCache(
                                root=str(tmp_path / "aot")))
    assert report["gates"] == {"loss_fell": True}
    assert report["loss"]["last"] < report["loss"]["first"]
    assert report["metrics"]["tokens_per_sec"] > 0
    spans = [s.name for s in DEFAULT_TRACER.records()]
    assert spans.count("train_step_call") - calls == 12
    assert "compile_resolve" in spans and "stream_tokenize" in spans
    meta = json.loads((tmp_path / "out" / "model" /
                       "stream_config.json").read_text())
    assert meta["stream"]["kinds"] == list(layer_kinds(6))
    assert meta["stream"]["vocab_size"] == 512
