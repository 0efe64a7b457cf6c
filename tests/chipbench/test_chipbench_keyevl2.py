"""The configuration `keye-vl2-30b-a3b` and its cell `stream-lm-8k-longdoc`:
the configuration file against the published one, the experiment file
against it, required work counted by hand, the program against the plain
reference at a toy width (loss, every gradient leaf, the shares of one
expert layer, the exact selection), the cell's command line rehearsed on
the CPU, and the control and the planted fault coming out not `correct`."""

import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, rehearse, run
from chipbench.reference import keyevl2 as ref
from chipbench.traffic import stream_resident as sr
from chipbench.traffic import stream_sparse_resident as ssr
from chipbench.work import keyevl2 as work

ROOT = Path(__file__).resolve().parents[2]
CELL = "stream-lm-8k-longdoc"
NAME = "keye-vl2-30b-a3b"

# https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json
# as the architectures' catalog holds it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}

# one toy the tests here share: two layers at hidden 64, 16 experts of which
# the four from 4 on are held, 32 keys a query, float32 so that the
# comparison with the reference is tight
TOY = {
    "config": {"hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 16,
               "moe_intermediate_size": 32, "vocab_size": 512,
               "num_hidden_layers": 2, "num_local_experts": 16,
               "num_experts": 4, "first_expert": 4, "num_experts_per_tok": 4,
               "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                             "topk": 32},
               "dtype": "float32",
               "corpus": {"duration_sec": 60.0, "num_target_files": 10,
                          "benign_rate_hz": 20.0}},
    "cell": {"seq_len": 256, "num_seqs": 4, "traces": 2, "corpus_seed": 11,
             "doc_median": 96.0, "doc_sigma": 1.0, "doc_min": 16,
             "table_rows": 4, "in_flight": 2, "trace_seconds": 1.0,
             "seq_cost": None,      # the mix's is measured for its 32
             "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3,
                        "grad_gap_mean": 1e-3, "update_gap": 1e-2,
                        "update_gap_mean": 1e-3, "grad_diff": 2e-3,
                        "grad_diff_mean": 1e-3, "update_diff": 5e-2,
                        "update_diff_mean": 1e-2}},
    "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e11,
              "hbm_bytes": 1 << 34},
}


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several query blocks, two key spans and several tiles an expert at
    the toy's 256 tokens."""
    from nerrf_tpu.ops import dsa, moe

    monkeypatch.setattr(dsa, "QUERY_BLOCK", 64)
    monkeypatch.setattr(dsa, "KEY_SPAN", 128)
    monkeypatch.setattr(moe, "TILE", 16)


@pytest.fixture(scope="module")
def full():
    return json.loads((ROOT / f"chipbench/configs/{NAME}.json").read_text())


@pytest.fixture(scope="module")
def toy_config(full):
    return rehearse.merge(full, TOY["config"])


@pytest.fixture(scope="module")
def toy_cell():
    _, _, cell, _ = run.load_cell(CELL)
    return rehearse.merge(cell, TOY["cell"])


@pytest.fixture(scope="module")
def toy_data(toy_config, toy_cell):
    arrays, waste = sr.make_sequences(toy_config, toy_cell)
    table = sr.make_order_table(
        2_200_000_321, 4, ssr.sequence_costs(toy_config, {}, arrays["segments"]))
    return arrays, table, waste


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


# --- the configuration file ----------------------------------------------------

def test_configuration_holds_the_published_keys(full):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert entry["source"] == ("https://huggingface.co/Kwai-Keye/"
                               "Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert len(full["source"]) <= 200
    differs = {k for k, v in PUBLISHED.items() if full.get(k, "absent") != v}
    assert differs == set(entry["reduced"]) == set(full["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key, cut in full["reduced"].items():
        assert cut["published"] == PUBLISHED[key] and cut["held"] == full[key]
        assert cut["why"]
    # the guide's floors: 4 layers, 8 experts, an eighth of the vocabulary
    assert full["num_hidden_layers"] == 6 >= 4
    assert full["num_experts"] == 16 >= 8 and full["first_expert"] == 0
    assert full["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert full["num_experts"] * 8 == full["num_local_experts"] == 128
    assert set(full["assumed"]) >= {
        "qk_norm", "rope", "indexer_key_norm", "indexer_rope",
        "indexer_weight_scale", "chunk_sizes", "indexer_loss",
        "router_aux_loss", "biases"}
    assert all(v["why"] for v in full["assumed"].values())
    assert full["assumed"]["indexer_weight_scale"]["value"] == (16 * 64) ** -0.5
    assert "float32 parameters" in full["precision"]
    assert "v5e-8" in full["deployment"] and full["model"] == "keyevl2"


def test_the_cut_holds_659_million_parameters(full):
    # a layer outside its experts: q, k, v, o 18,874,368; the indexer's
    # three 2,260,992; the router 262,144; norms 2 x 2048 + 2 x 128 + 2 x 64
    outside = (2048 * 4096 * 2 + 2 * 2048 * 512) + 2048 * (1024 + 64 + 16) \
        + 2048 * 128 + 4480
    assert outside == 21_401_984
    layer = outside + 16 * 3 * 2048 * 768
    assert layer == 96_899_456
    total = 6 * layer + 2 * 18992 * 2048 + 2048
    assert total == 659_190_016
    assert ref.count_params(full) == work.count_params(full) == total
    assert total * 16 / 16.91e9 == pytest.approx(0.62, abs=0.005)


def test_experiment_file_equals_the_benchmarks_configuration(full):
    from nerrf_tpu.config import EXPERIMENTS, Experiment, to_dict

    exp = EXPERIMENTS["stream-keye-vl2-30b-a3b"]
    assert Experiment.load(
        ROOT / "configs/stream-keye-vl2-30b-a3b.json") == exp
    assert ssr.stream_config_of(full) == exp.stream
    assert exp.stream.stack == ("dsa_moe",) * 6 and not exp.stream.tie_head
    assert sr.train_config_of(full, 1) == exp.train
    assert full["corpus"] == to_dict(exp.corpus)
    _, entry, cell, _ = run.load_cell(CELL)
    assert entry["traffic"] == "longdoc-t8192-b1-q2" and entry["chips"] == 1
    assert cell["generator"] == "stream_sparse_resident"
    pack = to_dict(exp.stream_data)
    for key in ("seq_len", "num_seqs", "doc_median", "doc_sigma", "doc_min"):
        assert cell[key] == pack[key]
    assert (cell["batch"], cell["in_flight"], cell["seq_len"],
            cell["num_seqs"], cell["traces"], cell["corpus_seed"],
            cell["table_rows"]) == (1, 2, 8192, 32, 6, 20261003, 64)
    assert (cell["doc_median"], cell["doc_sigma"], cell["doc_min"]) == (
        16384.0, 0.5, 2048)


def test_the_mix_fixes_the_weights_seed_and_the_seed_draws_the_order(
        toy_config, toy_data):
    """The held experts' load follows the weights' draw, so the weights are
    the cell's (like the corpus); ``--seed`` draws the order."""
    _, _, cell, _ = run.load_cell(CELL)
    assert cell["weights_seed"] == 20261003 == cell["corpus_seed"]
    assert ssr.weights_seed_of(cell, 7) == ssr.weights_seed_of(cell, 8)
    assert ssr.weights_seed_of({}, 7) == 7
    arrays, _, _ = toy_data
    costs = ssr.sequence_costs(toy_config, {}, arrays["segments"])
    assert len(costs) == 4 and min(costs) > 0
    # the mix's measured seconds order the pairs where it has them
    assert len(cell.get("seq_cost") or [0] * 32) == cell["num_seqs"] == 32
    assert ssr.sequence_costs(toy_config, {"seq_cost": [3, 1, 2, 4]},
                              arrays["segments"]) == [3.0, 1.0, 2.0, 4.0]
    with pytest.raises(RuntimeError, match="every resident sequence"):
        ssr.sequence_costs(toy_config, {"seq_cost": [1.0]}, arrays["segments"])
    a, b = (sr.make_order_table(s, 4, costs) for s in (3_300_000_001,
                                                       3_300_000_004))
    assert sorted(a.ravel().tolist()) == sorted(b.ravel().tolist())
    same = lambda x, y: all(
        (p == q).all() for p, q in zip(jax.tree_util.tree_leaves(x),
                                       jax.tree_util.tree_leaves(y)))
    w = [sr.make_weights(toy_config, ssr.weights_seed_of(cell, s))
         for s in (1, 2)]
    assert same(*w) and not same(w[0], sr.make_weights(toy_config, 1))


# --- required work, counted by hand --------------------------------------------

def test_required_work_by_hand(full):
    d = work.shapes_of(full)
    assert work.projection_flops_per_token(d) == 2 * 2048 * (
        4096 + 512 + 512 + 4096 + 1024 + 64 + 16 + 128)
    assert work.index_flops_per_pair(d) == 2 * 16 * 64
    assert work.attention_flops_per_pair(d) == 16_384
    assert work.expert_flops_per_assignment(d) == 3 * 2 * 2048 * 768
    assert work.head_flops_per_token(d) == 2 * 2048 * 18992
    # one unpacked document of 8192 tokens: 14.7 M selected of 33.5 M pairs
    seg = np.ones((1, 8192), np.int32)
    one = work.packing_of(seg, 2048)
    assert one == {"tokens": 8192.0, "pairs_full": 8192 * 8193 / 2,
                   "pairs_selected": 2048 * 2049 / 2 + 6144 * 2048}
    assert one["pairs_selected"] / 1e6 == pytest.approx(14.68, abs=0.01)
    flops = work.train_flops(full, one)
    tera = {k: v / 1e12 for k, v in flops.items()}
    assert tera["projections"] == pytest.approx(6.31, abs=0.01)
    assert tera["moe_experts"] == pytest.approx(1.39, abs=0.01)   # even split
    assert tera["lm_head"] == pytest.approx(1.91, abs=0.01)
    assert tera["sparse_attention"] == pytest.approx(
        14.68e6 * 16384 * 3 * 6 / 1e12, rel=1e-3)
    assert tera["dsa_indexer"] == pytest.approx(
        2048 * 6 * (33.56e6 + 2 * 14.68e6) / 1e12, rel=1e-3)
    assert flops["total"] == sum(v for k, v in flops.items() if k != "total")
    assert 14.5 < tera["total"] < 15.0
    # the counted assignments replace the even split
    counted = work.train_flops(full, dict(one, assignments=9000.0))
    assert counted["moe_experts"] == 3 * 9_437_184 * 6 * 9000.0
    moved = work.train_work(full, dict(one, assignments=8192.0))
    assert moved["moe_experts"]["bytes"] == 3 * 6 * 2 * (
        2 * 8192 * 2048 + 16 * 3 * 2048 * 768)
    assert moved["sparse_attention"]["bytes"] == 3 * 6 * 2 * 8192 * 72 * 128
    assert moved["dsa_indexer"]["bytes"] == 3 * 6 * 4 * 8192 * 1104
    assert moved["sparse_attention"]["groups"] == ["dsa_attention"]
    from chipbench import roofline

    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())
    v5e = next(v for k, v in peaks.items() if not k.startswith("_"))
    assert {k: roofline.least_seconds(w["flops"], w["bytes"], v5e)[1]
            for k, w in moved.items()} == {
        "moe_experts": "flops", "sparse_attention": "flops",
        "dsa_indexer": "flops"}


def test_packing_counts_selected_pairs_and_scopes_are_told_apart():
    seg = np.array([[1, 1, 1, 2, 2, 0], [1, 1, 1, 1, 1, 1]])
    got = work.packing_of(seg, topk=2)
    assert got["tokens"] == (3 + 2 + 6) / 2
    assert got["pairs_full"] == (6 + 3 + 21) / 2
    assert got["pairs_selected"] == ((3 + 2) + (3 + 0) + (3 + 4 * 2)) / 2
    groups = [g for g, _ in work.SCOPE_GROUPS]
    # the indexer's loss is told apart before the indexer, the combine
    # (inside the experts' walk) before the experts, every scope before its
    # layer's
    assert groups.index("dsa_indexer_loss") < groups.index("dsa_indexer")
    assert groups.index("moe_combine") < groups.index("moe_experts")
    assert max(groups.index(g) for g in groups if g.startswith(
        ("dsa_", "moe_"))) < groups.index("stream_layer")
    assert set(sum(work.ROOFLINES.values(), [])) <= set(groups)


# --- the new ops against brute force --------------------------------------------------

def test_exact_topk_against_brute_force_selection():
    """Documents shorter and longer than ``topk``, ties at zero, padding."""
    from nerrf_tpu.ops import dsa

    rng = np.random.default_rng(3)
    t, topk = 192, 24
    seg = np.concatenate([np.full(10, 1), np.full(100, 2), np.full(60, 3),
                          np.zeros(22)]).astype(np.int32)
    qi = rng.standard_normal((t, 4, 8)).astype(np.float32)
    ki = rng.standard_normal((t, 8)).astype(np.float32)
    wi = rng.standard_normal((t, 4)).astype(np.float32)
    wi[50:60] = 0.0          # rows whose scores are all +0 or -0: ties
    got = np.asarray(dsa.selection(jnp.asarray(qi), jnp.asarray(ki),
                                   jnp.asarray(wi), jnp.asarray(seg),
                                   topk=topk, block=64))
    scores = np.einsum("tj,tjs->ts", wi, np.maximum(
        np.einsum("tje,se->tjs", qi, ki), 0.0))
    for q in range(t):
        allowed = [s for s in range(q + 1) if seg[s] == seg[q]]
        want = sorted(allowed, key=lambda s: (-scores[q, s], s))[:topk]
        assert sorted(np.flatnonzero(got[q])) == sorted(want), q
    counts = got.sum(axis=1)
    pos = np.asarray(dsa.doc_positions(jnp.asarray(seg)))
    # positions restart at each document; a query keeps min(t + 1, topk)
    assert pos[:12].tolist() == list(range(10)) + [0, 1]
    assert pos[110] == 0 and pos[170] == 0 and pos[191] == 21
    assert (counts == np.minimum(pos + 1, topk)).all()
    # the reference's sort-based selection says the same
    theirs = ref.top_by_sort(
        jnp.asarray(scores), jnp.asarray(
            (np.arange(t)[None] <= np.arange(t)[:, None])
            & (seg[:, None] == seg[None])), topk)
    assert (np.asarray(theirs) == got).all()


def test_sparse_attention_equals_masked_dense_attention():
    """Blocks, spans and the `lax.switch` over key lengths change nothing:
    the op against softmax over the explicit mask, and its KL."""
    from nerrf_tpu.ops import dsa

    rng = np.random.default_rng(5)
    t, topk = 256, 32
    seg = jnp.asarray(np.concatenate([np.full(20, 1), np.full(150, 2),
                                      np.full(86, 3)]).astype(np.int32))
    n = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    q, k, v = n(t, 4, 16), n(t, 2, 16), n(t, 2, 16)
    qi, ki, wi = n(t, 4, 8), n(t, 8), n(t, 4)
    o, kl, pairs = dsa.sparse_attention(q, k, v, qi, ki, wi, seg, topk=topk,
                                        block=32, span=64)
    mask = dsa.selection(qi, ki, wi, seg, topk=topk)
    assert int(pairs) == int(mask.sum())
    kk, vv = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q, kk) / 4.0
    soft = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
    want = jnp.einsum("hqk,khd->qhd", soft, vv)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    target = soft.mean(0)
    index = dsa.index_scores(qi, ki, wi)
    log_q = jax.nn.log_softmax(jnp.where(mask, index, -1e30), axis=-1)
    want_kl = jnp.sum(jnp.where(mask, jax.scipy.special.xlogy(
        target, target) - target * log_q, 0.0))
    assert float(kl) == pytest.approx(float(want_kl), rel=1e-5)
    with pytest.raises(ValueError, match="whole multiples"):
        dsa.sparse_attention(q, k, v, qi, ki, wi, seg, topk=topk, block=48)


def test_the_shares_of_one_expert_layer_add_up_to_the_uncut_layer(toy_config):
    """Four chips' shares (experts 0-3, 4-7, 8-11, 12-15) of the program's
    expert layer, each routed over all 16, add up to what the reference
    gives with all 16 held; gradients through the hand-written backward
    pass equal autodiff of the reference's."""
    from nerrf_tpu.ops import moe

    c = dict(ref.dims(toy_config), held=16, first=0)
    rng = np.random.default_rng(7)
    n = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    z = n(200, 64)
    p = {"router": {"kernel": n(64, 16) * 0.3}, "w_gate": n(16, 64, 32) / 8,
         "w_up": n(16, 64, 32) / 8, "w_down": n(16, 32, 64) / 6}

    def whole(z, p):
        return ref.experts(p, z, ref.routing(p, z, c, "f32"), c, "f32")

    def share(z, p, first):
        cut = lambda w: w[first:first + 4]
        y, counts = moe.moe_share(
            z, z @ p["router"]["kernel"], cut(p["w_gate"]), cut(p["w_up"]),
            cut(p["w_down"]), k=4, first=first)
        return y, counts

    with jax.default_matmul_precision("highest"):
        want = whole(z, p)
        parts = [share(z, p, first) for first in (0, 4, 8, 12)]
        got = sum(y for y, _ in parts)
        assert _rel(got, want) < 1e-5
        # every token's 4 assignments land on exactly one chip each
        assert sum(int(c.sum()) for _, c in parts) == 200 * 4
        assert _rel(parts[1][0], want) > 0.1
        cot = n(200, 64)
        g_want = jax.grad(lambda z, p: jnp.sum(whole(z, p) * cot),
                          argnums=(0, 1))(z, p)
        g_got = jax.grad(lambda z, p: sum(
            jnp.sum(share(z, p, f)[0] * cot) for f in (0, 4, 8, 12)),
            argnums=(0, 1))(z, p)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree_util.tree_leaves(g_want)):
        assert _rel(a, b) < 1e-4, jax.tree_util.keystr(path)


def test_dispatch_plan_groups_every_held_assignment_once():
    from nerrf_tpu.ops import moe

    rng = np.random.default_rng(9)
    experts = jnp.asarray(np.stack([rng.permutation(16)[:4]
                                    for _ in range(100)]).astype(np.int32))
    plan = moe.dispatch_plan(experts, first=4, held=4, tile=8)
    src, dest = np.asarray(plan.src), np.asarray(plan.dest)
    assert src.shape == (moe.num_tiles(100, 4, 4, 8), 8)
    here = (np.asarray(experts) >= 4) & (np.asarray(experts) < 8)
    assert int(plan.counts.sum()) == here.sum() == (src < 100).sum()
    # a tile's indices are sorted and unique: its tokens, then its own
    # empty rows
    assert (np.diff(src, axis=1) > 0).all() and src.max() < 100 + 8
    assert (dest[~here] == src.size).all()
    for t, k in zip(*np.nonzero(here)):
        row = dest[t, k]
        assert src.ravel()[row] == t
        assert int(plan.tile_expert[row // 8]) == int(experts[t, k]) - 4
    assert int(plan.tiles_used) == int(np.ceil(
        np.asarray(plan.counts) / 8).sum())


# --- the program against the reference, toy width ---------------------------------

def test_program_loss_and_gradients_match_the_reference(toy_config, toy_data):
    from nerrf_tpu.models.stream import StreamNet
    from nerrf_tpu.train.stream import make_stream_loss_fn

    arrays, _, _ = toy_data
    tok, seg = (jnp.asarray(arrays[k][:2]) for k in ("tokens", "segments"))
    assert len(np.unique(np.asarray(seg[0]))) >= 2     # packed documents
    scfg = ssr.stream_config_of(toy_config)
    model = StreamNet(scfg)
    params = ref.make_params(toy_config, jax.random.PRNGKey(1))
    own = model.init(jax.random.PRNGKey(0), tok, seg)["params"]
    assert jax.tree_util.tree_map(jnp.shape, params) == \
        jax.tree_util.tree_map(jnp.shape, own)
    loss_fn = make_stream_loss_fn(model)
    batch = {"tokens": tok, "segments": seg}
    with jax.default_matmul_precision("highest"):
        (lp, aux), gp = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
            params, batch, jax.random.PRNGKey(2))
    fn = ref.make_loss_and_grad(toy_config)
    lr, gr = fn(params, tok, seg)
    assert float(lp) == pytest.approx(float(lr), rel=2e-6)
    assert float(aux["index_loss"]) > 0.01      # the indexer's term is there
    assert float(aux["token_loss"]) == pytest.approx(
        float(lp) - float(aux["index_loss"]), rel=1e-5)
    gap = jax.tree_util.tree_map(_rel, gp, gr)
    worst = max(jax.tree_util.tree_leaves_with_path(gap),
                key=lambda kv: kv[1])
    assert worst[1] < 1e-4, worst
    # every leaf moves, the indexer's and the router's among them
    norms = jax.tree_util.tree_map(lambda g: float(jnp.linalg.norm(g)), gr)
    assert min(jax.tree_util.tree_leaves(norms)) > 0
    # both sides counted the same selection and the same routing
    assert float(aux["selected_pairs"]) == sum(
        int(x) for x in fn.stats["selected_pairs"])
    assert float(aux["held_assignments"]) == sum(
        int(x.sum()) for x in fn.stats["held_assignments"])
    assert 0 < float(aux["selected_pairs"]) < float(aux["allowed_pairs"])


def test_reference_gradient_a_layer_at_a_time_equals_autodiff(toy_config,
                                                              toy_data):
    arrays, _, _ = toy_data
    tok, seg = (jnp.asarray(arrays[k][:2]) for k in ("tokens", "segments"))
    params = ref.make_params(toy_config, jax.random.PRNGKey(3))
    c = ref.dims(toy_config)
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda p: ref.batch_loss(p, tok, seg, c)))(params)
    got_l, got_g = ref.make_loss_and_grad(toy_config)(params, tok, seg)
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-6)
    assert jax.tree_util.tree_structure(got_g) == \
        jax.tree_util.tree_structure(want_g)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree_util.tree_leaves(want_g)):
        assert _rel(a, b) < 1e-4, jax.tree_util.keystr(path)


def test_program_and_reference_choose_alike(toy_config, toy_data):
    arrays, table, _ = toy_data
    alike = ssr.chosen_alike(toy_config, arrays, table, 2_200_000_321)
    assert len(alike["same_experts_share"]) == 2
    assert min(alike["same_experts_share"]) > 0.99
    assert min(alike["same_keys_share"]) > 0.99


# --- the cell's command line, rehearsed ------------------------------------------------

@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    toy = copy.deepcopy(TOY)
    toy["cache_root"] = str(tmp_path_factory.mktemp("aot"))
    return toy


@pytest.mark.parametrize("trace", (0, 1))
def test_command_line_prints_the_contracts_last_line(toy, capsys, trace):
    rc = run.main(["--workload", CELL, "--seed", "2200000321", "--seconds",
                   "0.5", "--trace", str(trace)], rehearsal=toy)
    assert rc == 0
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert list(res)[:4] == ["correct", "attempted", "failed", "metrics"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1 and res["rehearsal"] is True
    # the three AdamW steps: losses, first gradient, parameters' change
    assert set(res["compared"]) == {
        "loss_gap.1", "loss_gap.2", "loss_gap.3", "grad_gap",
        "grad_gap_mean", "update_gap", "update_gap_mean", "grad_diff",
        "grad_diff_mean", "update_diff", "update_diff_mean"}
    assert all(v <= lim for v, lim in res["compared"].values())
    extras = res["extras"]
    assert len(extras["routed"]) == 3 and len(extras["same_keys_share"]) == 2
    assert extras["packing"]["assignments"] == pytest.approx(np.mean(
        [r["held_assignments"] for r in extras["routed"]]) / 2)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if not trace:
        assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
        return
    new = ["moe_experts_roofline.train", "sparse_attention_roofline.train",
           "dsa_indexer_roofline.train", "dsa_topk_ms.train",
           "moe_dispatch_ms.train", "moe_load_imbalance.train"]
    # this cell's entries, by name: others may be appended, and may list it
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in new:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "train_windows_per_s"
        assert res["metrics"][name]["value"] > 0, name
    assert res["metrics"]["moe_load_imbalance.train"]["value"] >= 1.0
    # the readers that list every cell, or none, read here too
    for name in ("step_mfu.train", "host_dispatch_ms.train",
                 "device_idle_share.train"):
        assert res["metrics"][name]["value"] > 0, name
    assert res["metrics"]["setup_timeline_jit_s.train"]["value"] >= 0
    assert res["metrics"]["compiles_in_window.train"]["value"] == 0
    # no metric whose list leaves this cell out is read here
    assert not {m["name"] for m in bench["per_layer"]
                if CELL not in m.get("workloads", [CELL])} & set(res["metrics"])
    groups = dict(res["breakdown"]["device_ops"])
    assert groups["scope:dsa_attention"] > 0
    scope_s = extras["scope_s"]
    for g in ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
              "dsa_indexer", "dsa_topk", "dsa_attention", "dsa_indexer_loss",
              "lm_head"):
        assert scope_s[g] > 0, g
    assert sum(scope_s.values()) == pytest.approx(extras["leaf_op_s"])


def test_the_programs_registry_holds_the_routing_and_the_selection(toy):
    """`count_sparse` (called where a loop syncs) -> counters and gauges."""
    from nerrf_tpu.models.stream import StreamConfig
    from nerrf_tpu.observability import DEFAULT_REGISTRY as reg
    from nerrf_tpu.train.stream import count_sparse

    scfg = StreamConfig(num_layers=2, kinds=("dsa_moe",) * 2,
                        experts_per_token=4)
    before = {h: reg.value("moe_assignments_total", labels={"held": h})
              for h in ("true", "false")}
    pairs = reg.value("dsa_selected_pairs_total")
    count_sparse({"held_assignments": 500.0, "selected_pairs": 9000.0,
                  "allowed_pairs": 30000.0, "load_max_over_mean": 1.25,
                  "routed_tokens": 256.0}, scfg, steps=2)
    assert reg.value("moe_assignments_total",
                     labels={"held": "true"}) - before["true"] == 1000.0
    assert reg.value("moe_assignments_total", labels={"held": "false"}) \
        - before["false"] == 2 * (256 * 4 * 2 - 500)
    assert reg.value("dsa_selected_pairs_total") - pairs == 18000.0
    assert reg.value("moe_expert_load_max_over_mean") == 1.25
    assert reg.value("dsa_selected_share") == pytest.approx(0.3)
    count_sparse({}, scfg)      # a step of another kind counts nothing


# --- the control and the planted fault -----------------------------------------------------

@pytest.fixture(scope="module")
def sound(toy_config, toy_data):
    arrays, table, _ = toy_data
    return sr.follow_reference(toy_config, arrays, table, 2_200_000_321)


@pytest.mark.parametrize("kwargs", [
    {"precision": "fp8"}, {"fault": "renormalise_over_held"}])
def test_control_and_planted_fault_come_out_not_correct(
        toy_config, toy_data, sound, kwargs):
    """The reference in the program's place, held against the f32 reference
    by the comparison and THE CELL'S OWN LIMITS: in per-tensor fp8 and with
    the routing weights renormalised over the held experts only it fails a
    limit.  (The bfloat16 control of the other stream cell is no yardstick
    here: it rounds the index scores and the router's logits too, which the
    program keeps in float32 so that its choices are the reference's.)"""
    arrays, table, _ = toy_data
    limits = run.load_cell(CELL)[2]["limits"]
    other = sr.follow_reference(toy_config, arrays, table, 2_200_000_321,
                                **kwargs)
    numbers = sr.compare_all(other, sound)
    got, table_, _ = compare.verdict(numbers, limits)
    assert got is False, (kwargs, table_)
    print(kwargs, table_)


def test_the_reference_held_against_itself_reads_zero(sound):
    limits = run.load_cell(CELL)[2]["limits"]
    same, table_, _ = compare.verdict(sr.compare_all(sound, sound), limits)
    assert same and all(v == 0 for v, _ in table_.values())
    with pytest.raises(ValueError, match="unknown fault"):
        ref.routing({"router": {"kernel": jnp.ones((4, 16))}},
                    jnp.ones((2, 4)), {"K": 2, "E": 16, "first": 0,
                                       "held": 4}, "f32", fault="other")
