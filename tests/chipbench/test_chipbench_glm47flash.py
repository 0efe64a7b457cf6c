"""The configuration `glm-4.7-flash` and its cell `stream-lm-8k-mla-longdoc`:
the configuration file against the published one, the experiment file
against it, parameters and required work counted by hand, the program
against the plain reference at a toy width (loss, every gradient leaf, three
updates through the rehearsed command line), the shares of one expert layer,
the cell's command line rehearsed on the CPU, and the control and the three
planted faults coming out not `correct`."""

import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, rehearse, run
from chipbench.reference import glm47flash as ref
from chipbench.traffic import stream_mla_resident as smr
from chipbench.traffic import stream_resident as sr
from chipbench.work import glm47flash as work

ROOT = Path(__file__).resolve().parents[2]
CELL = "stream-lm-8k-mla-longdoc"
NAME = "glm-4.7-flash"
NEW_METRICS = ["mla_attention_roofline.train", "mla_latent_roofline.train",
               "moe_shared_roofline.train", "mtp_step_share.train"]
# metrics of another cell's that list this one too (PR 39)
LISTED_METRICS = ["moe_experts_roofline.train", "moe_dispatch_ms.train",
                  "moe_load_imbalance.train", "lm_head_roofline.train"]

# https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json as the
# architectures' catalog holds it
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}

# one toy the tests here share: a dense layer, two expert layers and the MTP
# module at hidden 64, 16 experts of which the four from 4 on are held,
# float32 so that the comparison with the reference is tight
TOY = {
    "config": {"hidden_size": 64, "num_attention_heads": 4,
               "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 12,
               "qk_rope_head_dim": 4, "v_head_dim": 20,
               "intermediate_size": 96, "moe_intermediate_size": 32,
               "vocab_size": 512, "num_hidden_layers": 3,
               "router_experts": 16, "n_routed_experts": 4,
               "first_expert": 4, "num_experts_per_tok": 4,
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
SEED = 3_500_000_321


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several query blocks, two key spans and several tiles an expert at
    the toy's 256 tokens."""
    from nerrf_tpu.ops import mla, moe

    monkeypatch.setattr(mla, "QUERY_BLOCK", 64)
    monkeypatch.setattr(mla, "KEY_SPAN", 128)
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
        SEED, 4, smr.sequence_costs(toy_config, {}, arrays["segments"]))
    return arrays, table, waste


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


# --- the configuration file ----------------------------------------------------

def test_configuration_holds_the_published_keys(full):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert entry["source"] == ("https://huggingface.co/zai-org/"
                               "GLM-4.7-Flash/blob/main/config.json")
    assert len(full["source"]) <= 200 and len(entry["why"]) <= 200
    differs = {k for k, v in PUBLISHED.items() if full.get(k, "absent") != v}
    assert differs == set(entry["reduced"]) == set(full["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, cut in full["reduced"].items():
        assert cut["published"] == PUBLISHED[key] and cut["held"] == full[key]
        assert cut["why"]
    # the guide's floors: four layers after the dense one, 8 experts, an
    # eighth of the vocabulary; the MTP module is kept
    assert full["num_hidden_layers"] - full["first_k_dense_replace"] == 4
    assert full["n_routed_experts"] == 8 and full["first_expert"] == 0
    assert full["n_routed_experts"] * 8 == full["router_experts"] == 64
    assert full["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert full["num_nextn_predict_layers"] == 1
    assert set(full["assumed"]) >= {
        "mtp_loss_weight", "mtp_form", "mtp_targets", "rope", "router_bias",
        "router_aux_loss", "tie_break"}
    assert all(v["why"] for v in full["assumed"].values())
    assert full["assumed"]["mtp_loss_weight"]["value"] == 0.3
    assert full["assumed"]["router_bias"]["value_std"] == ref.BIAS_STD
    assert "float32 parameters" in full["precision"]
    assert "v5e-8" in full["deployment"] and full["model"] == "glm47flash"


def test_the_cut_holds_706_million_parameters(full):
    attention = (2048 * 768 + 768 + 768 * 5120 + 2048 * 576 + 512
                 + 512 * 8960 + 5120 * 2048)
    assert attention == 21_759_232
    dense = attention + 2 * 2048 + 3 * 2048 * 10240
    assert dense == 84_677_888
    outside = attention + 2 * 2048 + 2048 * 64 + 64 + 3 * 2048 * 1536
    assert outside == 31_331_648
    routed = outside + 8 * 3 * 2048 * 1536
    assert routed == 106_829_120
    mtp = routed + 2 * 2048 * 2048 + 3 * 2048
    assert mtp == 115_223_872
    total = dense + 4 * routed + mtp + 2 * 19360 * 2048 + 2048
    assert total == 706_518_848
    assert ref.count_params(full) == work.count_params(full) == total
    assert total * 16 / 16.91e9 == pytest.approx(0.668, abs=0.001)
    # whole, by the same count: 29.94 B and 0.64 B of MTP
    whole = outside + 64 * 3 * 2048 * 1536
    assert (dense + 46 * whole + 2 * 154880 * 2048 + 2048) / 1e9 == \
        pytest.approx(29.94, abs=0.01)


def test_experiment_file_equals_the_benchmarks_configuration(full):
    from nerrf_tpu.config import EXPERIMENTS, Experiment, to_dict

    exp = EXPERIMENTS["stream-glm-4.7-flash"]
    assert Experiment.load(ROOT / "configs/stream-glm-4.7-flash.json") == exp
    assert smr.stream_config_of(full) == exp.stream
    assert exp.stream.stack == ("mla_dense",) + ("mla_moe",) * 4
    assert exp.stream.mtp_layers == 1 and not exp.stream.tie_head
    assert exp.stream.routed_layers == 5
    assert sr.train_config_of(full, 1) == exp.train
    assert full["corpus"] == to_dict(exp.corpus)
    _, entry, cell, _ = run.load_cell(CELL)
    assert entry["traffic"] == "longdoc-mtp-t8192-b1-q2"
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert cell["generator"] == "stream_mla_resident"
    pack = to_dict(exp.stream_data)
    for key in ("seq_len", "num_seqs", "doc_median", "doc_sigma", "doc_min"):
        assert cell[key] == pack[key]
    # the documents of `longdoc-t8192-b1-q2`
    other = json.loads((ROOT / "chipbench/traffic/longdoc-t8192-b1-q2.json"
                        ).read_text())
    for key in ("batch", "seq_len", "num_seqs", "traces", "corpus_seed",
                "doc_median", "doc_sigma", "doc_min", "table_rows",
                "in_flight"):
        assert cell[key] == other[key], key
    assert (cell["batch"], cell["in_flight"], cell["table_rows"]) == (1, 2, 64)


def test_the_seed_makes_the_weights_unless_the_mix_fixes_them(toy_config,
                                                              toy_data):
    from chipbench.traffic import stream_sparse_resident as ssr

    _, _, cell, _ = run.load_cell(CELL)
    fixed = cell.get("weights_seed")
    assert (fixed is None) == (cell.get("seq_cost") is None)
    assert ssr.weights_seed_of(cell, 7) == (7 if fixed is None else fixed)
    arrays, _, _ = toy_data
    costs = smr.sequence_costs(toy_config, {}, arrays["segments"])
    assert costs == [work.packing_of(row[None])["pairs"]
                     for row in arrays["segments"]]
    assert smr.sequence_costs(toy_config, {"seq_cost": [3, 1, 2, 4]},
                              arrays["segments"]) == [3.0, 1.0, 2.0, 4.0]
    with pytest.raises(RuntimeError, match="every resident sequence"):
        smr.sequence_costs(toy_config, {"seq_cost": [1.0]},
                           arrays["segments"])


# --- required work, counted by hand --------------------------------------------

def test_required_work_by_hand(full):
    d = work.shapes_of(full)
    assert work.latent_flops_per_token(d) == 2 * (
        2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048)
    assert work.attention_flops_per_pair(d) == 2 * 20 * (256 + 256) == 20_480
    assert work.shared_flops_per_token(d) == 3 * 2 * 2048 * 1536
    assert work.expert_flops_per_assignment(d) == 3 * 2 * 2048 * 1536
    assert work.dense_flops_per_token(d) == 3 * 2 * 2048 * 10240
    assert work.head_flops_per_token(d) == 2 * 2048 * 19360
    # one unpacked document of 8192 tokens: 33.6 M attending pairs
    seg = np.ones((1, 8192), np.int32)
    one = work.packing_of(seg)
    assert one == {"tokens": 8192.0, "pairs": 8192 * 8193 / 2}
    block = {k: v / 1e12 for k, v in work.block_flops(d, one).items()}
    # a layer forward, as the issue reckons it
    assert block["mla_attention"] == pytest.approx(0.69, abs=0.005)
    assert block["mla_latent"] == pytest.approx(0.36, abs=0.005)
    assert block["moe_shared"] == pytest.approx(0.15, abs=0.005)
    assert block["moe_experts"] == pytest.approx(0.08, abs=0.005)
    flops = work.train_flops(full, one)
    tera = {k: v / 1e12 for k, v in flops.items()}
    assert tera["mla_attention"] == pytest.approx(
        3 * 5 * 33.56e6 * 20480 / 1e12, rel=1e-3)
    assert tera["dense_mlp"] == pytest.approx(3.09, abs=0.01)
    assert tera["lm_head"] == pytest.approx(1.95, abs=0.01)
    assert tera["mtp"] == pytest.approx(
        3 * (sum(block.values()) + 8192 * (2 * 4096 * 2048 + 2 * 2048 * 19360)
             / 1e12), rel=1e-6)
    assert flops["total"] == sum(v for k, v in flops.items() if k != "total")
    # the six cores are the largest single share; the module about a fifth
    assert 29.0 < tera["total"] < 30.5
    assert 12.0 < tera["mla_attention"] + 3 * block["mla_attention"] < 12.8
    assert 0.19 < tera["mtp"] / tera["total"] < 0.23
    counted = work.train_flops(full, dict(one, assignments=9000.0))
    assert counted["moe_experts"] == 3 * 4 * 9000.0 * 3 * 2 * 2048 * 1536
    moved = work.train_work(full, one)
    assert moved["mla_attention"]["bytes"] == 3 * 5 * 2 * 8192 * 20 * (
        2 * 256 + 2 * 256)
    assert moved["moe_shared"]["bytes"] == 3 * 4 * 2 * (
        2 * 8192 * 2048 + 3 * 2048 * 1536)
    assert moved["mla_latent"]["groups"] == ["mla_latent"]
    # the stack's four routed walks at the even split (8192 x 4 x 8 / 64)
    assert moved["moe_experts"]["flops"] == flops["moe_experts"] == \
        3 * 4 * 4096 * 3 * 2 * 2048 * 1536
    assert moved["moe_experts"]["bytes"] == 3 * 4 * 2 * (
        2 * 4096 * 2048 + 8 * 3 * 2048 * 1536)
    # ONE pass of the head: the next-token pass under ``lm_head_loss``; the
    # MTP module's pass is under ``mtp_head_loss``, in ``mtp``
    assert moved["lm_head"]["flops"] == 3 * 8192 * 2 * 2048 * 19360
    assert moved["lm_head"]["bytes"] == 3 * 2 * (8192 + 19360) * 2048
    from chipbench import roofline

    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())
    v5e = next(v for k, v in peaks.items() if not k.startswith("_"))
    assert {k: roofline.least_seconds(w["flops"], w["bytes"], v5e)[1]
            for k, w in moved.items()} == dict.fromkeys(work.ROOFLINES,
                                                        "flops")


def test_packing_counts_pairs_and_scopes_are_told_apart():
    seg = np.array([[1, 1, 1, 2, 2, 0], [1, 1, 1, 1, 1, 1]])
    got = work.packing_of(seg)
    assert got == {"tokens": (3 + 2 + 6) / 2, "pairs": (6 + 3 + 21) / 2}
    groups = [g for g, _ in work.SCOPE_GROUPS]
    # the MTP module whole before its inner scopes, the combine (inside the
    # experts' walk) before the experts, every scope before its layer's
    assert max(groups.index(g) for g in work.MTP_GROUPS) < min(
        groups.index(g) for g in groups if g.startswith(("moe_", "mla_")))
    assert groups.index("moe_combine") < groups.index("moe_experts")
    assert max(groups.index(g) for g in groups if g.startswith(
        ("moe_", "mla_", "dense_"))) < groups.index("stream_layer")
    assert set(sum(work.ROOFLINES.values(), [])) <= set(groups)
    # the head's two passes fall in two groups: the next token's alone in
    # ``lm_head``, which its roofline's one pass of required work holds
    from chipbench.trace_reduce import group_of

    assert group_of(["stream/lm_head_loss/dot"], work.SCOPE_GROUPS) == \
        "lm_head"
    assert group_of(["stream/mtp_head_loss/dot"], work.SCOPE_GROUPS) == \
        "mtp_head"
    assert group_of(["stream/mtp_block/moe_experts/dot"],
                    work.SCOPE_GROUPS) == "mtp_block"
    # the names both expert models share
    from chipbench.work import keyevl2

    shared = {"moe_router", "moe_dispatch", "moe_combine", "moe_experts"}
    assert shared <= set(groups) and shared <= {
        g for g, _ in keyevl2.SCOPE_GROUPS}


# --- the new ops against brute force ----------------------------------------------

def _latent_inputs(rng, t=192, heads=3, nope=8, rot=4, dv=10):
    n = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    seg = jnp.asarray(np.concatenate(
        [np.full(40, 1), np.full(100, 2), np.full(30, 3), np.zeros(22)]
    ).astype(np.int32))
    return n(t, heads, nope + rot), n(t, rot), n(t, heads, nope + dv), seg


def _brute_force(q, k_r, kv, seg, nope, theta):
    """Latent attention with no shared code: per head, per query, a Python
    loop over the allowed keys; the rotation written out pair by pair."""
    q, k_r, kv, seg = (np.asarray(x, np.float64) for x in (q, k_r, kv, seg))
    t, heads, width = q.shape
    rot = width - nope
    pos = np.zeros(t)
    for i in range(1, t):
        pos[i] = pos[i - 1] + 1 if seg[i] == seg[i - 1] else 0

    def turn(x, p):
        out = x.copy()
        for i in range(rot // 2):
            a = p * theta ** (-2.0 * i / rot)
            out[i] = x[i] * np.cos(a) - x[i + rot // 2] * np.sin(a)
            out[i + rot // 2] = x[i + rot // 2] * np.cos(a) + x[i] * np.sin(a)
        return out

    o = np.zeros((t, heads, kv.shape[-1] - nope))
    for h in range(heads):
        for i in range(t):
            keys = [s for s in range(i + 1) if seg[s] == seg[i]]
            qi = np.concatenate([q[i, h, :nope], turn(q[i, h, nope:], pos[i])])
            scores = np.array([qi @ np.concatenate(
                [kv[s, h, :nope], turn(k_r[s], pos[s])]) for s in keys])
            p = np.exp((scores - scores.max()) / np.sqrt(width))
            o[i, h] = (p / p.sum()) @ kv[keys, h, nope:]
    return o


def test_latent_attention_equals_brute_force_and_sums_the_shared_keys_gradient():
    """`ops/mla.py`: assembly, blocks, spans and the hand-written backward
    pass change nothing; the one rotary key's gradient is the sum over the
    heads it serves."""
    from nerrf_tpu.ops import dsa, mla

    q, k_r, kv, seg = _latent_inputs(np.random.default_rng(5))
    nope, theta = 8, 1e4
    pos = dsa.doc_positions(seg)

    def program(q, k_r, kv):
        return mla.attention(*mla.assemble(q, k_r, kv, pos, nope=nope,
                                           theta=theta), seg, block=32,
                             span=64)

    want = _brute_force(q, k_r, kv, seg, nope, theta)
    np.testing.assert_allclose(np.asarray(program(q, k_r, kv)), want,
                               rtol=2e-5, atol=2e-5)

    def dense(q, k_r, kv):
        """jax.numpy against the explicit mask: what autodiff is taken of."""
        qq, kk, vv = mla.assemble(q, k_r, kv, pos, nope=nope, theta=theta)
        idx = jnp.arange(seg.shape[0])
        mask = (idx[None] <= idx[:, None]) & (seg[:, None] == seg[None])
        logits = jnp.einsum("qhd,khd->hqk", qq, kk) / np.sqrt(qq.shape[-1])
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(
            jnp.where(mask, logits, -1e30), axis=-1), vv)

    cot = jnp.asarray(np.random.default_rng(6).standard_normal(
        want.shape).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(program(*a) * cot),
                       argnums=(0, 1, 2))(q, k_r, kv)
        ref_g = jax.grad(lambda *a: jnp.sum(dense(*a) * cot),
                         argnums=(0, 1, 2))(q, k_r, kv)

        def one_head(h):
            # the rotary key as head h alone sees it
            return jax.grad(lambda k: jnp.sum(
                dense(q, k, kv)[:, h] * cot[:, h]))(k_r)

        by_head = sum(one_head(h) for h in range(q.shape[1]))
    for a, b in zip(got, ref_g):
        assert _rel(a, b) < 1e-4
    assert _rel(got[1], by_head) < 1e-4
    assert _rel(one_head(0), by_head) > 0.1
    with pytest.raises(ValueError, match="whole multiples"):
        mla.attention(*mla.assemble(q, k_r, kv, pos, nope=nope, theta=theta),
                      seg, block=48)


def test_the_bias_changes_choices_and_never_weights():
    from nerrf_tpu.ops import moe

    rng = np.random.default_rng(8)
    logits = jnp.asarray(rng.standard_normal((50, 16)).astype(np.float32))
    bias = jnp.asarray((rng.standard_normal(16) * 0.5).astype(np.float32))
    scores = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    weights, experts = moe.route_sigmoid(logits, 4, bias=bias, scale=1.8)
    plain, unbiased = moe.route_sigmoid(logits, 4, bias=jnp.zeros(16),
                                        scale=1.8)
    changed = 0
    for t in range(50):
        order = sorted(range(16),
                       key=lambda e: (-(np.float32(scores[t, e])
                                        + np.float32(bias[e])), e))[:4]
        assert sorted(order) == sorted(np.asarray(experts[t]).tolist()), t
        want = 1.8 * scores[t, np.asarray(experts[t])] / (
            scores[t, np.asarray(experts[t])].sum() + 1e-20)
        np.testing.assert_allclose(np.asarray(weights[t]), want, rtol=1e-5)
        changed += sorted(order) != sorted(np.asarray(unbiased[t]).tolist())
    assert changed > 10        # the bias moves choices
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.8, rtol=1e-5)
    # ... and a bias that moves no choice moves no weight
    same_w, same_e = moe.route_sigmoid(logits, 4, bias=jnp.full(16, 0.3),
                                       scale=1.8)
    assert (np.asarray(same_e) == np.asarray(unbiased)).all()
    np.testing.assert_array_equal(np.asarray(same_w), np.asarray(plain))
    # no gradient reaches it
    g = jax.grad(lambda b: jnp.sum(moe.route_sigmoid(
        logits, 4, bias=b, scale=1.8)[0] ** 2))(bias)
    assert float(jnp.abs(g).max()) == 0.0
    # the reference's router says the same
    c = {"K": 4, "scale": 1.8}
    p = {"router": {"kernel": jnp.eye(16)}, "router_bias": bias}
    theirs, chosen = ref.routing(p, logits, c, "f32")
    mine = np.zeros((50, 16), np.float32)
    np.put_along_axis(mine, np.asarray(experts), np.asarray(weights), axis=1)
    np.testing.assert_allclose(np.asarray(theirs), mine, rtol=1e-5, atol=1e-7)
    assert (np.asarray(chosen) == (mine > 0)).all()


def test_the_shares_of_one_expert_layer_add_up_to_the_uncut_layer(toy_config):
    """Four chips' shares (experts 0-3, 4-7, 8-11, 12-15) of the program's
    routed part, each routed over all 16 by the sigmoid router, plus the
    shared expert ONCE, add up to what the reference gives with all 16
    held; gradients through the hand-written backward pass equal autodiff of
    the reference's."""
    from functools import partial

    from nerrf_tpu.ops import moe

    c = dict(ref.dims(toy_config), held=16, first=0)
    rng = np.random.default_rng(7)
    n = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    z = n(200, 64)
    p = {"router": {"kernel": n(64, 16) * 0.3}, "router_bias": n(16) * 0.3,
         "w_gate": n(16, 64, 32) / 8, "w_up": n(16, 64, 32) / 8,
         "w_down": n(16, 32, 64) / 6,
         "shared_gate": {"kernel": n(64, 32) / 8},
         "shared_up": {"kernel": n(64, 32) / 8},
         "shared_down": {"kernel": n(32, 64) / 6}}

    def shared(z, p):
        return ref.swiglu(z, p["shared_gate"]["kernel"],
                          p["shared_up"]["kernel"],
                          p["shared_down"]["kernel"], "f32")

    def whole(z, p):
        return ref.experts(p, z, ref.routing(p, z, c, "f32")[0], c, "f32") \
            + shared(z, p)

    def share(z, p, first):
        cut = lambda w: w[first:first + 4]
        return moe.moe_share(
            z, z @ p["router"]["kernel"], cut(p["w_gate"]), cut(p["w_up"]),
            cut(p["w_down"]), k=4, first=first, router=partial(
                moe.route_sigmoid, bias=p["router_bias"], scale=c["scale"]))

    def summed(z, p):
        return sum(share(z, p, f)[0] for f in (0, 4, 8, 12)) + shared(z, p)

    with jax.default_matmul_precision("highest"):
        want = whole(z, p)
        parts = [share(z, p, first) for first in (0, 4, 8, 12)]
        assert _rel(summed(z, p), want) < 1e-5
        # every token's 4 assignments land on exactly one chip each
        assert sum(int(c.sum()) for _, c in parts) == 200 * 4
        assert _rel(parts[1][0] + shared(z, p), want) > 0.1
        # counted on every chip, the shared expert would come out 4 x
        assert _rel(sum(y for y, _ in parts) + 4 * shared(z, p), want) > 0.1
        cot = n(200, 64)
        g_want = jax.grad(lambda z, p: jnp.sum(whole(z, p) * cot),
                          argnums=(0, 1))(z, p)
        g_got = jax.grad(lambda z, p: jnp.sum(summed(z, p) * cot),
                         argnums=(0, 1))(z, p)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree_util.tree_leaves(g_want)):
        if "router_bias" in jax.tree_util.keystr(path):
            assert float(jnp.abs(a).max()) == float(jnp.abs(b).max()) == 0.0
        else:
            assert _rel(a, b) < 1e-4, jax.tree_util.keystr(path)


def test_mtp_targets_stop_at_document_boundaries():
    """A packed toy sequence with a boundary inside it: a position carries
    an MTP target only where its next TWO tokens are of its own document."""
    from nerrf_tpu.models.stream import mtp_targets, next_token_targets

    tokens = jnp.asarray([[10, 11, 12, 13, 20, 21, 22, 0, 0]])
    seg = jnp.asarray([[1, 1, 1, 1, 2, 2, 2, 0, 0]])
    two, w2 = mtp_targets(tokens, seg)
    one, w1 = next_token_targets(tokens, seg)
    assert np.asarray(w1[0]).tolist() == [1, 1, 1, 0, 1, 1, 0, 0, 0]
    assert np.asarray(w2[0]).tolist() == [1, 1, 0, 0, 1, 0, 0, 0, 0]
    assert np.asarray(two[0])[[0, 1, 4]].tolist() == [12, 13, 22]
    assert np.asarray(one[0])[[0, 1, 2, 4, 5]].tolist() == [11, 12, 13, 21, 22]
    theirs, w_ref = ref.mtp_targets_of(tokens[0], seg[0])
    assert (np.asarray(theirs) == np.asarray(two[0])).all()
    assert (np.asarray(w_ref) == np.asarray(w2[0])).all()
    shifted, _ = ref.mtp_targets_of(tokens[0], seg[0], "mtp_shift_one")
    assert (np.asarray(shifted) == np.asarray(one[0])).all()


# --- the program against the reference, toy width ---------------------------------

def test_program_loss_and_gradients_match_the_reference(toy_config, toy_data):
    from nerrf_tpu.models.stream import StreamNet
    from nerrf_tpu.train.stream import make_stream_loss_fn

    arrays, _, _ = toy_data
    tok, seg = (jnp.asarray(arrays[k][:2]) for k in ("tokens", "segments"))
    assert len(np.unique(np.asarray(seg[0]))) >= 2     # packed documents
    scfg = smr.stream_config_of(toy_config)
    model = StreamNet(scfg)
    params = ref.make_params(toy_config, jax.random.PRNGKey(1))
    own = model.init(jax.random.PRNGKey(0), tok, seg)["params"]
    assert jax.tree_util.tree_map(jnp.shape, params) == \
        jax.tree_util.tree_map(jnp.shape, own)
    batch = {"tokens": tok, "segments": seg}
    with jax.default_matmul_precision("highest"):
        (lp, aux), gp = jax.jit(jax.value_and_grad(
            make_stream_loss_fn(model), has_aux=True))(
                params, batch, jax.random.PRNGKey(2))
    fn = ref.make_loss_and_grad(toy_config)
    lr, gr = fn(params, tok, seg)
    assert float(lp) == pytest.approx(float(lr), rel=2e-6)
    # both terms are there, each equal to the reference's
    assert float(aux["token_loss"]) == pytest.approx(
        float(fn.stats["token_loss"]), rel=2e-6)
    assert float(aux["mtp_loss"]) == pytest.approx(
        float(fn.stats["mtp_loss"]), rel=2e-6)
    assert float(lp) == pytest.approx(
        float(aux["token_loss"]) + 0.3 * float(aux["mtp_loss"]), rel=1e-6)
    assert 0 < float(aux["mtp_targets"]) < float(jnp.sum(seg > 0))
    gap = jax.tree_util.tree_map(_rel, gp, gr)
    worst = max(jax.tree_util.tree_leaves_with_path(gap),
                key=lambda kv: kv[1])
    assert worst[1] < 1e-4, worst
    # every leaf moves but the correction biases, which no gradient reaches
    for path, g in jax.tree_util.tree_leaves_with_path(gr):
        norm = float(jnp.linalg.norm(g))
        assert (norm == 0) == ("router_bias" in jax.tree_util.keystr(path))
    # the embedding and the head are used twice and their gradients are
    # sums: without the MTP term both come out different
    alone = ref.make_loss_and_grad(rehearse.merge(
        toy_config, {"num_nextn_predict_layers": 0}))
    params_alone = {k: v for k, v in params.items()
                    if not k.startswith("mtp_")}
    _, g_alone = alone(params_alone, tok, seg)
    assert _rel(g_alone["lm_head"], gr["lm_head"]) > 0.05
    assert _rel(g_alone["tok_embed"]["embedding"],
                gr["tok_embed"]["embedding"]) > 0.05
    # both sides counted the same routing
    assert float(aux["held_assignments"]) == sum(
        int(x.sum()) for x in fn.stats["held_assignments"])
    assert len(fn.stats["held_assignments"]) == 2 * scfg.routed_layers


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
    alike = smr.chosen_alike(toy_config, arrays, table, SEED)
    # two routed layers of the stack, then the MTP block
    assert len(alike["same_experts_share"]) == 3
    assert min(alike["same_experts_share"]) > 0.99


def test_the_correction_bias_is_held_fixed_by_program_and_reference(
        toy_config, toy_data):
    """Weight decay moves every other leaf; `make_stream_tx` and the
    reference's `clip_and_update` leave the bias as it was."""
    from nerrf_tpu.train.stream import make_stream_tx

    arrays, _, _ = toy_data
    tok, seg = (jnp.asarray(arrays[k][:1]) for k in ("tokens", "segments"))
    params = ref.make_params(toy_config, jax.random.PRNGKey(4))
    cfg = sr.train_config_of(rehearse.merge(
        toy_config, {"train": {"warmup_steps": 0}}), 1)
    tx = make_stream_tx(cfg, smr.stream_config_of(toy_config))
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    updates, _ = tx.update(zeros, tx.init(params), params)
    for path, u in jax.tree_util.tree_leaves_with_path(updates):
        frozen = "router_bias" in jax.tree_util.keystr(path)
        assert (float(jnp.abs(u).max()) == 0.0) == frozen, path
    before = jax.device_get(params)
    _, grads = ref.make_loss_and_grad(toy_config)(params, tok, seg)
    after, _, _ = ref.clip_and_update(params, grads, ref.init_opt(params),
                                      dict(toy_config["train"],
                                           warmup_steps=0))
    for name in ("layer_1", "layer_2", "mtp_block"):
        np.testing.assert_array_equal(np.asarray(after[name]["router_bias"]),
                                      before[name]["router_bias"])
        assert _rel(after[name]["router"]["kernel"],
                    before[name]["router"]["kernel"]) > 0
    # a stack without a sigmoid router keeps the trainer's optimizer as it is
    from nerrf_tpu.models.stream import StreamConfig
    from nerrf_tpu.train.loop import make_tx

    w = {"w": jnp.ones(3)}
    assert jax.tree_util.tree_structure(make_stream_tx(
        cfg, StreamConfig(vocab_size=8)).init(w)) == \
        jax.tree_util.tree_structure(make_tx(cfg).init(w))


# --- the cell's command line, rehearsed ------------------------------------------------

@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    toy = copy.deepcopy(TOY)
    toy["cache_root"] = str(tmp_path_factory.mktemp("aot"))
    return toy


@pytest.mark.parametrize("trace", (0, 1))
def test_command_line_prints_the_contracts_last_line(toy, capsys, trace):
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
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
    assert len(extras["routed"]) == 3
    assert len(extras["same_experts_share"]) == 3
    assert extras["packing"]["assignments"] == pytest.approx(np.mean(
        [r["held_assignments"] for r in extras["routed"]]) / 3)
    assert "next token" in out.err and "two ahead" in out.err
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if not trace:
        assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
        return
    # this cell's entries, by name: others may be appended, and may list it
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS + LISTED_METRICS:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "train_windows_per_s"
        assert res["metrics"][name]["value"] > 0, name
    assert res["metrics"]["mtp_step_share.train"]["value"] < 100
    assert res["metrics"]["moe_load_imbalance.train"]["value"] >= 1.0
    # the readers that list every cell, or none, read here too
    # (a CPU trace has no step executions to time and no allocator peak)
    for name in ("step_mfu.train", "host_dispatch_ms.train",
                 "device_idle_share.train"):
        assert res["metrics"][name]["value"] > 0, name
    assert res["metrics"]["setup_timeline_jit_s.train"]["value"] >= 0
    assert res["metrics"]["compiles_in_window.train"]["value"] == 0
    # no metric whose list leaves this cell out is read here
    assert not {m["name"] for m in bench["per_layer"]
                if CELL not in m.get("workloads", [CELL])} & set(res["metrics"])
    scope_s = extras["scope_s"]
    for g in ("mtp_embed_proj", "mtp_block", "mtp_head", "moe_router",
              "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
              "dense_mlp", "mla_attention", "mla_latent", "lm_head"):
        assert scope_s[g] > 0, g
    assert sum(scope_s.values()) == pytest.approx(extras["leaf_op_s"])
    assert dict(res["breakdown"]["device_ops"])


def test_the_programs_registry_holds_the_routing_and_the_mtp_term():
    """`count_sparse` (called where a loop syncs) -> counters and gauges,
    with no indexer's keys in ``aux``."""
    from nerrf_tpu.models.stream import StreamConfig
    from nerrf_tpu.observability import DEFAULT_REGISTRY as reg
    from nerrf_tpu.train.stream import count_sparse

    scfg = StreamConfig(num_layers=3, kinds=("mla_dense", "mla_moe",
                                             "mla_moe"),
                        experts_per_token=4, vocab_size=8, mtp_layers=1,
                        mtp_loss_weight=0.3)
    assert scfg.routed_layers == 3
    before = {h: reg.value("moe_assignments_total", labels={"held": h})
              for h in ("true", "false")}
    targets = reg.value("mtp_targets_total")
    pairs = reg.value("dsa_selected_pairs_total")
    count_sparse({"held_assignments": 500.0, "load_max_over_mean": 1.5,
                  "routed_tokens": 256.0, "token_loss": 7.0,
                  "mtp_loss": 10.0, "mtp_targets": 200.0}, scfg, steps=2)
    assert reg.value("moe_assignments_total",
                     labels={"held": "true"}) - before["true"] == 1000.0
    assert reg.value("moe_assignments_total", labels={"held": "false"}) \
        - before["false"] == 2 * (256 * 4 * 3 - 500)
    assert reg.value("mtp_targets_total") - targets == 400.0
    assert reg.value("stream_mtp_loss_share") == pytest.approx(0.3)
    assert reg.value("moe_expert_load_max_over_mean") == 1.5
    assert reg.value("dsa_selected_pairs_total") == pairs


# --- the control and the planted faults -----------------------------------------------------

@pytest.fixture(scope="module")
def sound(toy_config, toy_data):
    arrays, table, _ = toy_data
    return sr.follow_reference(toy_config, arrays, table, SEED)


@pytest.mark.parametrize("kwargs", [
    {"precision": "fp8"}, {"fault": "scale_from_nope"},
    {"fault": "bias_in_weights"}, {"fault": "mtp_shift_one"}])
def test_control_and_planted_faults_come_out_not_correct(
        toy_config, toy_data, sound, kwargs):
    """The reference in the program's place, held against the f32 reference
    by the comparison and THE CELL'S OWN LIMITS: in per-tensor fp8, and with
    one fault of each new mechanism (the softmax scale from the un-rotated
    width alone; the correction bias let into the routing weights; the MTP
    target one ahead, not two), it fails a limit."""
    arrays, table, _ = toy_data
    limits = run.load_cell(CELL)[2]["limits"]
    other = sr.follow_reference(toy_config, arrays, table, SEED, **kwargs)
    numbers = sr.compare_all(other, sound)
    got, table_, _ = compare.verdict(numbers, limits)
    assert got is False, (kwargs, table_)
    print(kwargs, table_)


def test_the_reference_held_against_itself_reads_zero(sound):
    limits = run.load_cell(CELL)[2]["limits"]
    same, table_, _ = compare.verdict(sr.compare_all(sound, sound), limits)
    assert same and all(v == 0 for v, _ in table_.values())
    c = {"K": 2, "E": 16, "first": 0, "held": 4, "eps": 1e-5}
    with pytest.raises(ValueError, match="unknown fault"):
        ref.layer({}, jnp.ones((2, 4)), jnp.ones(2, jnp.int32), c,
                  fault="other")
