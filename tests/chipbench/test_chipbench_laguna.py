"""The configuration `laguna-s-2.1` and its cell `stream-lm-8k-swa-packed`:
the configuration file against the published one, the experiment file
against it, parameters and required work counted by hand, the program
against the plain reference at a toy width (loss, every gradient leaf, the
pairs attended), the shares of one expert layer, the cell's command line
rehearsed on the CPU, and the control and the planted faults coming out not
`correct`."""

import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, rehearse, run
from chipbench.reference import laguna as ref
from chipbench.traffic import stream_gqa_resident as sgr
from chipbench.traffic import stream_resident as sr
from chipbench.work import laguna as work

ROOT = Path(__file__).resolve().parents[2]
CELL = "stream-lm-8k-swa-packed"
NAME = "laguna-s-2.1"
NEW_METRICS = ["gqa_window_roofline.train", "gqa_full_roofline.train",
               "gqa_proj_roofline.train"]

# https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json as the
# architectures' catalog holds it: the numbers at the top level, and the
# nested groups this test reads
PUBLISHED = {
    "vocab_size": 100352, "hidden_size": 3072, "intermediate_size": 12288,
    "num_hidden_layers": 48, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "model_type": "laguna",
    "rope_parameters": {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                           "factor": 128,
                           "original_max_position_embeddings": 8192,
                           "beta_slow": 1, "beta_fast": 32,
                           "attention_factor": 1.4852030263919618,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 12,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12}

# one toy the tests here share: the dense layer and one period of four at
# hidden 64, 6 / 4 query heads over 2 of 16, a window of 32, 16 experts of
# which the four from 4 on are held, float32 so that the comparison with the
# reference is tight
TOY = {
    "config": {"hidden_size": 64, "head_dim": 16, "num_key_value_heads": 2,
               "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
               "sliding_window": 32, "intermediate_size": 96,
               "moe_intermediate_size": 32,
               "shared_expert_intermediate_size": 32, "vocab_size": 512,
               "router_experts": 16, "num_experts": 4, "first_expert": 4,
               "num_experts_per_tok": 4, "dtype": "float32",
               "corpus": {"duration_sec": 60.0, "num_target_files": 10,
                          "benign_rate_hz": 20.0}},
    "cell": {"seq_len": 256, "num_seqs": 4, "traces": 2, "corpus_seed": 11,
             "doc_median": 48.0, "doc_sigma": 1.0, "doc_min": 16,
             "table_rows": 4, "in_flight": 2, "trace_seconds": 1.0,
             "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3,
                        "grad_gap_mean": 1e-3, "update_gap": 1e-2,
                        "update_gap_mean": 1e-3, "grad_diff": 2e-3,
                        "grad_diff_mean": 1e-3, "update_diff": 5e-2,
                        "update_diff_mean": 1e-2}},
    "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e11,
              "hbm_bytes": 1 << 34},
}
SEED = 3_600_000_417


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several query blocks and several tiles an expert at the toy's 256
    tokens."""
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
def toy_data(toy_config):
    _, _, cell, _ = run.load_cell(CELL)
    arrays, _ = sr.make_sequences(toy_config, rehearse.merge(cell,
                                                             TOY["cell"]))
    table = sr.make_order_table(
        SEED, 4, sgr.sequence_costs(toy_config, {}, arrays["segments"]))
    return arrays, table


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


# --- the configuration file ----------------------------------------------------

def test_configuration_holds_the_published_keys(full):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert entry["source"] == ("https://huggingface.co/poolside/"
                               "Laguna-S-2.1/blob/main/config.json")
    assert len(full["source"]) <= 200 and len(entry["why"]) <= 200
    differs = {k for k, v in PUBLISHED.items() if full.get(k, "absent") != v}
    assert differs == set(entry["reduced"]) == set(full["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key, cut in full["reduced"].items():
        assert cut["published"] == PUBLISHED[key] and cut["held"] == full[key]
        assert cut["why"]
    # the guide's floors: the dense layer and one whole period of four, 8
    # experts, an eighth of the vocabulary
    layers = full["num_hidden_layers"]
    assert full["layer_types"][1:layers] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert full["mlp_layer_types"][:layers] == ["dense"] + ["sparse"] * 4
    assert full["num_experts"] == 8 and full["first_expert"] == 0
    assert full["num_experts"] * 32 == full["router_experts"] == 256
    assert full["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert set(full["assumed"]) >= {
        "gate", "qk_norm", "shared_expert", "yarn", "partial_rotary",
        "window", "attention_scale", "tie_break", "positions"}
    assert all(v["why"] for v in full["assumed"].values())
    assert full["assumed"]["attention_scale"]["value"] == 128 ** -0.5
    assert "float32 parameters" in full["precision"]
    assert "32 chips" in full["deployment"] and full["model"] == "laguna"


def test_the_cut_holds_811_million_parameters(full):
    full_block = 3072 * 6144 + 2 * 3072 * 1024 + 6144 * 3072 + 3072 * 48
    assert full_block == 44_187_648
    window_block = 3072 * 9216 + 2 * 3072 * 1024 + 9216 * 3072 + 3072 * 72
    assert window_block == 63_135_744
    expert = 3 * 3072 * 1024
    assert expert == 9_437_184
    dense = full_block + 3 * 3072 * 12288 + 2 * 3072
    window = window_block + 3072 * 256 + 9 * expert + 2 * 3072
    last = full_block + 3072 * 256 + 9 * expert + 2 * 3072
    vocabulary = 2 * 12544 * 3072 + 3072
    total = dense + 3 * window + last + vocabulary
    assert total == 811_017_216
    assert ref.count_params(full) == work.count_params(full) == total
    assert total * 16 / 16.91e9 == pytest.approx(0.767, abs=0.001)
    # the same count for the whole model: the catalog's ~118 B
    whole = (dense + 36 * (window + 248 * expert) + 11 * (last + 248 * expert)
             + 2 * 100352 * 3072)
    assert whole / 1e9 == pytest.approx(117.5, abs=0.1)


def test_experiment_file_equals_the_benchmarks_configuration(full):
    from nerrf_tpu.config import EXPERIMENTS, Experiment, to_dict

    exp = EXPERIMENTS["stream-laguna-s-2.1"]
    assert Experiment.load(ROOT / "configs/stream-laguna-s-2.1.json") == exp
    assert sgr.stream_config_of(full) == exp.stream
    assert exp.stream.stack == ("gqa_full_dense",) + ("gqa_swa_moe",) * 3 + (
        "gqa_full_moe",)
    assert (exp.stream.num_heads, exp.stream.window_heads,
            exp.stream.num_kv_heads, exp.stream.head_dim) == (48, 72, 8, 128)
    assert exp.stream.routed_layers == 4 and not exp.stream.tie_head
    assert sr.train_config_of(full, 1) == exp.train
    assert full["corpus"] == to_dict(exp.corpus)
    _, entry, cell, _ = run.load_cell(CELL)
    assert entry["traffic"] == "packed-swa-t8192-b1-q2"
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert cell["generator"] == "stream_gqa_resident"
    pack = to_dict(exp.stream_data)
    for key in ("seq_len", "num_seqs", "doc_median", "doc_sigma", "doc_min"):
        assert cell[key] == pack[key]
    # the documents of `packed-t8192-b1-q2`
    other = json.loads((ROOT / "chipbench/traffic/packed-t8192-b1-q2.json"
                        ).read_text())
    for key in ("batch", "seq_len", "num_seqs", "traces", "corpus_seed",
                "doc_median", "doc_sigma", "doc_min", "table_rows",
                "in_flight"):
        assert cell[key] == other[key], key
    assert (cell["batch"], cell["in_flight"], cell["table_rows"]) == (1, 2, 64)


# --- required work, counted by hand --------------------------------------------

def test_required_work_by_hand(full):
    d = work.shapes_of(full)
    assert work.proj_params(d, 72) == 3072 * (2 * 72 * 128 + 2 * 8 * 128 + 72)
    assert work.attention_flops_per_pair(d, 72) == 2 * 72 * (128 + 128)
    # one unpacked document of 8192 tokens: every pair inside the window
    one = work.packing_of(np.ones((1, 8192), np.int32), 512)
    assert one["full_pairs"] == 8192 * 8193 / 2
    assert one["window_pairs"] == 512 * 513 / 2 + (8192 - 512) * 512
    flops = work.train_flops(full, one)
    tera = {k: v / 1e12 for k, v in flops.items()}
    assert flops["gqa_window_attention"] == 3 * 3 * one["window_pairs"] * (
        2 * 72 * 256)
    assert flops["gqa_full_attention"] == 3 * 2 * one["full_pairs"] * (
        2 * 48 * 256)
    assert flops["gqa_proj"] == 3 * 8192 * 2 * (
        2 * work.proj_params(d, 48) + 3 * work.proj_params(d, 72))
    assert flops["dense_mlp"] == 3 * 8192 * 6 * 3072 * 12288
    assert flops["moe_shared"] == 3 * 4 * 8192 * 6 * 3072 * 1024
    # the even split: 8192 x 10 x 8 / 256 = 320 tokens a held expert
    assert flops["moe_experts"] == 3 * 4 * 320 * 8 * 6 * 3072 * 1024
    assert flops["total"] == sum(v for k, v in flops.items() if k != "total")
    # about 24 TFLOP of products whatever the packing; the cores' 6.3 are
    # those of one whole document, the most a sequence can hold
    assert 23.0 < tera["total"] - tera["gqa_window_attention"] \
        - tera["gqa_full_attention"] < 24.5
    assert 6.0 < tera["gqa_window_attention"] + tera[
        "gqa_full_attention"] < 6.6
    moved = work.train_work(full, one)
    from chipbench import roofline

    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())
    v5e = next(v for k, v in peaks.items() if not k.startswith("_"))
    assert {k: roofline.least_seconds(w["flops"], w["bytes"], v5e)[1]
            for k, w in moved.items()} == dict.fromkeys(work.ROOFLINES,
                                                        "flops")


def test_packing_counts_both_kinds_of_pairs_and_scopes_are_told_apart():
    seg = np.array([[1, 1, 1, 2, 2, 0], [1, 1, 1, 1, 1, 1]])
    got = work.packing_of(seg, 2)
    assert got == {"tokens": (3 + 2 + 6) / 2, "full_pairs": (6 + 3 + 21) / 2,
                   "window_pairs": (5 + 3 + 11) / 2}
    groups = [g for g, _ in work.SCOPE_GROUPS]
    assert max(groups.index(g) for g in groups if g.startswith(
        ("moe_", "gqa_", "dense_"))) < groups.index("stream_layer")
    assert set(sum(work.ROOFLINES.values(), [])) <= set(groups)
    from chipbench.trace_reduce import group_of

    for path, group in (
            ("s/stream_layer_1/gqa_window_attention/mla_flash_fwd",
             "gqa_window_attention"),
            ("s/transpose(jvp(stream_layer_4))/gqa_full_attention/x",
             "gqa_full_attention"),
            ("s/stream_layer_2/gqa_proj/dot", "gqa_proj"),
            ("s/stream_layer_2/moe_shared/dot", "moe_shared")):
        assert group_of([path], work.SCOPE_GROUPS) == group


def test_the_generator_holds_the_counted_pairs_to_the_work_files(toy_config,
                                                                 toy_data):
    """`check_pairs`: what the program's ``aux`` says it attended in each
    warm-up step against `packing_of` on that step's sequence (3 window
    layers, 2 full ones); a count the work file does not make is refused."""
    arrays, table = toy_data
    routed = []
    for k in range(3):
        rows = np.asarray(table[k]).ravel()
        got = work.packing_of(arrays["segments"][rows], 32)
        routed.append({"window_pairs": 3 * got["window_pairs"],
                       "full_pairs": 2 * got["full_pairs"]})
    sgr.check_pairs(toy_config, arrays, table, routed)
    routed[1]["window_pairs"] += 1
    with pytest.raises(RuntimeError, match="window pairs"):
        sgr.check_pairs(toy_config, arrays, table, routed)


# --- the program against the reference, toy width ---------------------------------

def test_program_loss_gradients_and_pairs_match_the_reference(toy_config,
                                                               toy_data):
    from nerrf_tpu.models.stream import StreamNet
    from nerrf_tpu.train.stream import make_stream_loss_fn

    arrays, _ = toy_data
    tok, seg = (jnp.asarray(arrays[k][:2]) for k in ("tokens", "segments"))
    assert len(np.unique(np.asarray(seg[0]))) >= 3     # packed documents
    scfg = sgr.stream_config_of(toy_config)
    model = StreamNet(scfg)
    params = ref.make_params(toy_config, jax.random.PRNGKey(1))
    own = model.init(jax.random.PRNGKey(0), tok, seg)["params"]
    assert jax.tree_util.tree_map(jnp.shape, params) == \
        jax.tree_util.tree_map(jnp.shape, own)
    with jax.default_matmul_precision("highest"):
        (lp, aux), gp = jax.jit(jax.value_and_grad(
            make_stream_loss_fn(model), has_aux=True))(
                params, {"tokens": tok, "segments": seg},
                jax.random.PRNGKey(2))
    fn = ref.make_loss_and_grad(toy_config)
    lr, gr = fn(params, tok, seg)
    assert float(lp) == pytest.approx(float(lr), rel=2e-6)
    gap = jax.tree_util.tree_map(_rel, gp, gr)
    worst = max(jax.tree_util.tree_leaves_with_path(gap),
                key=lambda kv: kv[1])
    assert worst[1] < 1e-4, worst
    # every leaf moves
    assert min(float(jnp.linalg.norm(g))
               for g in jax.tree_util.tree_leaves(gr)) > 0
    # both sides counted the same routing and the same pairs, and so does
    # the work file from the segments alone
    assert float(aux["held_assignments"]) == sum(
        int(x.sum()) for x in fn.stats["held_assignments"])
    packed = work.packing_of(np.asarray(seg), 32)
    for kind, layers in (("window", 3), ("full", 2)):
        assert float(aux[f"{kind}_pairs"]) == fn.stats[f"{kind}_pairs"] == \
            packed[f"{kind}_pairs"] * 2 * layers
    assert fn.stats["window_pairs"] < 1.5 * fn.stats["full_pairs"]


@pytest.mark.parametrize("layer_type, mlp_type, kind", [
    ("full_attention", "dense", "gqa_full_dense"),
    ("full_attention", "sparse", "gqa_full_moe"),
    ("sliding_attention", "sparse", "gqa_swa_moe")])
def test_each_kind_alone_matches_the_reference(toy_config, toy_data,
                                                layer_type, mlp_type, kind):
    """A stack of one layer of each kind: the program's hidden state (the
    forward), loss and every gradient leaf against the reference's."""
    from nerrf_tpu.models.stream import StreamNet
    from nerrf_tpu.train.stream import make_stream_loss_fn

    heads = 6 if layer_type == "sliding_attention" else 4
    config = rehearse.merge(toy_config, {
        "num_hidden_layers": 1, "layer_types": [layer_type],
        "mlp_layer_types": [mlp_type],
        "num_attention_heads_per_layer": [heads]})
    scfg = sgr.stream_config_of(config)
    assert scfg.stack == (kind,)
    arrays, _ = toy_data
    tok, seg = (jnp.asarray(arrays[k][:1]) for k in ("tokens", "segments"))
    params = ref.make_params(config, jax.random.PRNGKey(5))
    model = StreamNet(scfg)
    c = ref.dims(config)
    with jax.default_matmul_precision("highest"):
        hidden = model.apply({"params": params}, tok, seg)["hidden"]
        x, _, _ = ref.layer(params["layer_0"],
                            params["tok_embed"]["embedding"][tok[0]], seg[0],
                            c, ref.kind_of(c, 0))
        (lp, _), gp = jax.jit(jax.value_and_grad(
            make_stream_loss_fn(model), has_aux=True))(
                params, {"tokens": tok, "segments": seg},
                jax.random.PRNGKey(2))
    assert _rel(hidden[0], ref.rms_norm(params["final_norm"], x,
                                        c["eps"])) < 1e-5
    lr, gr = ref.make_loss_and_grad(config)(params, tok, seg)
    assert float(lp) == pytest.approx(float(lr), rel=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree_util.tree_leaves(gr)):
        assert _rel(a, b) < 1e-4, jax.tree_util.keystr(path)


def test_reference_gradient_a_layer_at_a_time_equals_autodiff(toy_config,
                                                              toy_data):
    arrays, _ = toy_data
    tok, seg = (jnp.asarray(arrays[k][:2]) for k in ("tokens", "segments"))
    params = ref.make_params(toy_config, jax.random.PRNGKey(3))
    c = ref.dims(toy_config)
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda p: ref.batch_loss(p, tok, seg, c)))(params)
    got_l, got_g = ref.make_loss_and_grad(toy_config)(params, tok, seg)
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree_util.tree_leaves(want_g)):
        assert _rel(a, b) < 1e-4, jax.tree_util.keystr(path)


def test_yarn_frequencies_equal_hfs_formula_by_hand(full):
    """`ops/dsa.py::yarn_frequencies` and the reference's `yarn_inv_freq`
    against HF's ``_compute_yarn_parameters`` written out for the full
    layers' 64 rotary dimensions: the ramp runs from 9 to 18."""
    import math

    from nerrf_tpu.ops import dsa

    rope = full["rope_parameters"]["full_attention"]
    dim, base = 64, 500000.0

    def correction(turns):
        return dim * math.log(8192 / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low, high = math.floor(correction(32)), math.ceil(correction(1))
    assert (low, high) == (9, 18)
    want = []
    for i in range(32):
        extrapolation = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        theta_freq = 1.0 / base ** (2 * i / dim)
        want.append(theta_freq / 128 * (1 - extrapolation)
                    + theta_freq * extrapolation)
    mine = dsa.yarn_frequencies(64, base, 128.0, 8192, 32.0, 1.0)
    np.testing.assert_allclose(mine, want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(64, rope)), want,
                               rtol=1e-5)
    # the fastest nine turn as theta's, the slowest fourteen 128 x slower
    np.testing.assert_allclose(mine[:9] * base ** (np.arange(9) * 2 / 64), 1,
                               rtol=1e-5)
    np.testing.assert_allclose(
        mine[18:] * 128 * base ** (np.arange(18, 32) * 2 / 64), 1, rtol=1e-5)
    assert rope["attention_factor"] == pytest.approx(
        0.1 * math.log(128) + 1.0)


def test_the_shares_of_one_expert_layer_add_up_to_the_uncut_layer(toy_config):
    """Four chips' shares (experts 0-3, 4-7, 8-11, 12-15) of the program's
    routed part, each routed over all 16 by the softmax router with its
    scale, plus the shared expert ONCE, add up to what the reference gives
    with all 16 held; gradients through the hand-written backward pass equal
    autodiff of the reference's."""
    from functools import partial

    from nerrf_tpu.ops import moe

    c = dict(ref.dims(toy_config), held=16, first=0)
    rng = np.random.default_rng(7)
    n = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    z = n(200, 64)
    p = {"router": {"kernel": n(64, 16) * 0.3},
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
            cut(p["w_down"]), k=4, first=first,
            router=partial(moe.route, scale=c["scale"]))

    def summed(z, p):
        return sum(share(z, p, f)[0] for f in (0, 4, 8, 12)) + shared(z, p)

    with jax.default_matmul_precision("highest"):
        want = whole(z, p)
        parts = [share(z, p, first) for first in (0, 4, 8, 12)]
        assert _rel(summed(z, p), want) < 1e-5
        assert sum(int(x.sum()) for _, x in parts) == 200 * 4
        # counted on every chip, the shared expert would come out 4 x
        assert _rel(sum(y for y, _ in parts) + 4 * shared(z, p), want) > 0.1
        # without the scale the routed part is 2.5 x smaller
        weights, _ = moe.route(z @ p["router"]["kernel"], 4, scale=2.5)
        np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.5,
                                   rtol=1e-5)
        cot = n(200, 64)
        g_want = jax.grad(lambda z, p: jnp.sum(whole(z, p) * cot),
                          argnums=(0, 1))(z, p)
        g_got = jax.grad(lambda z, p: jnp.sum(summed(z, p) * cot),
                         argnums=(0, 1))(z, p)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree_util.tree_leaves(g_want)):
        assert _rel(a, b) < 1e-4, jax.tree_util.keystr(path)


def test_the_programs_registry_counts_the_pairs_by_kind():
    """`count_sparse` (called where a loop syncs) -> the pairs each
    grouped-query kind attended, beside the routing."""
    from nerrf_tpu.models.stream import StreamConfig
    from nerrf_tpu.observability import DEFAULT_REGISTRY as reg
    from nerrf_tpu.train.stream import count_sparse

    scfg = StreamConfig(num_layers=2, kinds=("gqa_full_dense", "gqa_swa_moe"),
                        experts_per_token=4, vocab_size=8)
    assert scfg.routed_layers == 1
    before = {k: reg.value("attention_pairs_total", labels={"kind": k})
              for k in ("window", "full")}
    count_sparse({"held_assignments": 50.0, "load_max_over_mean": 1.5,
                  "routed_tokens": 64.0, "token_loss": 7.0,
                  "window_pairs": 900.0, "full_pairs": 1200.0}, scfg,
                 steps=3)
    for kind, value in (("window", 900.0), ("full", 1200.0)):
        assert reg.value("attention_pairs_total", labels={"kind": kind}) \
            - before[kind] == 3 * value


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
    assert set(res["compared"]) == {
        "loss_gap.1", "loss_gap.2", "loss_gap.3", "grad_gap",
        "grad_gap_mean", "update_gap", "update_gap_mean", "grad_diff",
        "grad_diff_mean", "update_diff", "update_diff_mean"}
    assert all(v <= lim for v, lim in res["compared"].values())
    extras = res["extras"]
    assert len(extras["routed"]) == 3
    assert extras["packing"]["assignments"] == pytest.approx(np.mean(
        [r["held_assignments"] for r in extras["routed"]]) / 4)
    assert "pairs attended" in out.err
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if not trace:
        assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
        return
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "train_windows_per_s"
        assert res["metrics"][name]["value"] > 0, name
    # the readers that list every cell, or none, read here too
    # (a CPU trace has no step executions to time and no allocator peak)
    for name in ("step_mfu.train", "host_dispatch_ms.train",
                 "device_idle_share.train"):
        assert res["metrics"][name]["value"] > 0, name
    assert res["metrics"]["compiles_in_window.train"]["value"] == 0
    # no metric whose list leaves this cell out is read here
    assert not {m["name"] for m in bench["per_layer"]
                if CELL not in m.get("workloads", [CELL])} & set(res["metrics"])
    scope_s = extras["scope_s"]
    for g in ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
              "moe_shared", "dense_mlp", "gqa_window_attention",
              "gqa_full_attention", "gqa_proj", "lm_head"):
        assert scope_s[g] > 0, g
    assert sum(scope_s.values()) == pytest.approx(extras["leaf_op_s"])
    counters = res["extras"]
    assert counters["packing"]["window_pairs"] < counters["packing"][
        "full_pairs"]


# --- the control and the planted faults -----------------------------------------------------

@pytest.fixture(scope="module")
def sound(toy_config, toy_data):
    arrays, table = toy_data
    return sr.follow_reference(toy_config, arrays, table, SEED)


@pytest.mark.parametrize("kwargs", [
    {"precision": "fp8"}, {"fault": "half_batch"}, {"fault": "no_window"},
    {"fault": "rope_unscaled"}, {"fault": "router_unscaled"}])
def test_control_and_planted_faults_come_out_not_correct(
        toy_config, toy_data, sound, kwargs):
    """The reference in the program's place, held against the f32 reference
    by the comparison and THE CELL'S OWN LIMITS: in per-tensor fp8, and with
    each planted fault (half of the sequence's targets left out; the window
    layers attending the whole document; YaRN left out of the full layers'
    rotary; the routed part without its 2.5), it fails a limit."""
    arrays, table = toy_data
    limits = run.load_cell(CELL)[2]["limits"]
    other = sr.follow_reference(toy_config, arrays, table, SEED, **kwargs)
    got, table_, _ = compare.verdict(sr.compare_all(other, sound), limits)
    assert got is False, (kwargs, table_)


def test_the_reference_held_against_itself_reads_zero(sound):
    limits = run.load_cell(CELL)[2]["limits"]
    same, table_, _ = compare.verdict(sr.compare_all(sound, sound), limits)
    assert same and all(v == 0 for v, _ in table_.values())
    with pytest.raises(ValueError, match="unknown fault"):
        ref.layer({}, jnp.ones((2, 4)), jnp.ones(2, jnp.int32), {},
                  (4, False, True), fault="other")
