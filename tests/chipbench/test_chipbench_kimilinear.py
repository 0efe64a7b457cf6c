"""The configuration `kimi-linear-48b-a3b` and its cell
`stream-lm-8k-kda-packed`: the configuration file against the published one,
the experiment file against it, parameters and required work counted by
hand, the program against the plain reference at a toy width (each kind
alone and the whole stack: forward, loss, every gradient leaf, the delta
rule's tokens and resets), the shares of one expert layer, the cell's command
line rehearsed on the CPU, and the control and the five planted faults
coming out not `correct`."""

import copy
import json
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, rehearse, run
from chipbench.reference import kimilinear as ref
from chipbench.traffic import stream_kda_resident as skr
from chipbench.traffic import stream_resident as sr
from chipbench.work import kimilinear as work

ROOT = Path(__file__).resolve().parents[2]
CELL = "stream-lm-8k-kda-packed"
NAME = "kimi-linear-48b-a3b"
NEW_METRICS = ["kda_core_roofline.train", "kda_proj_roofline.train"]

# https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/
# config.json as the architectures' catalog holds it
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}

# one toy the tests here share: published layers 1-5 at hidden 64, 2
# delta-rule heads of 16 and 2 latent heads of 8 + 4 (values 8), gates of
# rank 16, 16 experts of which the four from 4 on are held, float32 so that
# the comparison with the reference is tight
TOY = {
    "config": {"hidden_size": 64, "num_attention_heads": 2,
               "kv_lora_rank": 16, "qk_nope_head_dim": 8,
               "qk_rope_head_dim": 4, "v_head_dim": 8,
               "linear_attn_config": {"num_heads": 2, "head_dim": 16},
               "assumed": {"gate_rank": {"value": 16}},
               "intermediate_size": 96, "moe_intermediate_size": 32,
               "vocab_size": 512, "router_experts": 16, "num_experts": 4,
               "first_expert": 4, "num_experts_per_token": 4,
               "dtype": "float32",
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
SEED = 3_600_000_443


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
        SEED, 4, skr.sequence_costs(toy_config, {}, arrays["segments"]))
    return arrays, table


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


# --- the configuration file ----------------------------------------------------

def test_configuration_holds_the_published_keys(full):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert entry["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
    assert len(full["source"]) <= 200 and len(entry["why"]) <= 200
    differs = {k for k, v in PUBLISHED.items() if full.get(k, "absent") != v}
    assert differs == set(entry["reduced"]) == set(full["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key, cut in full["reduced"].items():
        assert cut["published"] == PUBLISHED[key] and cut["held"] == full[key]
        assert cut["why"]
    # the guide's floors: the dense layer and one whole period of 3 : 1,
    # 8 experts, an eighth of the vocabulary
    assert full["published_layers"] == [1, 2, 3, 4, 5]
    assert skr.kinds_of(full) == ["kda_dense", "kda_moe", "kda_moe",
                                  "mla_moe", "kda_moe"]
    assert full["num_experts"] == 8 and full["first_expert"] == 0
    assert full["num_experts"] * 32 == full["router_experts"] == 256
    assert full["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert set(full["assumed"]) >= {
        "gate_rank", "a_log_init", "short_conv_bias", "l2_eps", "nope",
        "order", "resets", "softmax", "tie_break", "router_bias"}
    assert all(v["why"] for v in full["assumed"].values())
    assert full["assumed"]["gate_rank"]["value"] == 128
    assert full["assumed"]["l2_eps"]["value"] == ref.L2_EPS == 1e-6
    assert "float32 parameters" in full["precision"]
    assert "32 chips" in full["deployment"]
    assert full["model"] == "kimilinear"


def test_the_cut_holds_602_million_parameters(full):
    kda_block = (3 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096)
                 + 2304 * 32 + 3 * 4096 * 4 + 4096 + 32 + 128 + 4096 * 2304)
    assert kda_block == 39_514_272
    mla_block = 2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304
    assert mla_block == 29_114_880
    expert = 3 * 2304 * 1024
    router = 2304 * 256 + 256
    dense = kda_block + 3 * 2304 * 9216 + 2 * 2304
    assert dense == 103_219_872
    moe = router + 9 * expert + 2 * 2304
    assert kda_block + moe == 103_809_952
    assert mla_block + moe == 93_410_560
    vocabulary = 2 * 20480 * 2304 + 2304
    assert vocabulary == 94_374_144
    total = dense + 3 * (kda_block + moe) + mla_block + moe + vocabulary
    assert total == 602_434_432
    assert ref.count_params(full) == work.count_params(full) == total
    assert total * 16 / 16.91e9 == pytest.approx(0.570, abs=0.001)
    # the same count for the whole model: the catalog's 48 B
    whole = (dense + 19 * (kda_block + moe + 248 * expert)
             + 7 * (mla_block + moe + 248 * expert) + 2 * 163840 * 2304)
    assert whole / 1e9 == pytest.approx(49.1, abs=0.1)


def test_experiment_file_equals_the_benchmarks_configuration(full):
    from nerrf_tpu.config import EXPERIMENTS, Experiment, to_dict

    exp = EXPERIMENTS["stream-kimi-linear-48b-a3b"]
    assert Experiment.load(
        ROOT / "configs/stream-kimi-linear-48b-a3b.json") == exp
    assert skr.stream_config_of(full) == exp.stream
    assert exp.stream.stack == ("kda_dense", "kda_moe", "kda_moe", "mla_moe",
                                "kda_moe")
    assert (exp.stream.q_lora_rank, exp.stream.mla_rope) == (0, False)
    assert (exp.stream.num_heads, exp.stream.head_dim,
            exp.stream.d_conv) == (32, 128, 4)
    # the program ties the delta-rule heads to the latent layers' and the
    # gates' rank to the head size: a configuration that parts them is
    # refused
    with pytest.raises(RuntimeError, match="gates' rank"):
        skr.stream_config_of(rehearse.merge(
            full, {"assumed": {"gate_rank": {"value": 64}}}))
    assert exp.stream.routed_layers == 4 and not exp.stream.tie_head
    assert sr.train_config_of(full, 1) == exp.train
    assert full["corpus"] == to_dict(exp.corpus)
    _, entry, cell, _ = run.load_cell(CELL)
    assert entry["traffic"] == "packed-kda-t8192-b1-q2"
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert cell["generator"] == "stream_kda_resident"
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


# --- required work, counted by hand --------------------------------------------

def test_required_work_by_hand(full):
    d = work.shapes_of(full)
    assert work.kda_core_flops(d) == 7 * 32 * 128 * 128
    # one unpacked document of 8192 tokens
    one = work.packing_of(np.ones((1, 8192), np.int32))
    assert one == {"tokens": 8192, "docs": 1, "pairs": 8192 * 8193 / 2}
    flops = work.train_flops(full, one)
    assert flops["kda_core"] == 3 * 4 * 8192 * 7 * 32 * 128 * 128
    assert flops["kda_proj"] == 3 * 4 * 8192 * (
        2 * (3 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
             + 4096 * 2304) + 3 * 2 * 4 * 4096)
    assert flops["mla_attention"] == 3 * one["pairs"] * 2 * 32 * (192 + 128)
    assert flops["dense_mlp"] == 3 * 8192 * 6 * 2304 * 9216
    assert flops["moe_shared"] == 3 * 4 * 8192 * 6 * 2304 * 1024
    # the even split: 8192 x 8 x 8 / 256 = 256 tokens a held expert
    assert flops["moe_experts"] == 3 * 4 * 256 * 8 * 6 * 2304 * 1024
    assert flops["total"] == sum(v for k, v in flops.items() if k != "total")
    tera = {k: v / 1e12 for k, v in flops.items()}
    # 335.8 M parameters a token passes: 16.5 TFLOP of products a step;
    # the delta rule 0.36 of required work
    passed = flops["total"] - flops["kda_core"] - flops["mla_attention"]
    assert passed / (3 * 2 * 8192) / 1e6 == pytest.approx(335.8, abs=0.3)
    assert tera["kda_core"] == pytest.approx(0.361, abs=0.001)
    # the delta rule's bytes: q, k, v, o in bf16 and g in float32
    moved = work.train_work(full, one)
    assert moved["kda_core"]["bytes"] == 3 * 4 * 8192 * 32 * 128 * (4 * 2
                                                                     + 4)
    from chipbench import roofline

    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())
    v5e = next(v for k, v in peaks.items() if not k.startswith("_"))
    bound = {k: roofline.least_seconds(w["flops"], w["bytes"], v5e)[1]
             for k, w in moved.items()}
    assert bound["kda_core"] == "bytes" and bound["kda_proj"] == "flops"


def test_packing_counts_tokens_documents_and_pairs_and_scopes_are_told_apart():
    seg = np.array([[1, 1, 1, 2, 2, 0], [1, 1, 1, 1, 1, 1]])
    got = work.packing_of(seg)
    assert got == {"tokens": (3 + 2 + 6) / 2, "docs": 3 / 2,
                   "pairs": (6 + 3 + 21) / 2}
    groups = [g for g, _ in work.SCOPE_GROUPS]
    assert max(groups.index(g) for g in groups if g.startswith(
        ("moe_", "kda_", "mla_", "dense_"))) < groups.index("stream_layer")
    assert set(sum(work.ROOFLINES.values(), [])) <= set(groups)
    from chipbench.trace_reduce import group_of

    for path, group in (
            ("s/stream_layer_1/kda_core/while/body/dot", "kda_core"),
            ("s/transpose(jvp(stream_layer_2))/kda_core/x", "kda_core"),
            ("s/stream_layer_2/kda_proj/ssm_conv/mul", "kda_proj"),
            ("s/stream_layer_3/mla_attention/while", "mla_attention"),
            ("s/stream_layer_3/mla_latent/dot", "mla_latent"),
            ("s/stream_layer_2/moe_shared/dot", "moe_shared")):
        assert group_of([path], work.SCOPE_GROUPS) == group


def test_the_generator_holds_the_counted_tokens_and_resets_to_the_work_file(
        toy_config, toy_data):
    """`check_counts`: what the program's ``aux`` says its four delta-rule
    layers ran in each warm-up step against `packing_of` on that step's
    sequence; a count the work file does not make is refused."""
    arrays, table = toy_data
    routed = []
    for k in range(3):
        rows = np.asarray(table[k]).ravel()
        got = work.packing_of(arrays["segments"][rows])
        routed.append({"delta_tokens": 4 * got["tokens"],
                       "delta_resets": 4 * got["docs"]})
    skr.check_counts(toy_config, arrays, table, routed)
    routed[1]["delta_resets"] += 1
    with pytest.raises(RuntimeError, match="delta_resets"):
        skr.check_counts(toy_config, arrays, table, routed)


# --- the program against the reference, toy width ---------------------------------

def test_program_loss_gradients_and_counts_match_the_reference(toy_config,
                                                               toy_data):
    """The whole 5-layer stack: loss and every gradient leaf against the
    reference's; the routing and the delta rule's counts on both sides and
    in the work file."""
    from nerrf_tpu.models.stream import StreamNet
    from nerrf_tpu.train.stream import make_stream_loss_fn

    arrays, _ = toy_data
    tok, seg = (jnp.asarray(arrays[k][:2]) for k in ("tokens", "segments"))
    assert len(np.unique(np.asarray(seg[0]))) >= 3     # packed documents
    scfg = skr.stream_config_of(toy_config)
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
    # every leaf but the frozen correction biases moves
    for path, g in jax.tree_util.tree_leaves_with_path(gr):
        frozen = "router_bias" in jax.tree_util.keystr(path)
        assert (float(jnp.linalg.norm(g)) == 0) == frozen, path
    assert float(aux["held_assignments"]) == sum(
        int(x.sum()) for x in fn.stats["held_assignments"])
    packed = work.packing_of(np.asarray(seg))
    assert float(aux["delta_tokens"]) == packed["tokens"] * 2 * 4
    assert float(aux["delta_resets"]) == packed["docs"] * 2 * 4


@pytest.mark.parametrize("layer, kind", [
    (1, "kda_dense"), (2, "kda_moe"), (4, "mla_moe")])
def test_each_kind_alone_matches_the_reference(toy_config, toy_data, layer,
                                                kind):
    """A stack of one published layer of each kind: the program's hidden
    state (the forward), loss and every gradient leaf against the
    reference's."""
    from nerrf_tpu.models.stream import StreamNet
    from nerrf_tpu.train.stream import make_stream_loss_fn

    config = rehearse.merge(toy_config, {"num_hidden_layers": 1,
                                         "published_layers": [layer]})
    scfg = skr.stream_config_of(config)
    assert scfg.stack == (kind,)
    arrays, _ = toy_data
    tok, seg = (jnp.asarray(arrays[k][:1]) for k in ("tokens", "segments"))
    params = ref.make_params(config, jax.random.PRNGKey(5))
    model = StreamNet(scfg)
    c = ref.dims(config)
    with jax.default_matmul_precision("highest"):
        hidden = model.apply({"params": params}, tok, seg)["hidden"]
        x, _ = ref.layer(params["layer_0"],
                         params["tok_embed"]["embedding"][tok[0]], seg[0],
                         c, 0)
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
    tok, seg = (jnp.asarray(arrays[k][:1]) for k in ("tokens", "segments"))
    params = ref.make_params(toy_config, jax.random.PRNGKey(3))
    c = ref.dims(toy_config)
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda p: ref.batch_loss(p, tok, seg, c)))(params)
    got_l, got_g = ref.loss_and_grad(params, tok, seg, toy_config)
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree_util.tree_leaves(want_g)):
        assert _rel(a, b) < 1e-4, jax.tree_util.keystr(path)


def test_the_shares_of_one_expert_layer_add_up_to_the_uncut_layer(toy_config):
    """Four chips' shares (experts 0-3, 4-7, 8-11, 12-15) of the program's
    routed part, each routed over all 16 by the sigmoid router with its
    correction bias and its 2.446, plus the shared expert ONCE, add up to
    what the reference gives with all 16 held; gradients through the
    hand-written backward pass equal autodiff of the reference's."""
    from nerrf_tpu.ops import moe

    c = dict(ref.dims(toy_config), held=16, first=0)
    rng = np.random.default_rng(7)
    n = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    z = n(200, 64)
    p = {"router": {"kernel": n(64, 16) * 0.3}, "router_bias": n(16) * 0.1,
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
            router=partial(moe.route_sigmoid, bias=p["router_bias"],
                           scale=c["scale"]))

    def summed(z, p):
        return sum(share(z, p, f)[0] for f in (0, 4, 8, 12)) + shared(z, p)

    with jax.default_matmul_precision("highest"):
        want = whole(z, p)
        parts = [share(z, p, first) for first in (0, 4, 8, 12)]
        assert _rel(summed(z, p), want) < 1e-5
        assert sum(int(x.sum()) for _, x in parts) == 200 * 4
        # counted on every chip, the shared expert would come out 4 x
        assert _rel(sum(y for y, _ in parts) + 4 * shared(z, p), want) > 0.1
        # the weights of a token's four add up to 2.446
        weights, _ = moe.route_sigmoid(z @ p["router"]["kernel"], 4,
                                       bias=p["router_bias"], scale=2.446)
        np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.446,
                                   rtol=1e-5)
        cot = n(200, 64)
        g_want = jax.grad(lambda z, p: jnp.sum(whole(z, p) * cot),
                          argnums=(0, 1))(z, p)
        g_got = jax.grad(lambda z, p: jnp.sum(summed(z, p) * cot),
                         argnums=(0, 1))(z, p)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree_util.tree_leaves(g_want)):
        assert _rel(a, b) < 1e-4, jax.tree_util.keystr(path)


def test_the_programs_registry_counts_the_delta_rules_tokens_and_resets():
    """`count_sparse` (called where a loop syncs) -> the delta rule's tokens
    and resets, beside the routing."""
    from nerrf_tpu.models.stream import StreamConfig
    from nerrf_tpu.observability import DEFAULT_REGISTRY as reg
    from nerrf_tpu.train.stream import count_sparse

    scfg = StreamConfig(num_layers=2, kinds=("kda_dense", "kda_moe"),
                        experts_per_token=4, vocab_size=8)
    assert scfg.routed_layers == 1
    names = ("kda_tokens_total", "kda_resets_total")
    before = {k: reg.value(k) for k in names}
    count_sparse({"held_assignments": 50.0, "load_max_over_mean": 1.5,
                  "routed_tokens": 64.0, "token_loss": 7.0,
                  "delta_tokens": 120.0, "delta_resets": 6.0}, scfg,
                 steps=3)
    assert reg.value("kda_tokens_total") - before["kda_tokens_total"] == 360
    assert reg.value("kda_resets_total") - before["kda_resets_total"] == 18


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
    assert "zeroed its state at" in out.err
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if not trace:
        assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
        return
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "train_windows_per_s"
        assert res["metrics"][name]["value"] > 0, name
    # the readers that list no cell read here too (a CPU trace has no step
    # executions to time and no allocator peak)
    for name in ("step_mfu.train", "host_dispatch_ms.train",
                 "device_idle_share.train"):
        assert res["metrics"][name]["value"] > 0, name
    assert res["metrics"]["compiles_in_window.train"]["value"] == 0
    # no metric whose list leaves this cell out is read here
    assert not {m["name"] for m in bench["per_layer"]
                if CELL not in m.get("workloads", [CELL])} & set(res["metrics"])
    scope_s = extras["scope_s"]
    for g in ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
              "moe_shared", "dense_mlp", "kda_core", "kda_proj",
              "mla_attention", "mla_latent", "lm_head"):
        assert scope_s[g] > 0, g
    assert sum(scope_s.values()) == pytest.approx(extras["leaf_op_s"])


# --- the control and the planted faults -----------------------------------------------------

@pytest.fixture(scope="module")
def sound(toy_config, toy_data):
    arrays, table = toy_data
    return sr.follow_reference(toy_config, arrays, table, SEED)


@pytest.mark.parametrize("kwargs", [
    {"precision": "fp8"}, {"fault": "half_batch"}, {"fault": "no_reset"},
    {"fault": "head_decay"}, {"fault": "no_delta"},
    {"fault": "router_unscaled"}])
def test_control_and_planted_faults_come_out_not_correct(
        toy_config, toy_data, sound, kwargs):
    """The reference in the program's place, held against the f32 reference
    by the comparison and THE CELL'S OWN LIMITS: in per-tensor fp8, and with
    each planted fault (half of the sequence's targets left out; the state
    carried across document starts; each head's decay averaged over its
    channels; the update without its ``S^T k`` correction; the routed part
    without its 2.446), it fails a limit."""
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
        ref.layer({}, jnp.ones((2, 4)), jnp.ones(2, jnp.int32), {}, 0,
                  fault="other")
