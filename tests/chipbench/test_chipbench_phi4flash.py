"""The configuration `phi4-mini-flash` and its cell `stream-lm-8k-packed`:
the configuration file against the published one, the experiment file
against it, required work counted by hand, the program against the plain
reference at a toy width, the cell's command line rehearsed on the CPU, and
the control and the planted fault coming out not `correct`."""

import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, rehearse, run
from chipbench.reference import phi4flash as ref
from chipbench.traffic import stream_resident as sr
from chipbench.work import phi4flash as work

ROOT = Path(__file__).resolve().parents[2]
CELL = "stream-lm-8k-packed"

# https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json
# as the architectures' catalog holds it
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}

# one toy the tests here share: the six kinds at hidden 64, float32 so that
# the comparison with the reference is tight
TOY = {
    "config": {"hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 2, "intermediate_size": 128,
               "vocab_size": 512, "sliding_window": 32, "dtype": "float32",
               "assumed": {"dt_rank": {"value": 4}},
               "corpus": {"duration_sec": 60.0, "num_target_files": 10,
                          "benign_rate_hz": 20.0}},
    "cell": {"seq_len": 256, "num_seqs": 4, "traces": 2, "corpus_seed": 11,
             "doc_median": 64.0, "doc_min": 16, "table_rows": 4,
             "in_flight": 2, "trace_seconds": 1.0,
             "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3,
                        "grad_gap_mean": 1e-3, "update_gap": 1e-2,
                        "update_gap_mean": 1e-3, "grad_diff": 1e-3,
                        "grad_diff_mean": 1e-3, "update_diff": 5e-2,
                        "update_diff_mean": 1e-2}},
    "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e11,
              "hbm_bytes": 1 << 34},
}


@pytest.fixture(scope="module")
def full():
    return json.loads(
        (ROOT / "chipbench/configs/phi4-mini-flash.json").read_text())


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
        2_200_000_321, 4, sr.sequence_costs(toy_config, arrays["segments"]))
    return arrays, table, waste


# --- the configuration file ----------------------------------------------------

def test_configuration_holds_the_published_keys(full):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "phi4-mini-flash")
    assert entry["file"] == "chipbench/configs/phi4-mini-flash.json"
    assert "Phi-4-mini-flash-reasoning" in entry["source"]
    assert len(entry["source"]) <= 200 and len(full["source"]) <= 200
    differs = {k for k, v in PUBLISHED.items() if full.get(k) != v}
    assert differs == set(entry["reduced"]) == set(full["reduced"]) == {
        "num_hidden_layers", "vocab_size"}
    for key, cut in full["reduced"].items():
        assert cut["published"] == PUBLISHED[key] and cut["held"] == full[key]
        assert cut["why"]
    # an eighth of the vocabulary, whole heads, no width touched
    assert full["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert full["num_hidden_layers"] == len(full["kinds"]) == len(
        full["published_layers"]) == 6
    # every size the source lacks is listed as assumed, with where from
    assert set(full["assumed"]) >= {"d_state", "d_conv", "expand", "dt_rank",
                                    "stack", "head_pairs", "layer_norm",
                                    "lam_init"}
    assert all(v["why"] for v in full["assumed"].values())
    assert full["assumed"]["dt_rank"]["value"] == -(-2560 // 16)
    assert "float32 parameters" in full["precision"]


def test_the_cut_holds_697_million_parameters(full):
    # MLP 6 x 78.64 M; two mamba 2 x 41.24 M; swa + full 2 x 19.66 M; gmu
    # 26.2 M; cross 13.1 M; embedding 64.0 M
    n = ref.count_params(full)
    assert n == work.count_params(full) == 697_073_792
    assert n * 16 / 16.91e9 == pytest.approx(0.66, abs=0.005)
    d = work.shapes_of(full)
    assert (d["Di"], d["N"], d["K"], d["R"], d["d"]) == (5120, 16, 4, 160, 64)


def test_experiment_file_equals_the_benchmarks_configuration(full):
    from nerrf_tpu.config import EXPERIMENTS, to_dict
    from nerrf_tpu.models.stream import layer_kinds

    exp = EXPERIMENTS["stream-phi4-mini-flash"]
    assert sr.stream_config_of(full) == exp.stream
    assert tuple(full["kinds"]) == layer_kinds(6)
    assert sr.train_config_of(full, 1) == exp.train
    assert full["corpus"] == to_dict(exp.corpus)
    _, entry, cell, _ = run.load_cell(CELL)
    assert entry["traffic"] == "packed-t8192-b1-q2" and entry["chips"] == 1
    pack = to_dict(exp.stream_data)
    for key in ("seq_len", "num_seqs", "doc_median", "doc_sigma", "doc_min"):
        assert cell[key] == pack[key]
    assert (cell["batch"], cell["in_flight"], cell["seq_len"],
            cell["num_seqs"]) == (1, 2, 8192, 32)


# --- required work, counted by hand --------------------------------------------

def test_required_work_by_hand(full):
    d = work.shapes_of(full)
    assert work.mlp_flops_per_token(d) == 3 * 2 * 2560 * 10240 == 157_286_400
    assert work.scan_flops_per_token(d) == 6 * 5120 * 16 == 491_520
    assert work.head_flops_per_token(d) == 2 * 2560 * 25008
    assert work.mixer_flops_per_token("mamba", d) == (
        2 * 2560 * 10240 + 2 * 4 * 5120 + 2 * 5120 * 192 + 2 * 160 * 5120
        + 491_520 + 2 * 5120 * 2560)
    assert work.mixer_flops_per_token("full", d) == 2 * 2560 * (
        2560 + 1280 + 1280 + 2560)
    assert work.mixer_flops_per_token("cross", d) == 2 * 2560 * 2 * 2560
    assert work.mixer_flops_per_token("gmu", d) == 4 * 2560 * 5120
    # Q K^T over 40 heads of 64, the differences times 20 value pairs of 128
    assert work.attention_flops_per_pair(d) == 2 * 2560 + 2 * 2560
    # one unpacked document of 8192 tokens: the issue's 4.45 GFLOP a token
    one = {"tokens": 8192, "pairs_full": 8192 * 8193 // 2,
           "pairs_window": 512 * 513 // 2 + (8192 - 512) * 512}
    flops = work.train_flops(full, one)
    per_token = {k: v / 8192 / 1e9 for k, v in flops.items()}
    assert per_token["stream_mlp"] == pytest.approx(2.831, abs=1e-3)
    assert per_token["lm_head"] == pytest.approx(0.384, abs=1e-3)
    assert per_token["ssm_scan"] == pytest.approx(0.00295, abs=1e-5)
    assert per_token["stream_attention"] == pytest.approx(
        3 * 10240 * (2 * 4096.5 + 496.03) / 1e9, rel=1e-4)
    assert per_token["total"] == pytest.approx(4.45, abs=0.03)
    assert flops["total"] == sum(v for k, v in flops.items() if k != "total")
    moved = work.train_work(full, one)
    assert moved["ssm_scan"]["bytes"] == 3 * 2 * 8192 * (3 * 5120 + 32) * 2
    assert moved["ssm_scan"]["groups"] == ["ssm_scan"]
    assert moved["stream_attention"]["bytes"] == 3 * 3 * 8192 * 7680 * 2
    assert moved["stream_mlp"]["bytes"] == 3 * 6 * (
        8192 * 5120 + 3 * 2560 * 10240) * 2
    assert moved["lm_head"]["bytes"] == 3 * (8192 + 25008) * 2560 * 2
    # the scan is bound by bytes, the products by FLOPs
    from chipbench import roofline

    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())
    v5e = next(v for k, v in peaks.items() if not k.startswith("_"))
    bound = {k: roofline.least_seconds(w["flops"], w["bytes"], v5e)[1]
             for k, w in moved.items()}
    assert bound == {"ssm_scan": "bytes", "stream_attention": "flops",
                     "stream_mlp": "flops", "lm_head": "flops"}


def test_packing_counts_only_pairs_that_attend():
    seg = np.array([[1, 1, 1, 2, 2, 0], [1, 1, 1, 1, 1, 1]])
    got = work.packing_of(seg, window=2)
    # documents of 3, 2 and 6 tokens over two sequences
    assert got["tokens"] == (3 + 2 + 6) / 2
    assert got["pairs_full"] == (6 + 3 + 21) / 2
    assert got["pairs_window"] == ((3 + 2) + (3 + 0) + (3 + 4 * 2)) / 2
    groups = [g for g, _ in work.SCOPE_GROUPS]
    # an attention scope is told apart before its layer's
    assert groups.index("stream_attention") < groups.index("stream_layer")
    assert set(sum(work.ROOFLINES.values(), [])) <= set(groups)


# --- the program against the reference, toy width ---------------------------------

def test_program_loss_and_gradients_match_the_reference(toy_config, toy_data):
    from nerrf_tpu.models.stream import StreamNet, next_token_loss

    arrays, _, _ = toy_data
    tok, seg = (jnp.asarray(arrays[k][:2]) for k in ("tokens", "segments"))
    assert len(np.unique(np.asarray(seg[0]))) >= 3     # packed documents
    scfg = sr.stream_config_of(toy_config)
    model = StreamNet(scfg)
    params = ref.make_params(toy_config, jax.random.PRNGKey(1))
    own = model.init(jax.random.PRNGKey(0), tok, seg)["params"]
    assert jax.tree_util.tree_map(jnp.shape, params) == \
        jax.tree_util.tree_map(jnp.shape, own)

    def loss(p):
        hidden = model.apply({"params": p}, tok, seg)["hidden"]
        return next_token_loss(scfg, p, hidden, tok, seg)

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(loss))(params)
    lr, gr = ref.make_loss_and_grad(toy_config)(params, tok, seg)
    assert float(lp) == pytest.approx(float(lr), rel=2e-6)
    gap = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b)
                           / (jnp.linalg.norm(b) + 1e-12)), gp, gr)
    worst = max(jax.tree_util.tree_leaves_with_path(gap),
                key=lambda kv: kv[1])
    assert worst[1] < 1e-4, worst
    # the program's logits are the reference's
    hidden = model.apply({"params": params}, tok, seg)["hidden"]
    logits = hidden @ params["tok_embed"]["embedding"].T
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref.logits_of(params, tok, seg,
                                                     toy_config)),
        rtol=2e-4, atol=2e-4)


def test_reference_gradient_a_layer_at_a_time_equals_autodiff(toy_config,
                                                              toy_data):
    """`make_loss_and_grad` walks the stack backwards by hand, routing the
    hand-downs' cotangents; `jax.grad` of the whole loss says the same."""
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
        gap = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
        assert gap < 1e-4, (jax.tree_util.keystr(path), gap)


def test_reference_optimizer_by_leaf_equals_the_whole(toy_config):
    """`clip_and_update` (a leaf at a time) is `adamw.update` over the
    whole tree."""
    from chipbench.reference import adamw

    params = ref.make_params(toy_config, jax.random.PRNGKey(2))
    grads = jax.tree_util.tree_map(
        lambda p: 3.0 * jnp.cos(p * 17.0), params)
    opt = dict(toy_config["train"], warmup_steps=0)
    want_p, want_s, want_g = adamw.update(params, grads, adamw.init(params),
                                          opt)
    got_p, got_s, norms = ref.clip_and_update(
        dict(params), dict(grads), ref.init_opt(params), opt)
    for a, b in zip(jax.tree_util.tree_leaves(got_p),
                    jax.tree_util.tree_leaves(want_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)
    assert got_s["count"] == want_s["count"] == 1
    np.testing.assert_allclose(
        compare.leaf_norms(norms)[1], compare.leaf_norms(want_g)[1],
        rtol=1e-5)


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
    # the three updates: losses, first gradient, parameters' change
    assert set(res["compared"]) == {
        "loss_gap.1", "loss_gap.2", "loss_gap.3", "grad_gap",
        "grad_gap_mean", "update_gap", "update_gap_mean", "grad_diff",
        "grad_diff_mean", "update_diff", "update_diff_mean"}
    assert all(v <= lim for v, lim in res["compared"].values())
    assert res["extras"]["tokens_per_s"] == pytest.approx(
        256 * res["extras"]["end_to_end"]["train_windows_per_s"]
        if trace else 256 * res["metrics"]["train_windows_per_s"]["value"])
    assert 0 <= res["extras"]["pack_waste"] < 0.2
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if not trace:
        assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
        return
    new = ["ssm_scan_roofline.train", "stream_attention_roofline.train",
           "stream_mlp_roofline.train", "lm_head_roofline.train",
           "pack_waste_share.train"]
    # this cell's entries, by name: others may be appended, and may list it
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in new:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "train_windows_per_s"
        assert res["metrics"][name]["value"] > 0, name
    assert res["metrics"]["pack_waste_share.train"]["value"] == \
        pytest.approx(100 * res["extras"]["pack_waste"])
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
    for g in ("scope:ssm_scan", "scope:stream_attention", "scope:stream_mlp",
              "scope:lm_head"):
        assert groups[g] > 0
    assert sum(res["extras"]["scope_s"].values()) == pytest.approx(
        res["extras"]["leaf_op_s"])


def test_same_seed_same_sequences_whatever_the_seed(toy_config, toy_cell,
                                                    toy_data):
    arrays, table, waste = toy_data
    again, _ = sr.make_sequences(toy_config, toy_cell)
    assert (arrays["tokens"] == again["tokens"]).all()
    assert arrays["tokens"].shape == (4, 256) and 0 <= waste < 0.2
    assert arrays["tokens"].max() < toy_config["vocab_size"]
    assert sorted(table.ravel().tolist()) == [0, 1, 2, 3]


def test_order_table_pairs_sequences_by_cost():
    """Epochs; each half of an epoch holds one member of every cost pair at
    the pair's fixed place, cheap and costly alternating; the seed draws
    the members; a window's total cost hardly depends on the seed."""
    cost = [65, 136, 60, 136, 62, 60, 80, 69, 61, 64, 83, 60, 75, 56, 54, 59,
            92, 91, 136, 112, 112, 87, 65, 67, 81, 58, 58, 136, 88, 76, 136,
            77]
    a = sr.make_order_table(2_200_000_001, 64, cost)
    assert a.shape == (64, 1) and a.dtype == np.int32
    assert (a == sr.make_order_table(2_200_000_001, 64, cost)).all()
    assert (a != sr.make_order_table(2_200_000_002, 64, cost)).any()
    for lo in (0, 32):
        assert sorted(a[lo:lo + 32, 0].tolist()) == list(range(32))
    rank = np.argsort(np.argsort(cost, kind="stable"), kind="stable") // 2
    for lo in (0, 16, 32, 48):      # one member of each pair a half
        assert sorted(rank[a[lo:lo + 16, 0]].tolist()) == list(range(16))
        assert (rank[a[lo:lo + 16, 0]] == rank[a[:16, 0]]).all()
    c = np.asarray(cost)
    sums = [c[sr.make_order_table(s, 64, cost)[3:18, 0]].sum()
            for s in range(50)]
    assert len(set(sums)) > 5 and (max(sums) - min(sums)) < 0.03 * min(sums)
    with pytest.raises(RuntimeError, match="whole epochs"):
        sr.make_order_table(1, 48, cost)


# --- the control and the planted fault -----------------------------------------------------

@pytest.fixture(scope="module")
def sound(toy_config, toy_data):
    arrays, table, _ = toy_data
    return sr.follow_reference(toy_config, arrays, table, 2_200_000_321)


@pytest.mark.parametrize("kwargs, correct", [
    ({"precision": "fp8"}, False),
    ({"fault": "scan_ignores_documents"}, False),
    ({"precision": "bf16"}, True)])
def test_control_and_planted_fault_come_out_not_correct(
        toy_config, toy_data, sound, kwargs, correct):
    """The reference in the program's place, held against the f32 reference
    by the comparison and THE CELL'S OWN LIMITS (the toy's weights are as
    random as the cell's): in per-tensor fp8 and with the scan's state
    carried across document boundaries it fails a limit; in bfloat16, what
    a faithful program may differ by, it does not."""
    arrays, table, _ = toy_data
    limits = run.load_cell(CELL)[2]["limits"]
    other = sr.follow_reference(toy_config, arrays, table, 2_200_000_321,
                                **kwargs)
    numbers = sr.compare_all(other, sound)
    got, table_, _ = compare.verdict(numbers, limits)
    assert got is correct, (kwargs, table_)
    # the direction tells more than the norms do
    assert numbers["grad_diff_mean"] > 3 * numbers["grad_gap_mean"]


def test_the_reference_held_against_itself_reads_zero(sound):
    limits = run.load_cell(CELL)[2]["limits"]
    same, table_, _ = compare.verdict(sr.compare_all(sound, sound), limits)
    assert same and all(v == 0 for v, _ in table_.values())
