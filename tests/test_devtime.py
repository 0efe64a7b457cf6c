"""Device-efficiency plane (nerrf_tpu/devtime): chip-peak resolution,
cost-model drift pins against the real warmup ladder, live accounting
gauges, headroom math over synthetic arrival mixes, and the fail-open
profiler capture plane."""

import os

import numpy as np
import pytest

from nerrf_tpu.devtime import (
    ChipPeaks,
    DeviceTimeAccountant,
    HeadroomTracker,
    capture_trace,
    chip_peaks,
    predict_headroom,
    profiled,
    program_cost,
    resolve_kind,
    serve_program_costs,
    trace_summary,
    train_step_cost,
)
from nerrf_tpu.flight.journal import EventJournal
from nerrf_tpu.observability import MetricsRegistry


# ---------------------------------------------------------------------------
# chip peaks: exact-match resolution
# ---------------------------------------------------------------------------

def test_peaks_exact_match_only():
    got = resolve_kind("TPU v5 lite")  # what a v5e reports as device_kind
    assert (got.tflops_bf16, got.hbm_gbps) == (197.0, 819.0)
    assert got.ridge_flops_per_byte > 0
    # a similar name is a different part: "TPU v5" is what a v5p reports,
    # and a decorated kind is one the table does not know — no guessing
    for near_miss in ("TPU v5", "TPU v5e", "TPU v5 lite podslice"):
        assert resolve_kind(near_miss) is None, near_miss


def test_peaks_null_not_fake_for_unknown():
    assert resolve_kind("") is None
    assert resolve_kind("cpu") is None
    assert resolve_kind("TPU v99") is None  # future chip: None, no guess

    class FakeCpu:
        device_kind = "cpu"
        platform = "cpu"

    assert chip_peaks(FakeCpu()) is None


def test_bench_mfu_delegates_to_the_table():
    from nerrf_tpu.bench.mfu import chip_peak_tflops

    class Dev:
        device_kind = "TPU v5 lite"
        platform = "tpu"

    assert chip_peak_tflops(Dev()) == 197.0

    class Cpu:
        device_kind = ""
        platform = "cpu"

    assert chip_peak_tflops(Cpu()) is None


# ---------------------------------------------------------------------------
# cost model: drift-pinned to the real warmup ladder + sample_spec
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_serve():
    from nerrf_tpu.models import JointConfig, NerrfNet
    from nerrf_tpu.serve import ServeConfig, init_untrained_params
    from nerrf_tpu.train.loop import make_eval_fn

    cfg = ServeConfig(buckets=((64, 128, 32),))
    model = NerrfNet(JointConfig().small)
    params = init_untrained_params(model, cfg)
    return cfg, model, params, make_eval_fn(model)


def test_serve_costs_cover_exactly_the_warmup_ladder(small_serve):
    """The cost model's program set IS the warmup-compiled set: every
    bucket `warmup_batches` yields gets a cost, at the donor batch's
    exact shapes — which in turn must match `sample_spec` (the shape
    authority the deep pass proves admission against).  Any drift between
    the three surfaces fails here."""
    from nerrf_tpu.serve.config import bucket_tag
    from nerrf_tpu.serve.service import warmup_batches
    from nerrf_tpu.train.data import sample_spec

    cfg, _model, params, eval_fn = small_serve
    costs = serve_program_costs(eval_fn, params, cfg)
    ladder = {tag: batch for _b, tag, batch in warmup_batches(cfg)}
    assert set(costs) == set(ladder) != set()
    for bucket in cfg.buckets:
        tag = bucket_tag(bucket)
        spec = sample_spec(cfg.dataset_config(bucket))
        batch = ladder[tag]
        assert set(batch) == set(spec)
        for key, (shape, dtype) in spec.items():
            assert batch[key].shape == (cfg.batch_size,) + shape, key
            assert str(batch[key].dtype) == dtype, key
        cost = costs[tag]
        assert cost.program == f"serve_eval[{tag}]"
        assert cost.flops > 0
        assert cost.bytes_accessed > 0
        assert cost.intensity_flops_per_byte > 0
        assert cost.batch_slots == cfg.batch_size
        assert cost.xla_flops is None  # cross-check is opt-in


def test_program_cost_null_not_fake_for_matmul_free_fn():
    import jax.numpy as jnp

    cost = program_cost(lambda x: jnp.sum(x) + 1.0,
                        np.ones((8, 8), np.float32), program="nop")
    assert cost is None


def test_train_step_cost_at_dataset_shapes(small_serve):
    from nerrf_tpu.serve.service import _tiny_trace
    from nerrf_tpu.train.data import windows_of_trace
    from nerrf_tpu.train.loop import TrainConfig

    cfg, model, params, eval_fn = small_serve
    samples = windows_of_trace(_tiny_trace("devtime-test"),
                               cfg.dataset_config((64, 128, 32)))
    arrays = {k: np.stack([s[k] for s in samples]) for k in samples[0]}
    cost = train_step_cost(model, TrainConfig(model=model.cfg), arrays)
    assert cost is not None
    assert cost.program == "train_step"
    assert cost.flops > 0 and cost.bytes_accessed > 0
    # a train step (fwd+bwd+update of a batch) must out-cost a single
    # window's share of the eval program at the same shapes
    eval_cost = serve_program_costs(eval_fn, params, cfg)["64n/128e/32s"]
    per_window_eval = eval_cost.flops / eval_cost.batch_slots
    assert cost.flops > per_window_eval


# ---------------------------------------------------------------------------
# live accounting: gauges + null-not-fake MFU
# ---------------------------------------------------------------------------

def _fake_cost(flops=1e9, byts=1e6, program="serve_eval[t]"):
    from nerrf_tpu.devtime import ProgramCost

    return ProgramCost(program=program, flops=flops, bytes_accessed=byts,
                       peak_hbm_bytes=byts, batch_slots=8)


def test_accountant_mfu_present_only_with_known_peaks():
    for peaks, expect_mfu in ((ChipPeaks("test", 1.0, 100.0), True),
                              (None, False)):
        reg = MetricsRegistry(namespace="t")
        jrn = EventJournal(registry=reg)
        acc = DeviceTimeAccountant(registry=reg, journal=jrn, peaks=peaks)
        acc.register_cost("serve_eval[t]", _fake_cost())
        # 1e9 flops in 0.01 s = 100 GFLOP/s = 10% of the 1-TFLOP peak
        acc.observe_batch("serve_eval[t]", "t", 0.01, occupancy=4, slots=8,
                          real_density=0.5)
        mfu = reg.value("device_mfu", labels={"program": "serve_eval[t]"})
        if expect_mfu:
            assert mfu == pytest.approx(0.1, rel=0.01)
            assert reg.value("device_roofline_ridge") == pytest.approx(10.0)
        else:
            assert mfu == 0.0  # never set: absent, not fabricated
        # platform-free gauges export either way
        assert reg.value("device_util_fraction") > 0
        assert reg.value("device_useful_flops_fraction",
                         labels={"bucket": "t"}) == pytest.approx(0.25)
        assert reg.value("device_roofline_intensity",
                         labels={"program": "serve_eval[t]"}) == \
            pytest.approx(1e9 / 1e6)


def test_accountant_snapshot_surfaces_per_program_truth():
    reg = MetricsRegistry(namespace="t")
    acc = DeviceTimeAccountant(registry=reg, journal=EventJournal(),
                               peaks=ChipPeaks("test", 1.0, 100.0))
    acc.register_cost("p", _fake_cost(program="p"))
    for _ in range(3):
        acc.observe_batch("p", "t", 0.02, occupancy=8, slots=8)
    snap = acc.snapshot()
    assert snap["platform_peaks"]["tflops_bf16"] == 1.0
    p = snap["programs"]["p"]
    assert p["calls"] == 3
    assert p["device_seconds"] == pytest.approx(0.06, rel=0.01)
    assert p["mfu"] == pytest.approx(3e9 / 0.06 / 1e12, rel=0.01)
    assert snap["useful_flops_fraction"]["t"] == 1.0
    assert 0 < snap["util_fraction"] <= 1.0


def test_accountant_util_and_useful_age_out_stale_programs(monkeypatch):
    """Regression: utilization must not keep a quiet program's old busy
    seconds in the sum forever (per-observe eviction only touches the
    observed program), and snapshot's useful-FLOPs must apply the same
    trailing filter as its programs block."""
    import time as _time

    clock = [1000.0]
    monkeypatch.setattr(_time, "monotonic", lambda: clock[0])
    reg = MetricsRegistry(namespace="t")
    acc = DeviceTimeAccountant(registry=reg, journal=EventJournal(),
                               peaks=None, window_sec=60.0)
    # program A burns 50 busy-seconds, then traffic moves elsewhere
    for _ in range(5):
        acc.observe_batch("A", "a", 10.0, occupancy=8, slots=8)
    clock[0] += 600.0  # ten quiet minutes
    acc.observe_batch("B", "b", 0.001, occupancy=1, slots=8)
    assert reg.value("device_util_fraction") < 0.01  # not 0.83
    snap = acc.snapshot()
    assert snap["programs"]["A"]["calls"] == 0
    assert "a" not in snap["useful_flops_fraction"]  # aged out with A
    assert "b" in snap["useful_flops_fraction"]


def test_accountant_saturation_journal_record():
    reg = MetricsRegistry(namespace="t")
    jrn = EventJournal(registry=reg)
    acc = DeviceTimeAccountant(registry=reg, journal=jrn, peaks=None,
                               headroom_update_sec=0.0,
                               saturation_margin_streams=1.0)
    # one stream whose demand is ~2x the device: headroom < 0
    for i in range(20):
        acc.observe_admit("s0", "t")
        acc.observe_batch("p", "t", 0.2, occupancy=1, slots=8)
    kinds = [r.kind for r in jrn.tail()]
    assert "capacity_saturation" in kinds
    sat = [r for r in jrn.tail() if r.kind == "capacity_saturation"][-1]
    assert sat.data["headroom_streams"] < 1.0
    assert reg.value("capacity_headroom_streams") == \
        pytest.approx(sat.data["headroom_streams"], abs=0.5)


# ---------------------------------------------------------------------------
# headroom math: synthetic mixes vs the analytic saturation point
# ---------------------------------------------------------------------------

def test_headroom_uniform_mix_hits_analytic_saturation():
    # 4 streams, 2 windows/s each into one bucket costing 25 ms/window:
    # util = 0.2, per-stream demand 0.05 → saturation at exactly 20
    est = predict_headroom(
        {f"s{i}": 2.0 for i in range(4)},
        {f"s{i}": {"b": 1.0} for i in range(4)},
        {"b": 0.025})
    assert est.util == pytest.approx(0.2)
    assert est.saturation_streams == pytest.approx(20.0)
    assert est.headroom_streams == pytest.approx(16.0)


def test_headroom_skewed_rates():
    # rates 1/2/4/8 w/s, same 20 ms bucket: util = 0.3, mean demand
    # 0.075 → headroom (1-0.3)/0.075 = 9.333…
    est = predict_headroom(
        {"a": 1.0, "b": 2.0, "c": 4.0, "d": 8.0},
        {s: {"b": 1.0} for s in "abcd"},
        {"b": 0.02})
    assert est.util == pytest.approx(0.3)
    assert est.headroom_streams == pytest.approx((1 - 0.3) / 0.075)


def test_headroom_one_bucket_hot_mix():
    # two streams split across buckets; one bucket 10x more expensive:
    # util = 2·(0.5·0.1 + 0.5·0.01) = 0.11, mean demand 0.055
    est = predict_headroom(
        {"a": 1.0, "b": 1.0},
        {s: {"hot": 0.5, "cold": 0.5} for s in "ab"},
        {"hot": 0.1, "cold": 0.01})
    assert est.util == pytest.approx(0.11)
    assert est.per_bucket_util["hot"] == pytest.approx(0.1)
    assert est.saturation_streams == pytest.approx(2 + (1 - 0.11) / 0.055)


def test_headroom_degenerate_cases_return_null():
    # zero traffic
    assert predict_headroom({}, {}, {"b": 0.1}) is None
    assert predict_headroom({"s": 0.0}, {"s": {"b": 1.0}}, {"b": 0.1}) \
        is None
    # unknown bucket: never a fake number
    assert predict_headroom({"s": 1.0}, {"s": {"mystery": 1.0}},
                            {"b": 0.1}) is None
    # missing mix for an active stream
    assert predict_headroom({"s": 1.0}, {}, {"b": 0.1}) is None


def test_headroom_tracker_windows_arrivals_and_costs():
    trk = HeadroomTracker(window_sec=100.0)
    # 2 streams x 10 windows over 10 synthetic seconds = 1 w/s each;
    # measured cost 50 ms/window → saturation at 20 streams
    for i in range(10):
        t = float(i)
        trk.observe_admit("a", "b", t=t)
        trk.observe_admit("b", "b", t=t)
        trk.observe_batch("b", 0.1, 2, t=t + 0.5)
    est = trk.estimate(now=10.0)
    assert est is not None
    assert est.streams == 2
    assert est.saturation_streams == pytest.approx(20.0, rel=0.05)
    # no batches yet → no cost → null
    assert HeadroomTracker().estimate(now=1.0) is None


# ---------------------------------------------------------------------------
# profiler capture plane (the first tests trace_profile ever had)
# ---------------------------------------------------------------------------

def test_capture_produces_readable_trace_dir(tmp_path):
    import jax
    import jax.numpy as jnp

    jrn = EventJournal()
    out = str(tmp_path / "trace")
    with profiled(out, journal=jrn) as active:
        assert active == out
        jax.jit(lambda x: x * 2)(jnp.ones((16, 16))).block_until_ready()
    summary = trace_summary(out)
    assert summary is not None and summary["files"] > 0
    assert summary["bytes"] > 0
    kinds = [r.kind for r in jrn.tail()]
    assert "profile_capture" in kinds
    assert "profile_failed" not in kinds


def test_capture_disabled_is_a_noop(tmp_path):
    jrn = EventJournal()
    out = str(tmp_path / "trace")
    with profiled(out, enabled=False, journal=jrn) as active:
        assert active is None
    assert capture_trace(out, seconds=0.0, enabled=False, journal=jrn) \
        is None
    assert not os.path.exists(out)
    assert jrn.tail() == []


def test_capture_start_failure_is_fail_open_with_journal(tmp_path,
                                                         monkeypatch):
    import jax

    def boom(*a, **k):
        raise RuntimeError("profiler already active")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    jrn = EventJournal()
    out = str(tmp_path / "trace")
    with profiled(out, journal=jrn) as active:
        assert active is None  # fail-open: caller proceeds traceless
    recs = [r for r in jrn.tail() if r.kind == "profile_failed"]
    assert len(recs) == 1
    assert recs[0].data["phase"] == "start"
    assert "profiler already active" in recs[0].data["error"]
    assert capture_trace(out, seconds=0.0, journal=jrn) is None


def test_trace_summary_null_for_absent_or_empty(tmp_path):
    assert trace_summary(tmp_path / "nope") is None
    empty = tmp_path / "empty"
    empty.mkdir()
    assert trace_summary(empty) is None


# ---------------------------------------------------------------------------
# flight-recorder integration: profile-on-p99-breach into the bundle
# ---------------------------------------------------------------------------

def _breach_recorder(tmp_path, profile_sec):
    from nerrf_tpu.flight import FlightConfig, FlightRecorder

    reg = MetricsRegistry(namespace="t")
    jrn = EventJournal(registry=reg)
    rec = FlightRecorder(
        FlightConfig(out_dir=str(tmp_path / "bundles"),
                     p99_breach_sec=0.1, p99_min_count=4,
                     min_interval_sec=300.0,
                     profile_on_p99_sec=profile_sec),
        registry=reg, journal=jrn)
    for _ in range(6):
        rec.observe_window("s0", "tid-1", 1.0)
    rec.close()
    bundles = sorted((tmp_path / "bundles").glob("bundle-*"))
    assert len(bundles) == 1
    return bundles[0]


def test_p99_bundle_embeds_profiler_trace_and_doctor_reads_it(tmp_path):
    from nerrf_tpu.flight.doctor import format_report, read_bundle

    bundle_dir = _breach_recorder(tmp_path, profile_sec=0.1)
    assert (bundle_dir / "jax_trace").is_dir()
    bundle = read_bundle(bundle_dir)
    assert bundle["missing"] == []
    assert bundle["profile"] and bundle["profile"]["files"] > 0
    man_prof = bundle["manifest"]["profile"]
    assert man_prof["dir"] == "jax_trace"
    assert man_prof["seconds"] == 0.1
    report = format_report(bundle)
    assert "profiler trace:" in report
    assert "jax_trace/" in report


def test_p99_bundle_without_optin_has_no_trace(tmp_path):
    from nerrf_tpu.flight.doctor import format_report, read_bundle

    bundle_dir = _breach_recorder(tmp_path, profile_sec=0.0)
    assert not (bundle_dir / "jax_trace").exists()
    bundle = read_bundle(bundle_dir)
    assert bundle["profile"] is None
    assert bundle["manifest"]["profile"] is None
    assert "profiler trace:" not in format_report(bundle)


def test_profile_capture_failure_still_ships_the_bundle(tmp_path,
                                                        monkeypatch):
    import jax

    from nerrf_tpu.flight.doctor import read_bundle

    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("busy")))
    bundle_dir = _breach_recorder(tmp_path, profile_sec=0.1)
    bundle = read_bundle(bundle_dir)
    assert bundle["missing"] == []  # the bundle itself is intact
    assert bundle["profile"] is None
    assert "error" in bundle["manifest"]["profile"]
    # the fail-open record is in the bundled journal tail
    assert any(r.kind == "profile_failed" for r in bundle["records"])


# ---------------------------------------------------------------------------
# serve integration: the scorer-side observation path
# ---------------------------------------------------------------------------

def test_service_observe_devtime_derives_tag_occupancy_density():
    from conftest import make_service_shell

    from nerrf_tpu.serve import ServeConfig

    cfg = ServeConfig(buckets=((64, 128, 32),))
    svc, reg = make_service_shell(cfg)
    acc = DeviceTimeAccountant(registry=reg, journal=svc._journal,
                               peaks=None)
    acc.register_cost("serve_eval[64n/128e/32s]", _fake_cost(
        program="serve_eval[64n/128e/32s]"))
    svc._devtime = acc
    mask = np.zeros((8, 64), bool)
    mask[0, :32] = True   # one real window, half-dense
    mask[1, :16] = True   # one real window, quarter-dense
    batch = {"node_feat": np.zeros((8, 64, 5), np.float32),
             "edge_src": np.zeros((8, 128), np.int32),
             "seq_feat": np.zeros((8, 32, 100, 8), np.float32),
             "node_mask": mask}
    svc._observe_devtime(batch, 0.05)
    # occupancy 2/8 x mean density of the OCCUPIED slots (0.375)
    assert reg.value("device_useful_flops_fraction",
                     labels={"bucket": "64n/128e/32s"}) == \
        pytest.approx((2 / 8) * 0.375)
    snap = acc.snapshot()
    assert snap["programs"]["serve_eval[64n/128e/32s]"]["calls"] == 1
