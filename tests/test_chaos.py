"""Chaos plane: plan schema + determinism, every fault point armed AND
disarmed, poison-batch bisection isolating exactly the injected window,
stream quarantine, the scorer watchdog, reconnect backoff, and the
device-fault→exactly-one-bundle flight regression.

Fault points are tested against the REAL code paths they are threaded
through (gRPC drain, micro-batcher, registry store, compile cache, flight
recorder, alert sink) — the disarmed half of each test is the production
contract: with no plan armed, behavior is byte-identical to before the
chaos plane existed.
"""

import json
import threading
import time

import numpy as np
import pytest

from nerrf_tpu import chaos
from nerrf_tpu.flight.journal import EventJournal
from nerrf_tpu.observability import MetricsRegistry
from nerrf_tpu.serve import MicroBatcher, ServeConfig, WindowRequest

BUCKET = (128, 256, 32)


@pytest.fixture(autouse=True)
def _always_disarm():
    """No test may leak an armed plan into the rest of the suite."""
    yield
    chaos.disarm()


def _arm(faults, seed=0, registry=None, journal=None):
    return chaos.arm(chaos.FaultPlan(seed=seed, faults=tuple(faults)),
                     registry=registry or MetricsRegistry(namespace="test"),
                     journal=journal or EventJournal())


def _req(stream, idx, trace_id=None):
    sample = {"node_mask": np.zeros(BUCKET[0], np.bool_),
              "node_type": np.zeros(BUCKET[0], np.int32),
              "node_key": np.zeros(BUCKET[0], np.int64)}
    now = time.perf_counter()
    return WindowRequest(stream=stream, window_idx=idx, lo_ns=0, hi_ns=1,
                         bucket=BUCKET, sample=sample, t_admit=now,
                         deadline=now + 10,
                         trace_id=trace_id or f"w-{stream}-{idx}")


def _batcher(cfg=None, registry=None, journal=None, score=None,
             on_scored=None, on_failed=None):
    cfg = cfg or ServeConfig(buckets=(BUCKET,), batch_size=4,
                             batch_close_sec=10.0)
    mb = MicroBatcher(
        score_fn=score or (lambda b: np.zeros(b["node_mask"].shape)),
        cfg=cfg, registry=registry or MetricsRegistry(namespace="test"),
        journal=journal or EventJournal(),
        on_scored=on_scored, on_failed=on_failed)
    mb.mark_warm(BUCKET)
    return mb


# -- plan schema + validation -------------------------------------------------

def test_plan_json_roundtrip_and_validation():
    plan = chaos.FaultPlan.from_json(json.dumps({
        "seed": 9,
        "faults": [
            {"site": "serve.poison_window", "prob": 0.5,
             "match": {"stream": "s1"}},
            {"site": "ingest.wire_error", "every": 3},
        ]}))
    plan.validate(tuple(chaos.SITES))
    assert plan.seed == 9
    again = chaos.FaultPlan.from_dict(plan.to_dict())
    assert again == plan

    with pytest.raises(ValueError, match="unknown fault site"):
        chaos.FaultPlan(faults=(chaos.FaultSpec(site="nope", at=1),)) \
            .validate(tuple(chaos.SITES))
    with pytest.raises(ValueError, match="no trigger"):
        chaos.FaultSpec(site="ingest.wire_error").validate()
    with pytest.raises(ValueError, match="prob"):
        chaos.FaultSpec(site="ingest.wire_error", prob=1.5).validate()
    with pytest.raises(ValueError, match="unknown field"):
        chaos.FaultPlan.from_dict(
            {"faults": [{"site": "ingest.wire_error", "evrey": 3}]})
    # top-level faults ARRAY (an easy hand-edit mistake): one-line
    # INVALID, not an AttributeError traceback out of `nerrf chaos
    # validate`
    with pytest.raises(ValueError, match="JSON object"):
        chaos.FaultPlan.from_json('[{"site": "ingest.wire_error"}]')


def test_disarmed_points_are_noops():
    assert not chaos.armed()
    assert chaos.check("serve.poison_window", key="k") is None
    chaos.inject("ingest.wire_error", stream="s0")  # must not raise
    payload = b"payload-bytes"
    assert chaos.mangle("compilecache.corrupt_payload", payload) is payload


def test_seeded_plan_replays_deterministically():
    """The same plan + the same check sequence fires the same fault set —
    keyed draws AND counter draws; a different seed diverges."""
    faults = (chaos.FaultSpec(site="serve.poison_window", prob=0.5),
              chaos.FaultSpec(site="ingest.wire_error", prob=0.3),)
    keys = [f"w-{i:04x}" for i in range(64)]

    def fired_set(seed):
        ctl = _arm(faults, seed=seed)
        for k in keys:
            ctl.check("serve.poison_window", k, {"stream": "s"})
        for _ in range(64):  # unkeyed: the per-spec counter is the key
            ctl.check("ingest.wire_error", None, {})
        chaos.disarm()
        return [(s, k) for s, k, _ in ctl.fired]

    a, b = fired_set(seed=7), fired_set(seed=7)
    assert a == b and len(a) > 0
    assert fired_set(seed=8) != a
    # keyed draws are retry-stable: re-checking the same key fires the
    # same way (what lets bisection converge on the injected window)
    ctl = _arm(faults, seed=7)
    first = {k: ctl.check("serve.poison_window", k, {}) is not None
             for k in keys}
    second = {k: ctl.check("serve.poison_window", k, {}) is not None
              for k in keys}
    assert first == second


def test_trigger_shapes_at_every_bounds():
    ctl = _arm([chaos.FaultSpec(site="ingest.wire_error", at=3),
                chaos.FaultSpec(site="ingest.wire_stall", every=2,
                                max_fires=2, mode="stall")])
    hits = [ctl.check("ingest.wire_error", None, {}) is not None
            for _ in range(6)]
    assert hits == [False, False, True, False, False, False]
    stalls = [ctl.check("ingest.wire_stall", None, {}) is not None
              for _ in range(8)]
    assert stalls == [False, True, False, True, False, False, False, False]


def test_fault_injected_journaled_and_counted():
    reg = MetricsRegistry(namespace="test")
    jrn = EventJournal(registry=reg)
    _arm([chaos.FaultSpec(site="serve.poison_window",
                          match={"stream": "s1"})],
         registry=reg, journal=jrn)
    with pytest.raises(chaos.ChaosFault):
        chaos.inject("serve.poison_window", key="w-abc", stream="s1",
                     window_idx=4)
    recs = jrn.tail(kinds=("fault_injected",))
    assert len(recs) == 1
    assert recs[0].stream == "s1" and recs[0].window_id == 4
    assert recs[0].trace_id == "w-abc"
    assert recs[0].data["site"] == "serve.poison_window"
    assert reg.value("chaos_faults_injected_total",
                     labels={"site": "serve.poison_window"}) == 1


# -- ingest wire faults -------------------------------------------------------

def _replay_server():
    from nerrf_tpu.data.synth import SimConfig, simulate_trace
    from nerrf_tpu.ingest.service import TraceReplayServer

    tr = simulate_trace(SimConfig(duration_sec=20.0, attack=False,
                                  benign_rate_hz=6.0, seed=3))
    srv = TraceReplayServer(tr.events, tr.strings, batch_size=16)
    srv.start()
    return tr, srv


def test_ingest_wire_error_armed_and_disarmed():
    from nerrf_tpu.ingest.service import TrackerClient

    tr, srv = _replay_server()
    try:
        # disarmed: the stream drains completely
        ev, _ = TrackerClient(f"127.0.0.1:{srv.port}").stream(timeout=30.0)
        assert ev.num_valid == tr.events.num_valid
        # armed: the 2nd frame dies with the injected fault
        _arm([chaos.FaultSpec(site="ingest.wire_error", at=2)])
        got = []
        with pytest.raises(chaos.ChaosFault):
            for block, _s in TrackerClient(
                    f"127.0.0.1:{srv.port}").iter_blocks(
                    timeout=30.0, stream="s9"):
                got.append(block)
        assert len(got) == 1  # the frame before the fault delivered
    finally:
        srv.stop()


def test_ingest_wire_stall_delays_but_delivers():
    from nerrf_tpu.ingest.service import TrackerClient

    tr, srv = _replay_server()
    try:
        _arm([chaos.FaultSpec(site="ingest.wire_stall", mode="stall",
                              at=1, delay_sec=0.3)])
        t0 = time.perf_counter()
        ev, _ = TrackerClient(f"127.0.0.1:{srv.port}").stream(timeout=30.0)
        assert time.perf_counter() - t0 >= 0.3
        assert ev.num_valid == tr.events.num_valid  # slow, not lossy
    finally:
        srv.stop()


# -- batcher: poison bisection + device faults --------------------------------

def test_bisection_isolates_exactly_the_poisoned_window():
    """8 windows from 4 streams share one batch; ONE window is poisoned.
    Bisection must quarantine exactly it and score the other 7."""
    scored, failed = [], []
    jrn = EventJournal()
    reg = MetricsRegistry(namespace="test")
    cfg = ServeConfig(buckets=(BUCKET,), batch_size=8, batch_close_sec=10.0)
    mb = _batcher(cfg=cfg, registry=reg, journal=jrn,
                  on_scored=scored.extend,
                  on_failed=lambda reqs, exc: failed.extend(reqs))
    _arm([chaos.FaultSpec(site="serve.poison_window",
                          match={"stream": "s2", "window_idx": 1})],
         registry=reg, journal=jrn)
    for i in range(8):
        mb.submit(_req(f"s{i % 4}", i // 4))
    assert mb.drain_once() == 1
    assert [(r.stream, r.window_idx) for r in failed] == [("s2", 1)]
    assert len(scored) == 7
    assert ("s2", 1) not in {(s.stream, s.window_idx) for s in scored}
    # the retries re-padded to the SAME batch shape: no recompile counted
    assert reg.value("serve_recompiles_total",
                     labels={"bucket": "128n/256e/32s"}) == 0
    assert reg.value("serve_poison_bisections_total",
                     labels={"bucket": "128n/256e/32s"}) >= 1
    kinds = [r.kind for r in jrn.tail()]
    assert "batch_bisect" in kinds and "batch_failed" in kinds


def test_bisection_disabled_fails_whole_cohort():
    failed = []
    cfg = ServeConfig(buckets=(BUCKET,), batch_size=4,
                      batch_close_sec=10.0, bisect_failed_batches=False)
    mb = _batcher(cfg=cfg, on_failed=lambda reqs, exc: failed.extend(reqs))
    _arm([chaos.FaultSpec(site="serve.poison_window",
                          match={"stream": "s0", "window_idx": 0})])
    for i in range(4):
        mb.submit(_req(f"s{i}", 0))
    mb.drain_once()
    assert len(failed) == 4  # pre-bisection behavior: everyone pays


def test_device_error_and_latency_points():
    scored, failed = [], []
    mb = _batcher(on_scored=scored.extend,
                  on_failed=lambda reqs, exc: failed.extend(reqs))
    _arm([chaos.FaultSpec(site="serve.device_latency", mode="stall",
                          at=1, delay_sec=0.25),
          chaos.FaultSpec(site="serve.device_error", at=2)])
    for i in range(4):
        mb.submit(_req("s0", i))
    t0 = time.perf_counter()
    mb.drain_once()  # batch 1: stalled (scored), batch 2: first cohort
    assert time.perf_counter() - t0 >= 0.25
    # the at=2 device error hits the SECOND cohort scoring — the same
    # whole batch, which bisection then retries clean (transient fault)
    for i in range(4, 8):
        mb.submit(_req("s0", i))
    mb.drain_once()
    assert len(scored) == 8 and not failed  # transient: retries recovered


# -- service: quarantine + watchdog + the bundle regression -------------------

def _fake_service(cfg, registry=None, score=None, journal=None):
    """Real admission/demux/failure paths over a stub device program —
    the private-state skeleton comes from conftest.make_service_shell
    (one copy, shared with test_serve/test_registry)."""
    from conftest import make_service_shell

    svc, registry = make_service_shell(cfg, registry=registry,
                                       journal=journal)
    score = score or (lambda batch:
                      np.full(batch["node_mask"].shape, 0.9, np.float64))
    svc._batcher = MicroBatcher(score_fn=score, cfg=cfg, registry=registry,
                                on_scored=svc._on_scored,
                                on_failed=svc._on_failed,
                                journal=svc._journal)
    for b in cfg.buckets:
        svc._batcher.mark_warm(b)
    svc._batcher.start()
    svc._admission_open = True
    return svc, registry


def _stream_blocks(seed=5, duration=60.0, size=250):
    import dataclasses

    from nerrf_tpu.data.synth import SimConfig, simulate_trace

    tr = simulate_trace(SimConfig(duration_sec=duration, attack=True,
                                  attack_start_sec=duration / 3,
                                  num_target_files=4, benign_rate_hz=6.0,
                                  seed=seed))
    ev = tr.events
    blocks = [type(ev)(**{f.name: getattr(ev, f.name)[i:i + size]
                          for f in dataclasses.fields(ev)})
              for i in range(0, len(ev), size)]
    return tr, blocks


def _feed_stream(svc, sid, seed=5, duration=60.0):
    tr, blocks = _stream_blocks(seed=seed, duration=duration)
    for blk in blocks:
        svc.feed(sid, blk, tr.strings)


def _feed_interleaved(svc, feeds):
    """feeds: {sid: seed} — blocks alternate across streams so their
    windows close interleaved and pack into MIXED batches (the sibling
    evidence poison-proof bisection needs)."""
    data = {sid: _stream_blocks(seed=seed) for sid, seed in feeds.items()}
    for i in range(max(len(b) for _, b in data.values())):
        for sid, (tr, blocks) in data.items():
            if i < len(blocks):
                svc.feed(sid, blocks[i], tr.strings)


def test_stream_quarantined_after_strikes_sheds_then_releases():
    cfg = ServeConfig(buckets=((256, 512, 64),), batch_size=4,
                      batch_close_sec=0.05, window_sec=10.0, stride_sec=5.0,
                      quarantine_strikes=2, quarantine_release_sec=1.0)
    svc, reg = _fake_service(cfg)
    jrn = svc._journal
    _arm([chaos.FaultSpec(site="serve.poison_window",
                          match={"stream": "bad"})],
         registry=reg, journal=jrn)
    try:
        svc.join("bad")
        svc.join("good")
        # interleaved: bad and good windows share batches, so bisection
        # has the sibling-scored evidence that makes a strike a PROOF
        _feed_interleaved(svc, {"bad": 5, "good": 6})
        deadline = time.perf_counter() + 20.0
        while time.perf_counter() < deadline:
            with svc._lock:
                if "bad" in svc._quarantined:
                    break
            time.sleep(0.05)
        with svc._lock:
            assert "bad" in svc._quarantined
            assert svc._strikes["bad"] >= 2
        # post-quarantine admission sheds the bad stream only
        _feed_stream(svc, "bad", seed=7)
        assert reg.value("serve_admission_dropped_total",
                         labels={"reason": "quarantined"}) > 0
        kinds = {r.kind for r in jrn.tail()}
        assert "stream_quarantined" in kinds
        assert "device_batch_failed" in kinds
        # the good stream still scores end to end
        det = svc.leave("good", timeout=20.0)
        assert det.detector == "serve[max]"
        good_failed = [r for r in jrn.tail(kinds=("device_batch_failed",))
                       if r.stream == "good"]
        assert good_failed == []
        # timed release: after quarantine_release_sec (and the upstream
        # poison fixed — disarm), the stream serves again, clean slate
        chaos.disarm()
        time.sleep(cfg.quarantine_release_sec + 0.1)
        before = reg.value("serve_windows_admitted_total")
        _feed_stream(svc, "bad", seed=8)
        assert "stream_released" in {r.kind for r in jrn.tail()}
        with svc._lock:
            assert "bad" not in svc._quarantined
            assert svc._strikes["bad"] == 0
        # the gauge clears with the ledger (a released stream must not
        # read as permanently at the quarantine threshold)
        assert reg.value("serve_stream_strikes",
                         labels={"stream": "bad"}) == 0.0
        assert reg.value("serve_windows_admitted_total") > before
    finally:
        svc.stop(drain=False)


def test_strikes_key_on_base_stream_across_reconnect_sessions():
    """A resident stream renames per wire session (p, p#1, p#2 …): its
    poison strikes must accumulate under the BASE name — a reconnect is
    not a clean slate — and the metric label set stays bounded."""
    cfg = ServeConfig(buckets=(BUCKET,), batch_size=4,
                      batch_close_sec=10.0, quarantine_strikes=2)
    svc, reg = _fake_service(cfg)
    jrn = svc._journal
    try:
        boom = chaos.ChaosFault("injected")
        for sid, idx in (("p", 0), ("p#1", 0)):
            r = _req(sid, idx)
            r.poison = True  # as the batcher stamps a proven isolation
            svc._on_failed([r], boom)
        with svc._lock:
            assert svc._strikes == {"p": 2}
            assert "p" in svc._quarantined  # 2 strikes across 2 sessions
        rec = jrn.tail(kinds=("stream_quarantined",))[-1]
        assert rec.stream == "p"
        # one label series for the whole stream, not one per session
        assert reg.value("serve_windows_quarantined_total",
                         labels={"stream": "p"}) == 2
        assert reg.value("serve_windows_quarantined_total",
                         labels={"stream": "p#1"}) == 0
        # a joining session of the quarantined stream is shed at admission
        svc.join("p#2")
        _feed_stream(svc, "p#2", seed=13)
        assert reg.value("serve_admission_dropped_total",
                         labels={"reason": "quarantined"}) > 0
    finally:
        svc.stop(drain=False)


def test_device_wide_failure_strikes_no_stream():
    """An all-fail batch (every window fails, nothing scores) indicts
    the DEVICE: bisection finds no sibling evidence, so nobody is
    struck and nobody is quarantined — a transient device-wide fault
    must not permanently shed innocent streams."""
    cfg = ServeConfig(buckets=((256, 512, 64),), batch_size=4,
                      batch_close_sec=0.05, window_sec=10.0, stride_sec=5.0,
                      quarantine_strikes=1)  # ONE proven strike would trip
    svc, reg = _fake_service(cfg)
    jrn = svc._journal
    _arm([chaos.FaultSpec(site="serve.device_error", every=1)],
         registry=reg, journal=jrn)
    try:
        svc.join("s0")
        svc.join("s1")
        _feed_interleaved(svc, {"s0": 5, "s1": 6})
        svc.leave("s0", timeout=20.0)
        recs = jrn.tail(kinds=("device_batch_failed",))
        assert recs  # windows did terminally fail...
        assert all(r.data["poison"] is False for r in recs)
        with svc._lock:  # ...but no stream was blamed
            assert svc._quarantined == {}
            assert svc._strikes == {}
        assert "stream_quarantined" not in {r.kind for r in jrn.tail()}
    finally:
        svc.stop(drain=False)


def test_watchdog_tolerates_slow_bisection_progress():
    """The watchdog times ONE device call, not the whole bisection
    recursion: isolating a poison through several slow-but-returning
    retries must never flip the batcher wedged."""
    def slow_score(batch):
        time.sleep(0.2)  # each call well under the 0.4 s limit...
        return np.zeros(batch["node_mask"].shape)

    cfg = ServeConfig(buckets=(BUCKET,), batch_size=8,
                      batch_close_sec=0.02, scorer_wedge_sec=0.4)
    jrn = EventJournal()
    reg = MetricsRegistry(namespace="test")
    scored, failed = [], []
    mb = _batcher(cfg=cfg, registry=reg, journal=jrn, score=slow_score,
                  on_scored=scored.extend,
                  on_failed=lambda reqs, exc: failed.extend(reqs))
    _arm([chaos.FaultSpec(site="serve.poison_window",
                          match={"stream": "s0", "window_idx": 0})],
         registry=reg, journal=jrn)
    mb.start()
    try:
        for i in range(8):
            mb.submit(_req(f"s{i % 4}", i // 4))
        deadline = time.perf_counter() + 20.0
        # ...so the full isolation (~2·log2(8) calls ≈ 1 s total) takes
        # several wedge-limits of wall clock while making progress
        while len(scored) + len(failed) < 8 \
                and time.perf_counter() < deadline:
            time.sleep(0.05)
        assert len(scored) == 7 and len(failed) == 1
        assert not mb.wedged
        assert "scorer_wedged" not in {r.kind for r in jrn.tail()}
    finally:
        mb.stop(drain=False)


def test_intermittent_device_fault_confirm_retry_delivers_not_strikes():
    """An intermittently-failing device (not window-specific) can make a
    singleton bisection retry fail once while siblings score.  The
    confirm re-run must catch it: the window DELIVERS, no strike, no
    quarantine evidence."""
    calls = []
    scored, failed = [], []

    def flaky_score(batch):
        calls.append(1)
        # fail the full batch, the first half, and the first singleton —
        # then recover: the confirm re-run of that singleton succeeds
        if len(calls) in (1, 2, 4):
            raise RuntimeError("intermittent device fault")
        return np.zeros(batch["node_mask"].shape)

    cfg = ServeConfig(buckets=(BUCKET,), batch_size=4,
                      batch_close_sec=10.0)
    jrn = EventJournal()
    mb = _batcher(cfg=cfg, journal=jrn, score=flaky_score,
                  on_scored=scored.extend,
                  on_failed=lambda reqs, exc: failed.extend(reqs))
    for i in range(4):
        mb.submit(_req(f"s{i}", 0))
    mb.drain_once()
    assert failed == []          # nobody charged for the device's flake
    assert len(scored) == 4      # the once-failed window delivered too
    assert "device_batch_failed" not in {r.kind for r in jrn.tail()}
    assert "batch_failed" in {r.kind for r in jrn.tail()}  # but recorded


def test_plan_rejects_mode_the_site_cannot_execute():
    """A spec whose mode its point cannot execute would fire, journal,
    and count while injecting NOTHING — a phantom fault no recovery can
    match.  Validation must reject it at plan load, not at game time."""
    phantom = chaos.FaultPlan(faults=(
        chaos.FaultSpec(site="compilecache.corrupt_payload", at=1),))
    with pytest.raises(ValueError, match="phantom"):
        chaos.validate_plan(phantom)
    with pytest.raises(ValueError, match="phantom"):
        chaos.arm(phantom)
    assert not chaos.armed()
    with pytest.raises(ValueError, match="phantom"):
        chaos.validate_plan(chaos.FaultPlan(faults=(
            chaos.FaultSpec(site="serve.device_latency", at=1),)))  # needs stall
    # the executable combinations still validate
    chaos.validate_plan(chaos.FaultPlan(faults=(
        chaos.FaultSpec(site="compilecache.corrupt_payload",
                        mode="corrupt", at=1),
        chaos.FaultSpec(site="serve.device_latency", mode="stall", at=1),
        chaos.FaultSpec(site="serve.device_error", at=1),)))


def test_plan_rejects_counter_triggers_on_key_stable_sites():
    """serve.poison_window retries must replay identically (bisection
    convergence); counter triggers would hop windows between retries, so
    validation rejects them in favor of keyed prob / match."""
    for bad in (chaos.FaultSpec(site="serve.poison_window", every=8),
                chaos.FaultSpec(site="serve.poison_window", at=3)):
        with pytest.raises(ValueError, match="hop windows"):
            chaos.validate_plan(chaos.FaultPlan(faults=(bad,)))
    chaos.validate_plan(chaos.FaultPlan(faults=(
        chaos.FaultSpec(site="serve.poison_window", prob=0.5,
                        match={"stream": "s1"}),)))


def test_serve_detect_bad_chaos_plan_is_one_line_refusal(tmp_path,
                                                         capsys):
    """A typo'd --chaos-plan must refuse to boot with the one-line
    INVALID message (exit 2), not a traceback — serving WITHOUT the
    requested faults would silently fake the game day."""
    from nerrf_tpu import cli

    bad = tmp_path / "bad.json"
    bad.write_text('{"faults": [{"site": "not.a.site", "at": 1}]}')
    rc = cli.main(["serve-detect", "--trace", str(bad),  # never reached
                   "--chaos-plan", str(bad), "--metrics-port", "-1"])
    assert rc == 2
    assert "INVALID" in capsys.readouterr().err


def test_injected_device_fault_dumps_exactly_one_bundle(tmp_path):
    """The _on_failed regression: a persistent device fault must produce
    journaled device_batch_failed records with trace IDs, labeled failure
    counters, and (via the drop-burst trigger) EXACTLY ONE rate-limited
    flight bundle."""
    from nerrf_tpu.flight import FlightConfig, FlightRecorder

    cfg = ServeConfig(buckets=((256, 512, 64),), batch_size=2,
                      batch_close_sec=0.02, window_sec=10.0, stride_sec=5.0,
                      quarantine_strikes=0)  # isolate the bundle behavior
    svc, reg = _fake_service(cfg)
    jrn = svc._journal
    recorder = FlightRecorder(
        FlightConfig(out_dir=str(tmp_path / "bundles"), p99_breach_sec=None,
                     drop_burst_n=3, drop_burst_sec=30.0,
                     min_interval_sec=600.0),
        registry=reg, journal=jrn, slo=svc.slo, log=None)
    _arm([chaos.FaultSpec(site="serve.device_error", every=1)],
         registry=reg, journal=jrn)
    try:
        svc.join("s0")
        _feed_stream(svc, "s0", seed=9)
        svc.leave("s0", timeout=20.0)
        recs = jrn.tail(kinds=("device_batch_failed",))
        assert len(recs) >= 3
        assert all(r.trace_id for r in recs)
        assert reg.value("serve_windows_failed_total",
                         labels={"reason": "ChaosFault",
                                 "stream": "s0"}) >= 3
        bundles = [p for p in (tmp_path / "bundles").iterdir()
                   if p.name.startswith("bundle-")]
        assert len(bundles) == 1  # burst fired, rate limit held
        assert bundles[0].name.endswith("drop_burst")
    finally:
        recorder.close()
        svc.stop(drain=False)


def test_scorer_watchdog_wedges_fails_ready_and_unblocks_leave():
    release = threading.Event()
    calls = []

    def wedging_score(batch):
        calls.append(1)
        release.wait(timeout=30.0)
        return np.zeros(batch["node_mask"].shape)

    cfg = ServeConfig(buckets=((256, 512, 64),), batch_size=2,
                      batch_close_sec=0.02, window_sec=10.0, stride_sec=5.0,
                      scorer_wedge_sec=0.3)
    svc, reg = _fake_service(cfg, score=wedging_score)
    jrn = svc._journal
    try:
        # the wedge gauge exists (at 0) from start(): an alert rule on it
        # must read "healthy", never "no data"
        assert "serve_scorer_wedged" in reg.render()
        assert reg.value("serve_scorer_wedged") == 0.0
        svc.join("s0")
        _feed_stream(svc, "s0", seed=11)
        deadline = time.perf_counter() + 10.0
        while not svc._batcher.wedged and time.perf_counter() < deadline:
            time.sleep(0.05)
        assert svc._batcher.wedged
        ok, reason, _ = svc.ready()
        assert not ok and "wedged" in reason
        assert reg.value("serve_scorer_wedged") == 1.0
        # leave() must NOT wait its full timeout on a wedged scorer
        t0 = time.perf_counter()
        svc.leave("s0", timeout=30.0)
        assert time.perf_counter() - t0 < 5.0
        # recovery: release the stuck call → wedge clears, journaled
        release.set()
        deadline = time.perf_counter() + 10.0
        while svc._batcher.wedged and time.perf_counter() < deadline:
            time.sleep(0.05)
        assert not svc._batcher.wedged
        kinds = [r.kind for r in jrn.tail()]
        assert "scorer_wedged" in kinds and "scorer_recovered" in kinds
        ok, _, _ = svc.ready()
        assert ok
    finally:
        release.set()
        svc.stop(drain=False)


def test_reconnect_backoff_grows_and_is_counted():
    from nerrf_tpu.ingest.service import TraceReplayServer

    cfg = ServeConfig(buckets=((256, 512, 64),), batch_size=4,
                      batch_close_sec=0.02, window_sec=10.0, stride_sec=5.0)
    svc, reg = _fake_service(cfg)
    jrn = svc._journal
    tr, srv = _replay_server()
    _arm([chaos.FaultSpec(site="ingest.wire_error", every=1)],
         registry=reg, journal=jrn)  # every frame: sessions never healthy
    try:
        run = svc.connect("s0", f"127.0.0.1:{srv.port}", timeout=10.0,
                          follow=True, reconnect_sec=0.05,
                          reconnect_max_sec=0.4)
        deadline = time.perf_counter() + 20.0
        while time.perf_counter() < deadline:
            if len(jrn.tail(kinds=("reconnect",))) >= 4:
                break
            time.sleep(0.05)
        recs = jrn.tail(kinds=("reconnect",))
        assert len(recs) >= 4
        assert all(r.data["healthy"] is False for r in recs)
        delays = [r.data["delay_sec"] for r in recs[:4]]
        # exponential growth through the jitter: each doubling's MINIMUM
        # (0.5·backoff) clears the previous backoff's maximum
        assert delays[2] > delays[0]
        assert max(delays) <= 0.4
        assert reg.value("serve_reconnects_total",
                         labels={"stream": "s0"}) >= 4
        svc.stop(drain=False)
        assert run.done.wait(timeout=10.0)
    finally:
        srv.stop()
        svc.stop(drain=False)


# -- registry faults ----------------------------------------------------------

@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """One small-model checkpoint shared by the registry-fault tests —
    param init + save is the expensive part (~18 s), the faults under
    test are per-publish."""
    from nerrf_tpu.models import JointConfig, NerrfNet
    from nerrf_tpu.serve import init_untrained_params
    from nerrf_tpu.train.checkpoint import save_checkpoint

    cfg = ServeConfig(buckets=((256, 512, 64),))
    model = NerrfNet(JointConfig().small)
    params = init_untrained_params(model, cfg)
    ckpt = tmp_path_factory.mktemp("chaos-ckpt") / "ckpt"
    save_checkpoint(ckpt, params, model.cfg)
    return ckpt


def test_registry_store_io_fault_leaves_no_partial_version(checkpoint,
                                                           tmp_path):
    from nerrf_tpu.registry import ModelRegistry

    ckpt = checkpoint
    store = ModelRegistry(tmp_path / "reg", journal=EventJournal())
    v1 = store.publish("lin", ckpt)  # disarmed: publish works
    assert v1 == 1
    _arm([chaos.FaultSpec(site="registry.store_io", at=1)])
    with pytest.raises(chaos.ChaosFault):
        store.publish("lin", ckpt)
    # fail-closed: no partial version, no stranded tmp dir
    assert store.versions("lin") == [1]
    assert not [p for p in store.lineage_dir("lin").iterdir()
                if p.name.startswith(".publish.tmp")]
    chaos.disarm()
    assert store.publish("lin", ckpt) == 2  # and the store still works


def test_registry_corrupt_sidecar_fails_load_with_one_line_error(checkpoint,
                                                                 tmp_path):
    from nerrf_tpu.registry import ModelRegistry

    ckpt = checkpoint
    store = ModelRegistry(tmp_path / "reg", journal=EventJournal())
    _arm([chaos.FaultSpec(site="registry.corrupt_sidecar", mode="corrupt",
                          at=1)])
    v = store.publish("lin", ckpt)
    chaos.disarm()
    with pytest.raises(ValueError, match="corrupt checkpoint sidecar"):
        store.load("lin", v)


# -- compile cache corruption -------------------------------------------------

def test_compilecache_corrupt_payload_fails_open_and_repairs(tmp_path):
    import jax
    import jax.numpy as jnp

    from nerrf_tpu.compilecache import CompileCache

    reg = MetricsRegistry(namespace="test")
    jrn = EventJournal(registry=reg)
    cache = CompileCache(root=tmp_path / "aot", registry=reg, journal=jrn)
    fn = jax.jit(lambda x: jnp.sin(x) + 1.0)
    args = (jnp.ones((8,), jnp.float32),)
    _, info = cache.load_or_compile(fn, args, program="p")
    assert info.source == "fresh"
    entry = cache.entry_dir(info.fingerprint)
    assert entry.is_dir()
    _arm([chaos.FaultSpec(site="compilecache.corrupt_payload",
                          mode="corrupt", at=1)],
         registry=reg, journal=jrn)
    callee, info2 = cache.load_or_compile(fn, args, program="p")
    # fail-open: corrupt read → evict → fresh compile (repairing the
    # entry), and the result still computes
    assert info2.source == "fresh"
    np.testing.assert_allclose(np.asarray(callee(*args)),
                               np.sin(np.ones(8)) + 1.0, rtol=1e-6)
    chaos.disarm()
    _, info3 = cache.load_or_compile(fn, args, program="p")
    assert info3.source == "cache"  # the repair healed the entry


# -- flight recorder disk-full ------------------------------------------------

def test_flight_disk_full_fails_open_and_retries(tmp_path):
    from nerrf_tpu.flight import FlightConfig, FlightRecorder

    reg = MetricsRegistry(namespace="test")
    jrn = EventJournal(registry=reg)
    rec = FlightRecorder(
        FlightConfig(out_dir=str(tmp_path / "b"), p99_breach_sec=None,
                     min_interval_sec=600.0),
        registry=reg, journal=jrn, log=None)
    _arm([chaos.FaultSpec(site="flight.disk_full", at=1, max_fires=1)],
         registry=reg, journal=jrn)
    try:
        assert rec.trigger("manual", "first dump hits ENOSPC") is None
        out = tmp_path / "b"
        assert not out.exists() or not any(out.iterdir())  # no .tmp orphan
        # fail-open rolled the rate limit back: the retry succeeds
        path = rec.trigger("manual", "retry")
        assert path is not None and (tmp_path / "b").exists()
        assert len([p for p in out.iterdir()
                    if p.name.startswith("bundle-")]) == 1
    finally:
        rec.close()


# -- alert sink slow consumer -------------------------------------------------

def test_alert_sink_slow_consumer_stalls_drain_only():
    from nerrf_tpu.serve.alerts import AlertSink, WindowAlert

    sink = AlertSink(slots=4, registry=MetricsRegistry(namespace="test"),
                     journal=EventJournal())
    _arm([chaos.FaultSpec(site="alerts.slow_consumer", mode="stall",
                          at=1, delay_sec=0.3)])
    t0 = time.perf_counter()
    sink.emit(WindowAlert(stream="s", window_idx=0, lo_ns=0, hi_ns=1,
                          max_prob=0.9, hot=[], t_admit=0.0, t_scored=0.0,
                          late=False))
    emit_cost = time.perf_counter() - t0
    assert emit_cost < 0.25  # the producer side is NOT the stalled one
    t0 = time.perf_counter()
    alerts = sink.drain()
    assert time.perf_counter() - t0 >= 0.3
    assert len(alerts) == 1  # slow, not lossy


# -- the soak smoke -----------------------------------------------------------

@pytest.mark.slow
def test_chaos_bench_smoke_survives():
    """The survival-gated soak at smoke size: every gate in
    run_chaos_bench.gates must hold (same harness bench.py runs)."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                           / "benchmarks"))
    from run_chaos_bench import gates, run

    res = run(smoke=True, log=None)
    failed = [name for name, ok in gates(res) if not ok]
    assert not failed, (failed, res)
