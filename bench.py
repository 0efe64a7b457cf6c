#!/usr/bin/env python3
"""Benchmark of record: full-size NerrfNet train-steps/sec on TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

- value: steady-state train-steps/sec of the flagship joint model
  (28-layer ~2.2M-param GraphSAGE-T + 2×256 BiLSTM, batch of 8 window graphs
  at the corpus's fitted capacities — 1024 nodes / 2048 edges / 128
  sequences × 100 events) on the
  default JAX backend (the real TPU chip under the driver).
- vs_baseline: ratio vs the same architecture implemented in PyTorch
  (`nerrf_tpu/bench/torch_baseline.py`) measured on this host — the
  reference's planned-but-never-built PyTorch training stack (ROADMAP.md:62-69),
  which in this CUDA-less environment runs on CPU.
- extras: held-out-trace edge ROC-AUC (quality gate ≥0.90) and context.

Without a TPU the bench prints a one-line reason on stderr and exits
non-zero: a steps/s figure from any other backend is never written under
this metric's name.  A failed leg fails the run the same way.

Skip the torch leg with NERRF_BENCH_SKIP_TORCH=1 (vs_baseline then null).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
import time


def _round_of(path: str) -> int:
    """Round number encoded in an artifact filename (``..._r<N>.json``)."""
    m = re.search(r"_r(\d+)\.json$", path)
    return int(m.group(1)) if m else -1


def main() -> None:
    t_wall = time.perf_counter()
    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nerrf_tpu.data import make_corpus
    from nerrf_tpu.graph import GraphConfig
    from nerrf_tpu.models import JointConfig, NerrfNet
    from nerrf_tpu.train import TrainConfig, build_dataset
    from nerrf_tpu.train.data import DatasetConfig
    from nerrf_tpu.train.loop import (
        evaluate,
        init_state,
        make_eval_fn,
        make_idx_schedule,
        make_train_superstep,
    )
    from nerrf_tpu.bench.flops import analytic_flops

    log = lambda *a: print(*a, file=sys.stderr, flush=True)
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"bench: no TPU (default backend is {backend!r}); this metric "
            "is a device metric and is not measured anywhere else")

    # every timed region ends by fetching a scalar result to the host: the
    # device-to-host copy cannot complete before the computation that
    # produces it
    from nerrf_tpu.utils import fetch_value as fetch

    # one synced round trip so the artifact records what a per-call host
    # loop would have measured instead of the chip
    _tinyf = jax.jit(lambda x: x + 1.0)
    _tiny = _tinyf(jnp.zeros((8,), jnp.float32))
    fetch(_tiny)  # compile + first round trip
    _t0 = time.perf_counter()
    for _ in range(4):
        fetch(_tinyf(_tiny))
    dispatch_rtt_ms = round((time.perf_counter() - _t0) * 1e3 / 4, 1)
    log(f"[bench] synced dispatch round trip: {dispatch_rtt_ms:.0f} ms")
    log(f"[bench] backend={backend} devices={jax.devices()}")

    # --- data: corpus at full shapes ----------------------------------------
    corpus = make_corpus(
        12, attack_fraction=0.5, base_seed=42, duration_sec=180.0,
        num_target_files=24, benign_rate_hz=40.0,
    )
    # flagship training shapes: the generated corpus's auto-fit capacities
    # when the corpus exists (its manifest is authoritative — r2 trained at
    # 256/512 and silently truncated the densest windows), else the
    # joint-100h config values
    cap = {"max_nodes": 1024, "max_edges": 2048}
    man_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "datasets", "corpus100", "manifest.json")
    if os.path.exists(man_path):
        try:
            cap = json.load(open(man_path)).get("graph_capacity") or cap
        except Exception:
            pass
    ds_cfg = DatasetConfig(
        graph=GraphConfig(window_sec=45.0, stride_sec=15.0,
                          max_nodes=cap["max_nodes"],
                          max_edges=cap["max_edges"]),
        seq_len=100, max_seqs=128,
    )
    shape_tag = f"{cap['max_nodes']}n/{cap['max_edges']}e"
    train_ds = build_dataset(corpus[:9], ds_cfg)
    eval_ds = build_dataset(corpus[9:], ds_cfg)
    log(f"[bench] dataset: {len(train_ds)} train / {len(eval_ds)} eval windows")

    # --- JAX training -------------------------------------------------------
    # NERRF_BENCH_STEPS shrinks the run for dress rehearsals (validating
    # every leg end-to-end where 200 flagship steps would blow the clock,
    # e.g. CPU); the metric of record always uses the default
    try:
        bench_steps = max(2, int(os.environ.get("NERRF_BENCH_STEPS", "200")))
    except ValueError:
        bench_steps = 200
    cfg = TrainConfig(model=JointConfig(), batch_size=8,
                      num_steps=bench_steps,
                      learning_rate=2e-3, warmup_steps=min(30, bench_steps // 2),
                      seed=0)
    model = NerrfNet(cfg.model)
    rng = jax.random.PRNGKey(0)

    t0 = time.perf_counter()
    state = jax.jit(lambda r: init_state(model, cfg, train_ds.arrays, r))(rng)
    fetch(state.step)
    log(f"[bench] init: {time.perf_counter() - t0:.1f}s")

    # HBM-resident dataset + device-resident batch schedule inside a
    # K-step lax.scan: one host call runs K full train steps on device, so
    # no per-call dispatch or per-execution overhead sits between steps —
    # the timed quantity is the chip.
    steps_per_call = min(32, max(2, bench_steps // 4))
    idx_table = make_idx_schedule(len(train_ds), cfg)
    train_step = make_train_superstep(
        model, cfg, train_ds.arrays, idx_table, steps_per_call)

    # compile-latency accounting (VERDICT r3 item 8: flagship first-compile
    # cost is a measured risk — record it in the artifact of record; with
    # the persistent cache enabled above, a warm process re-running the
    # same shapes should show a near-zero figure here)
    compile_seconds = {}
    t0 = time.perf_counter()
    state, losses, rng = train_step(state, rng)
    loss = losses[-1]
    fetch(loss)
    compile_seconds["train_step"] = round(time.perf_counter() - t0, 1)
    log(f"[bench] first superstep ({steps_per_call} steps, compile): "
        f"{compile_seconds['train_step']:.1f}s")

    timed_calls = max(1, (bench_steps - steps_per_call) // steps_per_call)
    timed_steps = timed_calls * steps_per_call
    t0 = time.perf_counter()
    for _ in range(timed_calls):
        state, losses, rng = train_step(state, rng)
    loss = losses[-1]
    # step-time attribution: everything up to here is host dispatch (the
    # supersteps queue async), the final fetch is the host-blocked wait for
    # the device to drain — their split says whether the chip or the host
    # owns the step time (data-wait is structurally zero on this path: the
    # dataset and batch schedule are device-resident)
    dispatch_s = time.perf_counter() - t0
    fetch(loss)
    elapsed = time.perf_counter() - t0
    steps_per_sec = timed_steps / elapsed
    host_blocked_fraction = max(elapsed - dispatch_s, 0.0) / elapsed
    from nerrf_tpu.observability import DEFAULT_REGISTRY
    from nerrf_tpu.train.data import padding_waste_fractions

    padding_waste = padding_waste_fractions(train_ds.arrays)
    DEFAULT_REGISTRY.gauge_set(
        "train_host_blocked_fraction", host_blocked_fraction,
        help="fraction of timed train wall spent blocked on device results")
    for kind, frac in padding_waste.items():
        DEFAULT_REGISTRY.gauge_set(
            "train_padding_waste_fraction", frac,
            labels={"kind": kind, "bucket": shape_tag},
            help="fraction of padded capacity carrying no real data")
    log(f"[bench] {timed_steps} steps in {elapsed:.1f}s → {steps_per_sec:.2f} steps/s "
        f"(final loss {float(loss):.4f}, host-blocked "
        f"{100 * host_blocked_fraction:.0f}%, padding waste {padding_waste})")

    # --- MFU: analytic model FLOPs of one step × steps/s vs chip peak.
    # flops.py counts every dot_general/conv in the step's jaxpr at its
    # logical shape; the XLA cost_analysis figure is recorded alongside as
    # a cross-check but is NOT the numerator — on TPU it costs matmuls at
    # their MXU-padded shapes (~3x high here, enough to put "MFU" at 195%).
    from nerrf_tpu.bench.mfu import flops_per_step, mfu
    from nerrf_tpu.devtime import chip_peaks

    chip = chip_peaks(jax.devices()[0])  # None off-chip: null, never fake
    super_flops = analytic_flops(train_step, state, rng)
    step_flops = super_flops / steps_per_call if super_flops else None
    xla_super_flops = flops_per_step(train_step, state, rng)
    xla_step_flops = (
        xla_super_flops / steps_per_call if xla_super_flops else None)
    achieved_tflops, mfu_pct = mfu(step_flops, steps_per_sec, jax.devices()[0])
    if step_flops:
        log(f"[bench] flops/step={step_flops:.3g} → "
            f"{achieved_tflops:.1f} TFLOP/s"
            + (f" ({mfu_pct:.1f}% MFU)" if mfu_pct else ""))

    # --- real-density leg: the deployed bucket (4096n/8192e) ----------------
    # builder.py:104-110: a ~25k-event real-eBPF window needs ~3.2k nodes /
    # 4.4k edges, so the power-of-two deployment bucket is 4096/8192 — the
    # corpus-fitted 1024/2048 flagship shape has never been the deployed
    # density (VERDICT r4 weak #4).  Padded capacity IS the compute cost at
    # that bucket (static shapes), so the same corpus re-padded measures the
    # real step time.
    big_cfg = TrainConfig(model=JointConfig(), batch_size=8,
                          num_steps=max(2, bench_steps // 4),
                          learning_rate=2e-3, warmup_steps=2, seed=0)
    big_ds_cfg = DatasetConfig(
        graph=GraphConfig(window_sec=45.0, stride_sec=15.0,
                          max_nodes=4096, max_edges=8192),
        seq_len=100, max_seqs=128,
    )
    big_ds = build_dataset(corpus[:6], big_ds_cfg)
    big_state = jax.jit(lambda r: init_state(
        model, big_cfg, big_ds.arrays, r))(jax.random.PRNGKey(1))
    big_k = min(8, max(2, big_cfg.num_steps // 4))
    big_step = make_train_superstep(
        model, big_cfg, big_ds.arrays,
        make_idx_schedule(len(big_ds), big_cfg), big_k)
    brng = jax.random.PRNGKey(4)
    t0 = time.perf_counter()
    big_state, blosses, brng = big_step(big_state, brng)
    fetch(blosses[-1])
    compile_seconds["train_step_4096"] = round(
        time.perf_counter() - t0, 1)
    bcalls = max(1, (big_cfg.num_steps - big_k) // big_k)
    bsteps = bcalls * big_k
    t0 = time.perf_counter()
    for _ in range(bcalls):
        big_state, blosses, brng = big_step(big_state, brng)
    fetch(blosses[-1])
    bdt = time.perf_counter() - t0
    big_sps = bsteps / bdt
    big_super = analytic_flops(big_step, big_state, brng)
    big_flops = big_super / big_k if big_super else None
    big_tflops, big_mfu = mfu(big_flops, big_sps, jax.devices()[0])
    big_bucket = {
        "shape": "4096n/8192e/128seq", "batch": big_cfg.batch_size,
        "padding_waste": padding_waste_fractions(big_ds.arrays),
        # `auto` routes by node bucket (fused past DENSE_ADJ_MAX_NODES)
        # — stamp the mode this leg's numbers belong to
        "gnn_aggregation": big_cfg.model.gnn.resolved_aggregation(
            big_ds_cfg.graph.max_nodes),
        "steps_per_sec": round(big_sps, 3),
        "model_flops_per_step":
            round(big_flops) if big_flops else None,
        "achieved_tflops":
            round(big_tflops, 2) if big_tflops else None,
        "mfu_pct": round(big_mfu, 2) if big_mfu else None,
        "num_steps": big_cfg.num_steps,
    }
    log(f"[bench] big bucket 4096n/8192e: {big_sps:.3f} steps/s"
        + (f", {big_mfu:.1f}% MFU" if big_mfu else ""))
    # free the 4096-shape params+optimizer before the eval legs
    big_state = big_ds = big_step = blosses = None  # noqa: F841
    import gc

    gc.collect()

    # --- quality gate on held-out traces ------------------------------------
    metrics = evaluate(make_eval_fn(model), state.params, eval_ds, cfg.batch_size)
    log(f"[bench] eval: edge_auc={metrics['edge_auc']:.4f} "
        f"seq_auc={metrics['seq_auc']:.4f} seq_f1={metrics['seq_f1']:.4f}")

    # --- MCTS planner: rollouts/s with the TPU value net --------------------
    # (BASELINE.json metric of record; M1-scale incident: 45 files, 4 procs)
    from nerrf_tpu.planner import MCTSConfig, MCTSPlanner, UndoDomain
    from nerrf_tpu.planner.value_net import ValueNet

    prng = np.random.default_rng(7)
    F, P = 45, 4
    domain = UndoDomain(
        file_paths=[f"/app/uploads/doc_{i}.lockbit3" for i in range(F)],
        file_scores=prng.beta(0.4, 0.4, F).astype(np.float32),
        file_loss_mb=prng.uniform(2.0, 5.0, F).astype(np.float32),
        proc_names=[f"{4000 + p}:python3" for p in range(P)],
        proc_scores=np.array([0.95] + [0.1] * (P - 1), np.float32),
        max_steps=64,
    )
    # --- long-context leg: StreamNet over raw 4096-event streams ------------
    from nerrf_tpu.data import build_streams
    from nerrf_tpu.models import StreamConfig, StreamNet
    from nerrf_tpu.train.stream import init_stream_state, make_stream_step

    # the trainer's own stream step (`train/stream.py`: make_tx, the
    # resident scheduled step), every segment in each step's batch
    sb = build_streams(corpus[:6], max_len=4096)
    smodel = StreamNet(StreamConfig())
    s_steps = min(50, max(3, bench_steps // 4))
    scfg = TrainConfig(batch_size=len(sb), num_steps=s_steps + 1,
                       learning_rate=1e-3, warmup_steps=2, seed=0)
    placed = sb.arrays()
    sstate = init_stream_state(smodel, scfg, placed, jax.random.PRNGKey(2))
    step_fn = make_stream_step(
        smodel, scfg, placed,
        np.tile(np.arange(len(sb), dtype=np.int32), (s_steps + 1, 1)))
    t0 = time.perf_counter()
    sstate, sloss, _, srng = step_fn(sstate, jax.random.PRNGKey(3))
    fetch(sloss)
    compile_seconds["stream_step"] = round(time.perf_counter() - t0, 1)
    t0 = time.perf_counter()
    for _ in range(s_steps):
        sstate, sloss, _, srng = step_fn(sstate, srng)
    fetch(sloss)
    dt = time.perf_counter() - t0
    ev = placed["feat"].shape[0] * placed["feat"].shape[1]
    stream_events_per_sec = ev * s_steps / dt
    log(f"[bench] stream: {placed['feat'].shape[0]}x{placed['feat'].shape[1]} "
        f"events/step, {s_steps / dt:.0f} steps/s → "
        f"{stream_events_per_sec / 1e6:.1f}M events/s "
        f"(loss {float(sloss):.4f})")

    vnet = ValueNet.create()
    vnet.fit_to_domain(domain, num_rollouts=256, steps=150)
    planner = MCTSPlanner(domain, value_fn=vnet,
                          cfg=MCTSConfig(num_simulations=800, batch_size=128))
    plan = planner.plan()
    rollouts_per_sec = plan.rollouts_per_sec
    log(f"[bench] mcts: {plan.rollouts} rollouts @ "
        f"{plan.rollouts_per_sec:.0f}/s, {len(plan.actions)} actions")

    from nerrf_tpu.planner import DeviceMCTS

    # single-program on-device search (no per-batch round trips)
    dm = DeviceMCTS(domain, cfg=MCTSConfig(num_simulations=800),
                    value_apply=vnet.apply_fn, value_params=vnet.params)
    t0 = time.perf_counter()
    dm.plan()  # compile
    compile_seconds["device_planner"] = round(time.perf_counter() - t0, 1)
    dplan = dm.plan()
    device_rollouts_per_sec = dplan.rollouts_per_sec
    log(f"[bench] mcts device: {dplan.rollouts} rollouts @ "
        f"{dplan.rollouts_per_sec:.0f}/s, {len(dplan.actions)} actions")

    # --- torch baseline (same architecture, this host) ----------------------
    vs_baseline = None
    torch_sps = None
    if os.environ.get("NERRF_BENCH_SKIP_TORCH") != "1":
        try:
            from nerrf_tpu.bench.torch_baseline import measure_torch_steps_per_sec

            t0 = time.perf_counter()
            torch_sps = measure_torch_steps_per_sec(
                train_ds.arrays, batch_size=cfg.batch_size, timed_steps=3)
            vs_baseline = steps_per_sec / torch_sps
            log(f"[bench] torch-cpu baseline: {torch_sps:.3f} steps/s "
                f"({time.perf_counter() - t0:.1f}s) → "
                f"vs_baseline={vs_baseline:.1f}x")
        except Exception as e:  # torch leg must never sink the bench
            log(f"[bench] torch baseline failed: {e!r}")

    # --- round artifacts: results produced by longer offline runs ----------
    # (the 100h corpus training and the adversarial eval take tens of
    # minutes — they run via their own scripts and check their reports in;
    # the bench surfaces the headline numbers with provenance)
    artifacts = {}
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "results")

    def _j100():
        # newest round first (scan, don't enumerate: a hardcoded round list
        # silently dropped the r5 chip-trained artifact from the line of
        # record until it was widened)
        cands = sorted(glob.glob(os.path.join(art_dir, "joint100h_r*.json")),
                       key=_round_of, reverse=True)
        p = cands[0] if cands else ""
        if not p:
            return None
        r = json.load(open(p))
        return {
            "hours": r.get("corpus_hours"),
            "edge_auc": r.get("metrics", {}).get("edge_auc"),
            "seq_f1": r.get("metrics", {}).get("seq_f1"),
            "steps_per_sec": r.get("steps_per_sec"),
            "provenance": "python -m nerrf_tpu.train.run "
                          "--experiment joint-100h",
        }

    def _adv():
        # preference: newest chip artifact, then the CPU probe artifact
        # (current code, small model), then older chip/CPU rounds — the r2
        # file predates the mutation gate + hardened corpus and would
        # misreport the current system
        # rounds <= 2 predate the mutation gate + hardened corpus and would
        # misreport the current system: they rank BELOW the probe artifact
        rounds = sorted(
            (q for q in glob.glob(os.path.join(art_dir, "adversarial_r*.json"))
             if _round_of(q) > 2),
            key=_round_of, reverse=True)
        p = next((q for q in rounds + [
            os.path.join(art_dir, "adversarial_probe_cpu.json"),
            os.path.join(art_dir, "adversarial_r2.json")]
            if os.path.exists(q)), "")
        if not p:
            return None
        r = json.load(open(p))
        return {
            "fp_undo_rate_worst": r.get("kpi", {}).get(
                "fp_undo_rate_worst_model"),
            "fp_undo_met": r.get("kpi", {}).get("fp_undo_met"),
            "node_threshold": r.get("node_threshold"),
            "source": os.path.basename(p),
            "provenance": "python benchmarks/run_adversarial_eval.py",
        }

    def _recovery():
        p = os.path.join(art_dir, "m1_recovery.json")
        if not os.path.exists(p):
            return None
        r = json.load(open(p))
        return {
            "mttr_seconds": r.get("kpis", {}).get("mttr_seconds"),
            "data_loss_bytes": r.get("kpis", {}).get("data_loss_bytes"),
            "false_positive_undos":
                r.get("kpis", {}).get("false_positive_undos"),
            "backend": r.get("backend"),
            "provenance": "python benchmarks/run_recovery_bench.py "
                          "--scale m1",
        }

    def _tracker():
        p = os.path.join(art_dir, "tracker_perf.json")
        if not os.path.exists(p):
            return None
        r = json.load(open(p))
        return {
            "events_per_sec_sustained":
                r.get("paced", {}).get("events_per_sec_sustained"),
            "p50_latency_us":
                r.get("paced", {}).get("delivery_latency_us", {}).get("p50"),
            "flood_events_per_sec":
                r.get("flood", {}).get("events_per_sec_sustained"),
            "provenance": "python benchmarks/run_tracker_bench.py",
        }

    # per-artifact isolation: one truncated/corrupt JSON on disk must not
    # silently drop the valid artifacts after it
    for key, loader in (("corpus100h", _j100), ("adversarial", _adv),
                        ("m1_recovery", _recovery), ("tracker", _tracker)):
        try:
            entry = loader()
            if entry is not None:
                artifacts[key] = entry
        except Exception as e:
            log(f"[bench] artifact surfacing for {key} failed: {e!r}")

    try:
        from nerrf_tpu.ops.segment import active_impls

        kernel_path = active_impls()
        # the flagship GNN's 28-layer aggregation no longer dispatches
        # segment kernels at all under dense_adj/fused — record the mode
        # (at the flagship node bucket: `auto` routes by bucket size) so
        # the kernel attribution can't silently mislead (r2 verdict weak
        # #5); the 4096 leg stamps its own mode in big_bucket
        kernel_path["gnn_aggregation"] = cfg.model.gnn.resolved_aggregation(
            cap["max_nodes"])
        kernel_path["lstm_impl"] = cfg.model.lstm.resolved_impl()
    except Exception:
        kernel_path = None

    # the rollouts/s of record is what `nerrf undo` actually uses: the
    # on-device planner when a chip is present (make_planner kind='auto'),
    # the host planner otherwise
    headline_rollouts = device_rollouts_per_sec or rollouts_per_sec

    print(json.dumps({
        "metric": "nerrfnet_train_steps_per_sec",
        "value": round(steps_per_sec, 3),
        "unit": f"steps/s (batch=8 windows, {shape_tag}/128seq)",
        "vs_baseline": round(vs_baseline, 2) if vs_baseline else None,
        "vs_baseline_note": "same-arch torch on this host's CPU (no CUDA in "
                            "env; chip-side metric of record is mfu_pct)",
        "backend": backend,
        # a shrunk rehearsal must be distinguishable from the metric of
        # record
        "num_steps": cfg.num_steps,
        "rehearsal": (cfg.num_steps != 200) or None,
        "model_flops_per_step": round(step_flops) if step_flops else None,
        "flops_method": "analytic (dot_general/conv at logical shapes from "
                        "the step jaxpr; nerrf_tpu/bench/flops.py)",
        "xla_cost_analysis_flops_per_step":
            round(xla_step_flops) if xla_step_flops else None,
        "achieved_tflops":
            round(achieved_tflops, 2) if achieved_tflops else None,
        "mfu_pct": round(mfu_pct, 2) if mfu_pct else None,
        "steps_per_call": steps_per_call,
        "dispatch_rtt_ms": dispatch_rtt_ms,
        "attribution": {
            # where the flagship step time went (see docs/benchmarks.md):
            # host_blocked = waiting on device results, host_dispatch =
            # issuing work; data_wait is structurally 0 on the
            # device-resident schedule; padding waste per capacity bucket
            "host_blocked_fraction": round(host_blocked_fraction, 4),
            "host_dispatch_fraction": round(dispatch_s / elapsed, 4),
            "data_wait_fraction": 0.0,
            "padding_waste": {shape_tag: padding_waste},
        },
        "sync_method": "device-to-host fetch of the final loss",
        "big_bucket": big_bucket,
        "edge_roc_auc": round(metrics["edge_auc"], 4),
        "seq_f1": round(metrics["seq_f1"], 4),
        "mcts_rollouts_per_sec":
            round(headline_rollouts, 1) if headline_rollouts else None,
        "mcts_host_rollouts_per_sec":
            round(rollouts_per_sec, 1) if rollouts_per_sec else None,
        "mcts_device_rollouts_per_sec":
            round(device_rollouts_per_sec, 1)
            if device_rollouts_per_sec else None,
        "compile_seconds": compile_seconds or None,
        # device truth (nerrf_tpu/devtime): per-program analytic-vs-
        # cost_analysis FLOPs
        "device_truth": {
            "flops_authority": "analytic jaxpr counters (bench/flops.py); "
                               "cost_analysis recorded as cross-check only",
            "train_step": {
                "analytic_flops":
                    round(step_flops) if step_flops else None,
                "cost_analysis_flops":
                    round(xla_step_flops) if xla_step_flops else None,
                "cost_analysis_over_analytic":
                    (round(xla_step_flops / step_flops, 2)
                     if step_flops and xla_step_flops else None),
                "mfu_pct": round(mfu_pct, 2) if mfu_pct else None,
            },
            "chip": {
                "device_kind": getattr(jax.devices()[0], "device_kind", ""),
                "peak_tflops_bf16": chip.tflops_bf16 if chip else None,
                "peak_hbm_gbps": chip.hbm_gbps if chip else None,
                "ridge_flops_per_byte":
                    round(chip.ridge_flops_per_byte, 1) if chip else None,
            },
        },
        "kernel_path": kernel_path,
        "stream_events_per_sec":
            round(stream_events_per_sec) if stream_events_per_sec else None,
        "torch_cpu_steps_per_sec": round(torch_sps, 3) if torch_sps else None,
        "artifacts": artifacts or None,
        "wall_seconds": round(time.perf_counter() - t_wall, 1),
    }))


if __name__ == "__main__":
    main()
