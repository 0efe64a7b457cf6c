#!/usr/bin/env python3
"""Adversarial detector evaluation: per-scenario quality + FP-undo rate.

AUC 0.997 on easy synthetic data says little about the <5% false-positive
undo KPI (`/root/reference/README.md:27`, threat-model.mdx:275-319) under an
adversarial mix — this harness measures it (VERDICT r1 item 5).  Scenarios
(data/synth.py SimConfig.scenario):

  standard            the five-phase attack the detectors train on
  benign-mass-rename  hard negative: archive job bulk-renames the target dir
  slow-drip           attack stretched across ~80% of the trace
  benign-comm         attack under the benign python3 worker's pid+comm
  multi-process       attack sharded over 4 interleaved pids

r4 adds the scenarios the indicator heuristic provably FAILS (VERDICT r3
item 3 — the learned model must demonstrate a measured gap over the
closed-form rules, or it isn't worth its parameters):

  inplace-stealth     in-place encryption: no rename, extensions kept,
                      non-README note — every heuristic indicator absent
  partial-encrypt     head-only in-place encryption, minimal bytes moved
  interleaved-backup  encryption racing the benign backup sweep over the
                      same files; the only renames in the trace are benign
  exfil-encrypt       staged read-exfil → dwell → partial encrypt
  benign-atomic-rewrite  hard negative: atomic-save rewrites fire the
                      write→rename motif on every file (heuristic FP probe)

For each scenario × {heuristic, model} detector:
  * window-level edge ROC-AUC / seq F1 (where the scenario has positives)
  * file-level product metrics: detection rate over actually-encrypted
    files, and the FP-undo rate = benign files among all files the pipeline
    would roll back (the KPI; measured at the pipeline's operating
    threshold — the checkpoint's held-out-calibrated node_threshold when
    one exists, the historical 0.5 otherwise; reported as node_threshold).
    The robust-aggregation leg runs at its own calibrated cut when the
    sidecar carries one (node_threshold_robust), else at the max cut with
    a report note (r3 advisor).

The summary's ``heuristic_gap`` lists, per scenario, model detection minus
heuristic detection at matched FP-undo discipline — the deliverable is a
measured gap in the model's favor on the stealth family.

Usage:
  python benchmarks/run_adversarial_eval.py --out benchmarks/results/adversarial.json
  ... --model-dir <ckpt>     # evaluate a trained checkpoint (e.g. joint-100h)
  ... --train-steps 300      # or train a fresh standard-corpus model
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np

SCENARIOS = ("standard", "benign-mass-rename", "slow-drip", "benign-comm",
             "multi-process", "inplace-stealth", "partial-encrypt",
             "interleaved-backup", "exfil-encrypt", "benign-atomic-rewrite")


def _log(msg):
    print(f"[adv] {msg}", file=sys.stderr, flush=True)


def _scenario_traces(scenario: str, n: int, seed: int):
    from nerrf_tpu.data.synth import BENIGN_SCENARIOS, SimConfig, simulate_trace

    traces = []
    for i in range(n):
        attack = scenario not in BENIGN_SCENARIOS
        traces.append(simulate_trace(SimConfig(
            duration_sec=180.0, num_target_files=24, benign_rate_hz=40.0,
            attack=attack, scenario=scenario, seed=seed + 37 * i,
            attack_start_sec=70.0,
        ), name=f"{scenario}-{i}"))
    return traces


def _attacked_files(trace) -> tuple[set, set]:
    """(encrypted, attack_touched) ground truth — shared with threshold
    calibration via pipeline.attack_touched_files (one label derivation)."""
    from nerrf_tpu.pipeline import attack_touched_files

    return attack_touched_files(trace)


def _file_metrics(items, detect) -> dict:
    """items: (trace, payload) pairs; ``detect(item)`` → DetectionResult.
    Payload carries a precomputed detection so aggregation variants don't
    re-run the model."""
    tp = fp = 0
    attacked_total = 0
    flagged_total = 0
    for item in items:
        tr = item[0]
        det = detect(item)
        # the detection's own operating point: the checkpoint's held-out
        # calibrated threshold when one exists, 0.5 otherwise — measuring a
        # calibrated model at someone else's cut misreports its FP behavior
        flagged = set(det.flagged_files())
        encrypted, touched = _attacked_files(tr)
        attacked_total += len(encrypted)
        flagged_total += len(flagged)
        tp += len(flagged & encrypted)
        # an undo of a file the attack never touched reverts legitimate work
        fp += len(flagged - touched)
    return {
        "files_attacked": attacked_total,
        "files_flagged": flagged_total,
        "detection_rate": round(tp / attacked_total, 4) if attacked_total else None,
        "fp_undo_rate": round(fp / flagged_total, 4) if flagged_total else 0.0,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="benchmarks/results/adversarial.json")
    ap.add_argument("--model-dir", default=None,
                    help="trained checkpoint (nerrf_tpu.train.checkpoint); "
                         "default: train a fresh standard-corpus model")
    ap.add_argument("--train-steps", type=int, default=300)
    ap.add_argument("--train-traces", type=int, default=24,
                    help="fresh-model path: corpus size (hard-scenario mix "
                         "needs enough traces to cover the variants)")
    ap.add_argument("--traces", type=int, default=6)
    ap.add_argument("--seed", type=int, default=77)
    ap.add_argument("--platform", default=None,
                    help="force a JAX platform (e.g. 'cpu') before backend "
                         "init; same effect as JAX_PLATFORMS in the "
                         "environment")
    args = ap.parse_args(argv)

    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from nerrf_tpu.data.synth import make_corpus
    from nerrf_tpu.models import NerrfNet
    from nerrf_tpu.pipeline import heuristic_detect, model_detect
    from nerrf_tpu.train import TrainConfig, build_dataset
    from nerrf_tpu.train.loop import evaluate, make_eval_fn, train_nerrfnet

    t0 = time.time()
    backend = jax.default_backend()
    _log(f"backend={backend}")

    if args.model_dir:
        from nerrf_tpu.train.checkpoint import load_calibration, load_checkpoint

        params, model_cfg = load_checkpoint(args.model_dir)
        model = NerrfNet(model_cfg)
        trained_on = f"checkpoint:{args.model_dir}"
        calib = load_calibration(args.model_dir)
        node_threshold = calib.get("node_threshold")
        robust_threshold = calib.get("node_threshold_robust")
    else:
        corpus = make_corpus(args.train_traces, attack_fraction=0.5,
                             base_seed=args.seed,
                             duration_sec=180.0, num_target_files=24,
                             benign_rate_hz=40.0, hard_scenarios=True)
        cfg = TrainConfig(batch_size=8, num_steps=args.train_steps,
                          eval_every=100, seed=args.seed)
        res = train_nerrfnet(build_dataset(corpus), cfg=cfg, log=_log)
        params, model = res.state.params, NerrfNet(cfg.model)
        trained_on = f"fresh standard corpus ({args.train_steps} steps)"
        from nerrf_tpu.pipeline import calibrate_file_thresholds

        cals = calibrate_file_thresholds(params, model, log=_log)
        node_threshold = cals["max"].threshold if cals.get("max") else None
        robust_threshold = (cals["robust"].threshold
                            if cals.get("robust") else None)
    eval_fn = make_eval_fn(model)
    _log(f"file-detector operating threshold: "
         f"{node_threshold if node_threshold is not None else '0.5 (default)'}"
         f" / robust {robust_threshold if robust_threshold is not None else '(max cut)'}")

    from nerrf_tpu.data.synth import BENIGN_SCENARIOS, STEALTH_SCENARIOS

    report = {"backend": backend, "trained_on": trained_on,
              "node_threshold": node_threshold,
              "robust_threshold": robust_threshold,
              # r3 advisor: when no robust-calibrated cut exists the robust
              # leg runs at the max-calibrated operating point, which can
              # understate its detection (robust scores ≤ max scores)
              "robust_leg_note": None if robust_threshold is not None else
              "robust leg measured at the max-calibrated cut",
              "scenarios": {}}
    worst_fp = 0.0
    for scenario in SCENARIOS:
        _log(f"scenario {scenario}…")
        traces = _scenario_traces(scenario, args.traces, args.seed + 1000)
        entry = {}
        # window-level metrics need positive labels; capacities must fit the
        # scenario's densest window or the AUC measures truncation, not the
        # model (train/data.py fit_dataset_config)
        if scenario not in BENIGN_SCENARIOS:
            from nerrf_tpu.train.data import fit_dataset_config

            ds = build_dataset(traces, fit_dataset_config(traces))
            m = evaluate(eval_fn, params, ds)
            entry["edge_auc"] = round(m["edge_auc"], 4)
            entry["seq_f1"] = round(m["seq_f1"], 4)
        # one model pass per trace; both aggregation rules derived from the
        # cached per-window scores (pipeline.DetectionResult.rescored)
        detections = [model_detect(tr, params, model,
                                   threshold=node_threshold)
                      for tr in traces]
        entry["model"] = _file_metrics(
            list(zip(traces, detections)), lambda td: td[1])
        entry["model_robust"] = _file_metrics(
            list(zip(traces, detections)),
            lambda td: td[1].rescored("robust") if robust_threshold is None
            else dataclasses.replace(td[1].rescored("robust"),
                                     threshold=robust_threshold))
        entry["heuristic"] = _file_metrics(
            [(tr, None) for tr in traces], lambda td: heuristic_detect(td[0]))
        report["scenarios"][scenario] = entry
        worst_fp = max(worst_fp, entry["model"]["fp_undo_rate"])
        _log(f"  {scenario}: {json.dumps(entry)}")

    worst_fp_robust = max(
        e["model_robust"]["fp_undo_rate"]
        for e in report["scenarios"].values())
    # The model-vs-heuristic deliverable (VERDICT r3 item 3): per attack
    # scenario, detection-rate gap in the model's favor; per benign
    # scenario, FP-undo gap in the model's favor.  Positive = model wins.
    gap = {}
    for sc, e in report["scenarios"].items():
        if sc in BENIGN_SCENARIOS:
            gap[sc] = round(e["heuristic"]["fp_undo_rate"]
                            - e["model"]["fp_undo_rate"], 4)
        else:
            gap[sc] = round((e["model"]["detection_rate"] or 0.0)
                            - (e["heuristic"]["detection_rate"] or 0.0), 4)
    stealth_won = [sc for sc in STEALTH_SCENARIOS
                   if (report["scenarios"][sc]["model"]["detection_rate"]
                       or 0.0) >= 0.95
                   and report["scenarios"][sc]["model"]["fp_undo_rate"] < 0.05
                   and (report["scenarios"][sc]["heuristic"]["detection_rate"]
                        or 0.0) <= 0.05]
    report["heuristic_gap"] = gap
    report["kpi"] = {
        "fp_undo_rate_worst_model": round(worst_fp, 4),
        "fp_undo_rate_worst_model_robust": round(worst_fp_robust, 4),
        "fp_undo_kpi": 0.05,
        "fp_undo_met": bool(worst_fp < 0.05),
        "fp_undo_met_robust": bool(worst_fp_robust < 0.05),
        # scenarios where the heuristic is blind (≤5% detection) and the
        # model detects ≥95% of victims at <5% FP-undo — the r4 bar
        "stealth_scenarios_model_wins": sorted(stealth_won),
        "model_beats_heuristic": bool(stealth_won),
    }
    report["wall_seconds"] = round(time.time() - t0, 1)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(report["kpi"]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
