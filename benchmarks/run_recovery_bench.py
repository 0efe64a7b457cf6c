#!/usr/bin/env python3
"""M0/M1-style recovery benchmark, retargeted at this framework.

The reference's benchmark (`/root/reference/benchmarks/m1/scripts/`) measured
a kubectl-exec rename-back loop (44 ms / 45 files / 2,500 MB/s,
`m1_recovery_results.json`) — possible only because its simulator left
plaintext behind the ransom extension.  This harness measures the honest
pipeline end-to-end on real destroyed data:

  seed + snapshot → XOR-encrypt attack → detect → MCTS plan → sandbox gate →
  verified restore,

and emits the reference's metrics schema (recovery duration, files/s, MB/s)
plus the product KPIs (`threat-model.mdx:275-319`): MTTR, data loss,
false-positive undo rate.

Usage: python benchmarks/run_recovery_bench.py [--scale m0|m1] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["m0", "m1"], default="m1")
    ap.add_argument("--out", default=None)
    ap.add_argument("--simulations", type=int, default=800)
    ap.add_argument("--planner", choices=("auto", "host", "device"),
                    default="auto")
    args = ap.parse_args()

    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    from nerrf_tpu.pipeline import build_undo_domain, heuristic_detect
    from nerrf_tpu.planner import MCTSConfig, make_planner
    from nerrf_tpu.planner.value_net import ValueNet
    from nerrf_tpu.rollback import (
        FileSimConfig,
        RollbackExecutor,
        SandboxGate,
        SnapshotStore,
        run_file_attack,
    )
    from nerrf_tpu.rollback.filesim import seed_files

    log = lambda *a: print(*a, file=sys.stderr, flush=True)
    # M0: 25 files ~12 MB total; M1: 45 files ~110 MB total (reference
    # metadata.json values)
    cfg = (
        FileSimConfig(num_files=25, min_file_bytes=300_000, max_file_bytes=700_000)
        if args.scale == "m0"
        else FileSimConfig(num_files=45, min_file_bytes=2_000_000, max_file_bytes=5_000_000)
    )

    tmp = Path(tempfile.mkdtemp(prefix=f"nerrf-bench-{args.scale}-"))
    victim = tmp / "victim"
    try:
        seed_files(victim, cfg)
        store = SnapshotStore(tmp / "store")
        manifest = store.snapshot(victim, "pre-attack")
        total_bytes = sum(sz for _, sz, _ in manifest.files.values())
        log(f"[{args.scale}] seeded {len(manifest.files)} files "
            f"({total_bytes / 1e6:.1f} MB), snapshot taken")

        # Daemon-boot warmup, OUTSIDE the recovery window: a deployed nerrf
        # daemon compiles the bucketed device-search executable and the
        # value-net architecture once at startup (planner/device_mcts.py
        # program cache), so an incident plans against a warm program.  The
        # attack hasn't happened yet — nothing incident-specific leaks in.
        value = ValueNet.create()
        planner_cfg = MCTSConfig(num_simulations=args.simulations)
        planner_kind = args.planner
        if planner_kind != "host":
            # auto now means the device program on every backend (see
            # make_planner: 4.2× the host search even on CPU), so the
            # daemon-boot warmup runs for every non-host request — but a
            # failed warmup must not sink the bench when auto can still
            # fall back to the host search (explicit --planner device
            # keeps the hard failure: the operator asked for that program).
            # On failure, pin auto to host HERE: letting make_planner retry
            # the identical build inside the measured window would charge
            # the same compile failure to the artifact's plan time.
            from nerrf_tpu.planner.device_mcts import DeviceMCTS

            t_warm = time.perf_counter()
            try:
                DeviceMCTS.warmup_for(
                    1, 1, cfg=planner_cfg, value_apply=value.apply_fn,
                    value_params=value.params)
                log(f"[{args.scale}] device planner warm "
                    f"({time.perf_counter() - t_warm:.1f}s boot-time compile)")
            except Exception as e:  # noqa: BLE001
                if planner_kind == "device":
                    raise
                log(f"[{args.scale}] device planner warmup failed "
                    f"({type(e).__name__}: {e}); using the host search")
                planner_kind = "host"

        t_attack = time.perf_counter()
        trace, encrypted = run_file_attack(victim, cfg)
        attack_s = time.perf_counter() - t_attack
        log(f"[{args.scale}] attack: {len(encrypted)} files encrypted in {attack_s:.2f}s")

        # --- the measured recovery window (detect → plan → gate → execute) --
        t0 = time.perf_counter()
        detection = heuristic_detect(trace)
        t_detect = time.perf_counter() - t0

        domain = build_undo_domain(detection, manifest, root=str(victim))
        value.fit_to_domain(domain, num_rollouts=256, horizon=32, steps=200)
        planner = make_planner(domain, value, planner_cfg, kind=planner_kind)
        planner_kind = type(planner).__name__
        plan = planner.plan()
        t_plan = time.perf_counter() - t0 - t_detect

        gate = SandboxGate(store, manifest).rehearse(plan, victim, trace=trace)
        if not gate.approved:
            log(f"GATE REJECTED: {gate.reason}")
            return 3
        t_gate = time.perf_counter() - t0 - t_detect - t_plan

        ex = RollbackExecutor(store, manifest, victim)
        report = ex.execute(plan)
        mttr = time.perf_counter() - t0

        # --- KPIs ------------------------------------------------------------
        residual = store.diff(manifest, victim)
        data_loss_b = sum(
            manifest.files[k][1] for k, v in residual.items()
            if v in ("missing", "modified") and k in manifest.files
        )
        # false-positive undos: restored files that the attack never touched
        attacked_names = {e.name[: -len(cfg.ransom_ext)] for e in encrypted}
        fp_reverted = sum(
            1 for d in report.details
            if d["result"] == "restored" and Path(d["target"]).name not in attacked_names
        )
        clean_total = max(len(manifest.files) - len(encrypted), 0)
        fp_rate = fp_reverted / clean_total if clean_total else 0.0
        import jax

        result = {
            "scale": args.scale,
            # provenance: CPU-fallback artifacts must be distinguishable
            # from chip artifacts at the schema level, not just in prose
            "backend": jax.default_backend(),
            "attack": {
                "files": len(encrypted),
                "total_bytes": total_bytes,
                "duration_seconds": round(attack_s, 3),
            },
            "recovery": {
                "recovery_duration_ms": round(report.duration_seconds * 1000, 1),
                "files_recovered": report.files_restored,
                "files_per_second": round(report.files_per_sec, 1),
                "throughput_mbps": round(report.mb_per_sec, 1),
                "verified": report.verified,
            },
            "kpis": {
                "mttr_seconds": round(mttr, 2),
                "mttr_target_seconds": 3600,
                "data_loss_bytes": data_loss_b,
                "data_loss_target_bytes": 128 * 1024 * 1024,
                "false_positive_undos": fp_reverted,
                "false_positive_undo_rate": round(fp_rate, 4),
                "false_positive_rate_target": 0.05,
                "detect_seconds": round(t_detect, 3),
                "plan_seconds": round(t_plan, 3),
                "gate_seconds": round(t_gate, 3),
                "rollouts_per_sec": round(plan.rollouts_per_sec, 1),
                "planner": f"{args.planner}:{planner_kind}",
            },
            "reference_m1_recovery": {
                "note": "reference rename-back loop on intact plaintext "
                        "(benchmarks/m1/results/m1_recovery_results.json)",
                "recovery_duration_ms": 44,
                "files_per_second": 1022.72,
                "throughput_mbps": 2500,
            },
        }
        out = json.dumps(result, indent=2)
        if args.out:
            Path(args.out).write_text(out)
        print(out)
        ok = (
            report.verified
            and mttr < 3600
            and data_loss_b <= 128 * 1024 * 1024
        )
        return 0 if ok else 4
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
