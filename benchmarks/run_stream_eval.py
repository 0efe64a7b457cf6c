#!/usr/bin/env python3
"""Stream (long-context) detector quality probe: held-out per-event AUC.

The StreamNet path — whole-trace 4096-event streams, flash-style blockwise
attention, ring attention over `sp` at scale — is this framework's one
genuinely TPU-first addition over the reference's windowed-graph design
(`/root/reference/docs/content/docs/architecture.mdx:32-43` specifies
windows only).  Its *throughput* is measured by bench.py's stream leg on
chip; this probe measures the other half nothing else covers: does the
stream detector actually detect, at event granularity, on held-out traces?

Protocol: train a StreamNet on streams from N simulated incidents
(attack + benign mixed, adversarial scenarios included — r4 adds the
stealth family and the atomic-rewrite hard negative), CALIBRATE a per-event
operating threshold on a held-out calibration split, then report
precision/recall/F1 *at that fixed threshold* on a disjoint test split
(unseen seeds), alongside AUC and the best-F1 oracle for reference.  The
trained weights + calibrated threshold are saved as a stream checkpoint
(train.checkpoint.save_stream_checkpoint) so the operating point travels
with the model, exactly like the joint detector's node_threshold (VERDICT
r3 item 5: best-F1 alone is an oracle number no deployment can reproduce).

CPU-scale by default (~small model, short streams) so it runs with or
without the accelerator; on chip the same script measures the flagship
shapes.

Usage:
  python benchmarks/run_stream_eval.py --platform cpu \
      --out benchmarks/results/stream_probe_cpu.json
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np


def _log(msg):
    print(f"[stream-eval] {msg}", file=sys.stderr, flush=True)


def _traces(n, base_seed, duration_sec, files, rate):
    from nerrf_tpu.data.synth import SimConfig, simulate_trace

    # stealth family interleaved early: at small split sizes the rotation
    # must still reach no-rename attacks, or the calibrated threshold and
    # the reported AUC never see the hardest positives (the r4 default
    # split sizes below cover every family at least once per split)
    atk_scenarios = ("standard", "inplace-stealth", "slow-drip",
                     "partial-encrypt", "multi-process",
                     "interleaved-backup", "benign-comm", "exfil-encrypt")
    # benign traces rotate plain background with the hard-negative jobs —
    # rename-shaped (mass-rename) and write→rename-shaped (atomic-rewrite)
    # benign activity is what trips rename-keyed detectors, and a stream
    # AUC that never saw them would overstate robustness
    ben_scenarios = ("standard", "benign-mass-rename",
                     "benign-atomic-rewrite")
    out = []
    for i in range(n):
        attack = i % 2 == 0
        # attack traces are the EVEN i, so index each rotation by i//2 —
        # `i % len` would only ever reach the even-indexed scenarios and
        # silently skip the odd-indexed ones
        scenario = (atk_scenarios[(i // 2) % len(atk_scenarios)] if attack
                    else ben_scenarios[(i // 2) % len(ben_scenarios)])
        out.append(simulate_trace(SimConfig(
            duration_sec=duration_sec, num_target_files=files,
            benign_rate_hz=rate, attack=attack, scenario=scenario,
            seed=base_seed + 101 * i, attack_start_sec=duration_sec * 0.35,
        ), name=f"stream-{'atk' if attack else 'ben'}-{i}"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="benchmarks/results/stream_probe_cpu.json")
    ap.add_argument("--platform", default=None,
                    help="force a JAX platform before backend init "
                         "(same effect as JAX_PLATFORMS in the environment)")
    # split sizes sized to the scenario rotation: 16 traces = 8 attacks =
    # one full pass over every attack family (and 2⅔ passes over the benign
    # rotation) — smaller splits would silently measure a subset of the
    # families the header claims (r4 review finding)
    ap.add_argument("--train-traces", type=int, default=16)
    ap.add_argument("--calib-traces", type=int, default=16,
                    help="held-out traces the operating threshold is "
                         "calibrated on (disjoint seeds from --eval-traces)")
    ap.add_argument("--eval-traces", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=500)
    ap.add_argument("--ckpt-dir", default="runs/stream-probe",
                    help="save the trained StreamNet + calibrated threshold "
                         "sidecar here ('' skips)")
    args = ap.parse_args(argv)

    from nerrf_tpu.utils import enable_compilation_cache, sync_result

    enable_compilation_cache()
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp  # noqa: F401  (backend init after pin)

    from nerrf_tpu.data import build_streams
    from nerrf_tpu.models import StreamConfig, StreamNet
    from nerrf_tpu.parallel import MeshConfig, make_mesh
    from nerrf_tpu.train.metrics import best_f1, f1_at_threshold, roc_auc

    t0 = time.time()
    backend = jax.default_backend()
    _log(f"backend={backend}")

    train_tr = _traces(args.train_traces, args.seed, 120.0, 16, 30.0)
    calib_tr = _traces(args.calib_traces, args.seed + 3571, 120.0, 16, 30.0)
    eval_tr = _traces(args.eval_traces, args.seed + 7919, 120.0, 16, 30.0)
    train_sb = build_streams(train_tr, max_len=args.max_len)
    calib_sb = build_streams(calib_tr, max_len=args.max_len)
    eval_sb = build_streams(eval_tr, max_len=args.max_len)
    pos = float(train_sb.label[train_sb.mask].mean())
    _log(f"streams: {len(train_sb)} train / {len(calib_sb)} calib / "
         f"{len(eval_sb)} eval segments of "
         f"{args.max_len} events (train positive rate {pos:.3f})")

    # the trainer's own stream entry (`train.stream.train_stream`: make_tx,
    # the resident scheduled step, the train_step_call span): the same one
    # `python -m nerrf_tpu.train.run` takes for a stream experiment
    from nerrf_tpu.train.loop import TrainConfig
    from nerrf_tpu.train.stream import train_stream

    mesh = make_mesh(MeshConfig(dp=1, tp=1, sp=1), devices=jax.devices()[:1])
    cfg = StreamConfig()
    model = StreamNet(cfg, mesh=mesh)
    with mesh:
        t_train = time.perf_counter()
        res = train_stream(
            train_sb.arrays(), cfg,
            TrainConfig(batch_size=args.batch, num_steps=args.steps,
                        learning_rate=1e-3, warmup_steps=min(20, args.steps // 4),
                        weight_decay=1e-4, seed=args.seed,
                        eval_every=max(args.steps // 4, 1)), log=_log)
        state, loss = res.state, res.metrics["final_loss"]
        train_secs = time.perf_counter() - t_train
        _log(f"trained {args.steps} steps in {train_secs:.1f}s "
             f"(final loss {float(loss):.4f})")

        # --- held-out scoring: masked per-event scores ---------------------
        @jax.jit
        def fwd(params, batch):
            return model.apply({"params": params}, batch["feat"],
                               batch["mask"], deterministic=True)

        def score_split(sb):
            scores, labels = [], []
            arrs = sb.arrays()
            for i in range(0, len(sb), args.batch):
                idx = np.arange(i, min(i + args.batch, len(sb)))
                # fixed batch shape (wrap tail) → one compile
                full = np.resize(idx, args.batch)
                batch = {k: jnp.asarray(v[full]) for k, v in arrs.items()}
                out = jax.device_get(fwd(state.params, batch))
                logits = out["event_logits"][: len(idx)]
                for j in range(len(idx)):
                    m = arrs["mask"][idx[j]]
                    scores.append(logits[j][m])
                    labels.append(arrs["label"][idx[j]][m])
            return np.concatenate(scores), np.concatenate(labels)

        cs, cl = score_split(calib_sb)
        s, l = score_split(eval_sb)
    # operating threshold: best-F1 on the CALIBRATION split (the stream
    # head's KPI is F1, so the F1-optimal calib cut is the right operating
    # point — unlike the file detector, whose KPI is a precision floor);
    # everything reported on the test split at that FIXED cut
    calib_f1, t_cal = best_f1(cl, cs)
    auc = roc_auc(l, s)
    at_cal = f1_at_threshold(l, s, t_cal)
    f1_oracle, _t = best_f1(l, s)
    _log(f"calibrated threshold {t_cal:.4f} (calib F1 {calib_f1:.4f}); "
         f"held-out: {len(l)} events, event_auc={auc:.4f} "
         f"f1@threshold={at_cal['f1']:.4f} (oracle best_f1={f1_oracle:.4f})")

    calibration = {
        "stream_event_threshold": round(float(t_cal), 4),
        "stream_event_threshold_kind": "calib-split-best-f1",
        # the cut lives in RAW LOGIT space (best_f1 sweeps event_logits,
        # never sigmoided) — unlike the joint model's node_threshold, which
        # is a probability.  Recorded explicitly so a consumer mirroring
        # node_threshold usage can't mis-apply it (r4 advisor).
        "stream_event_threshold_space": "logit",
        "calib_f1": round(float(calib_f1), 4),
    }
    if args.ckpt_dir:
        from nerrf_tpu.train.checkpoint import save_stream_checkpoint

        save_stream_checkpoint(args.ckpt_dir, state.params, cfg,
                               calibration=calibration)
        _log(f"stream checkpoint + threshold sidecar → {args.ckpt_dir}")

    report = {
        "backend": backend,
        "model": {"dim": cfg.dim, "num_layers": cfg.num_layers,
                  "heads": cfg.num_heads, "max_len": args.max_len},
        "train": {"traces": args.train_traces, "segments": len(train_sb),
                  "steps": args.steps, "batch": args.batch,
                  "seconds": round(train_secs, 1),
                  "steps_per_sec": round(args.steps / train_secs, 3)},
        "calibration": calibration | {"traces": args.calib_traces,
                                      "events": int(len(cl))},
        "eval": {"traces": args.eval_traces, "segments": len(eval_sb),
                 "events": int(len(l)),
                 "positive_rate": round(float(l.mean()), 4)},
        "metrics": {"event_auc": round(float(auc), 4),
                    "event_f1_at_threshold": round(float(at_cal["f1"]), 4),
                    "event_precision_at_threshold":
                        round(float(at_cal["precision"]), 4),
                    "event_recall_at_threshold":
                        round(float(at_cal["recall"]), 4),
                    "event_best_f1": round(float(f1_oracle), 4)},
        "gates": {"event_auc>=0.90": bool(auc >= 0.90),
                  # the seq-head spec bar (architecture.mdx:59) applied to
                  # the DEPLOYED operating point, not the oracle sweep
                  "event_f1@threshold>=0.95": bool(at_cal["f1"] >= 0.95)},
        "ckpt_dir": args.ckpt_dir or None,
        "provenance": "python benchmarks/run_stream_eval.py",
        "wall_seconds": round(time.time() - t0, 1),
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(report["metrics"] | report["gates"]))
    return 0 if auc >= 0.90 else 1


if __name__ == "__main__":
    raise SystemExit(main())
