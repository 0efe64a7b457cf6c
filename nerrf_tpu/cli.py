"""The nerrf command-line interface.

Implements the reference's specified CLI surface (`/root/reference/ROADMAP.md:86`:
``nerrf undo --id <attack>``, ``nerrf status``; `README.md:81-82`) plus the
workflow commands the local benchmark needs.  Usage:

    python -m nerrf_tpu.cli simulate       --incident DIR [--files N]
    python -m nerrf_tpu.cli train-detector --model-dir DIR [--steps N]
    python -m nerrf_tpu.cli undo           --incident DIR [--model-dir DIR]
                                           [--dry-run] [--no-gate]
    python -m nerrf_tpu.cli status         --incident DIR

An *incident directory* is the unit of state: victim files under ``victim/``,
the snapshot store under ``store/``, the captured trace, and every stage's
JSON artifact (plan.json, gate.json, report.json) — so ``status`` can always
reconstruct where an incident stands.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def _log(msg: str) -> None:
    print(f"[nerrf] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
def cmd_simulate(args) -> int:
    from nerrf_tpu.rollback import FileSimConfig, SnapshotStore, run_file_attack
    from nerrf_tpu.rollback.filesim import seed_files
    from nerrf_tpu.schema.events import events_to_jsonl

    inc = Path(args.incident)
    victim = inc / "victim"
    if victim.exists() and any(victim.iterdir()):
        _log(f"refusing to simulate: {victim} is not empty")
        return 2
    cfg = FileSimConfig(num_files=args.files, seed=args.seed)
    seed_files(victim, cfg)
    store = SnapshotStore(inc / "store")
    manifest = store.snapshot(victim, snapshot_id="pre-attack")
    _log(f"seeded {len(manifest.files)} files, snapshot 'pre-attack' taken")

    t0 = time.time()
    trace, encrypted = run_file_attack(victim, cfg)
    (inc / "trace.jsonl").write_text(events_to_jsonl(trace.events, trace.strings))
    (inc / "incident.json").write_text(json.dumps({
        "created": time.time(),
        "attack_family": trace.ground_truth.attack_family,
        "target": str(victim),
        "snapshot_id": "pre-attack",
        "files_encrypted": len(encrypted),
        "attack_seconds": round(time.time() - t0, 3),
    }, indent=2))
    _log(f"attack complete: {len(encrypted)} files encrypted, trace written")
    return 0


# --------------------------------------------------------------------------
def cmd_train_detector(args) -> int:
    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    from nerrf_tpu.data import make_corpus
    from nerrf_tpu.graph import GraphConfig
    from nerrf_tpu.models import GraphSAGEConfig, JointConfig, LSTMConfig
    from nerrf_tpu.train import TrainConfig, build_dataset, train_nerrfnet
    from nerrf_tpu.train.checkpoint import save_checkpoint
    from nerrf_tpu.train.data import DatasetConfig

    model_cfg = JointConfig(
        gnn=GraphSAGEConfig(hidden=args.hidden, num_layers=args.layers, dropout=0.05),
        lstm=LSTMConfig(hidden=args.hidden, num_layers=1, dropout=0.05),
    )
    n_eval = max(2, args.traces // 4)
    if args.traces < n_eval + 4:
        _log(f"--traces must be ≥ {n_eval + 4} (need {n_eval} eval + ≥4 train runs)")
        return 2
    # hard-scenario mix: a deployed detector trained on rename-style attacks
    # alone re-learns the heuristic's shortcut (data/synth.py ATTACK_VARIANTS)
    corpus = make_corpus(args.traces, attack_fraction=0.5, base_seed=args.seed,
                         duration_sec=150.0, num_target_files=8,
                         benign_rate_hz=25.0, hard_scenarios=True)
    ds_cfg = DatasetConfig(graph=GraphConfig(max_nodes=256, max_edges=512),
                           seq_len=100, max_seqs=128)
    train_ds = build_dataset(corpus[:-n_eval], ds_cfg)
    eval_ds = build_dataset(corpus[-n_eval:], ds_cfg)
    _log(f"training detector on {len(train_ds)} windows ({args.steps} steps)…")
    train_cfg = TrainConfig(
        model=model_cfg, batch_size=8, num_steps=args.steps,
        learning_rate=3e-3, warmup_steps=min(30, args.steps // 5),
        # arming the health plane turns the in-step telemetry on with it:
        # divergence detection without grad/update norms is loss-only
        # (an armed archive wants the same records durable)
        telemetry=(args.metrics_port >= 0 or bool(args.flight_dir)
                   or bool(args.archive_dir)))
    compile_cache = None
    if not args.no_aot_cache:
        # persistent AOT cache (docs/compile-cache.md): a repeat run on an
        # unchanged config deserializes the step executable instead of
        # paying the train_step compile before step 0
        from nerrf_tpu.compilecache import CompileCache

        compile_cache = CompileCache(root=args.aot_cache, log=_log)
    # training-health plane (docs/training-health.md): /readyz with the
    # train-aware check + train_divergence/starvation/stall bundles —
    # both flags off costs the loop nothing
    from nerrf_tpu.trainwatch import training_health

    with training_health(metrics_port=args.metrics_port,
                         flight_dir=args.flight_dir,
                         archive_dir=args.archive_dir, log=_log) as monitor:
        if args.ckpt_every > 0:
            from nerrf_tpu.train.elastic import train_elastic

            res = train_elastic(
                train_ds, eval_ds, train_cfg,
                ckpt_dir=Path(args.model_dir) / "train_state",
                save_every=args.ckpt_every, log=_log,
                compile_cache=compile_cache, monitor=monitor)
        else:
            res = train_nerrfnet(train_ds, eval_ds, train_cfg, log=_log,
                                 compile_cache=compile_cache,
                                 monitor=monitor)
    if not res.metrics:
        # a divergence-halted run has no metrics and no usable weights —
        # the flight bundle (if armed) carries the evidence
        _log("training halted without metrics (diverged?); not saving a "
             "checkpoint")
        return 1
    _log(f"metrics: edge_auc={res.metrics['edge_auc']:.4f} "
         f"seq_f1={res.metrics['seq_f1']:.4f} ({res.steps_per_sec:.1f} steps/s)")
    save_checkpoint(args.model_dir, res.state.params, model_cfg)
    _log(f"checkpoint saved to {args.model_dir}")
    # calibrate the file-detector operating point and re-save the sidecar:
    # an uncalibrated checkpoint operates `nerrf undo` at the 0.5 cut that
    # measurably flags benign rotated logs (p≈0.80).  Shared helper — the
    # weights above are already safe on disk, and the helper guards the
    # node-head / multi-controller cases this inline copy used to miss.
    from nerrf_tpu.train.checkpoint import calibrate_and_resave

    calibrate_and_resave(args.model_dir, res.state.params, model_cfg,
                         node_loss_weight=train_cfg.node_loss_weight,
                         log=_log)
    if args.publish:
        # the train→serve hand-off: publish the calibrated checkpoint into
        # the registry lineage (immutable version, schema/feature-gated at
        # publish).  Promotion stays separate — a resident serve pod picks
        # the version up as a SHADOW candidate and promotes only when the
        # guardrails pass (docs/model-lifecycle.md).  Best-effort: a
        # registry failure must not turn a finished training run into a
        # CLI failure — the checkpoint is already safe under --model-dir.
        try:
            from nerrf_tpu.registry import ModelRegistry

            version = ModelRegistry(args.publish).publish(
                args.lineage, args.model_dir,
                source=f"nerrf train-detector --steps {args.steps}")
            _log(f"published {args.model_dir} as {args.lineage}/v{version} "
                 f"in {args.publish}")
        except Exception as e:  # noqa: BLE001
            _log(f"registry publish failed ({type(e).__name__}: {e}); "
                 f"checkpoint remains at {args.model_dir}")
    return 0 if res.metrics["edge_auc"] >= 0.9 else 1


# --------------------------------------------------------------------------
def cmd_undo(args) -> int:
    # undo is the MTTR-critical path and compiles detector + planner
    # programs — the persistent cache makes restart N+1's compiles free
    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    from nerrf_tpu.data.loaders import load_trace_jsonl
    from nerrf_tpu.pipeline import build_undo_domain, heuristic_detect, model_detect
    from nerrf_tpu.planner import MCTSConfig, make_planner
    from nerrf_tpu.planner.value_net import ValueNet
    from nerrf_tpu.rollback import RollbackExecutor, SandboxGate, SnapshotStore

    # Daemon-boot warmup, BEFORE the MTTR clock: compile the bucketed
    # device-search program (+ the value-net architecture) once, exactly
    # like run_recovery_bench's boot step — otherwise the CLI pays the XLA
    # compile inside the incident window that the published recovery
    # numbers exclude, and on a cold cache that compile can cost more than
    # the device search saves.  Best-effort: a failed warmup just means
    # make_planner's auto falls back to the host search.
    value = ValueNet.create()
    planner_kind = args.planner
    if planner_kind != "host":
        try:
            from nerrf_tpu.planner.device_mcts import DeviceMCTS

            t_warm = time.perf_counter()
            DeviceMCTS.warmup_for(
                1, 1, cfg=MCTSConfig(num_simulations=args.simulations),
                value_apply=value.apply_fn, value_params=value.params)
            _log(f"device planner warm "
                 f"({time.perf_counter() - t_warm:.1f}s boot-time compile)")
        except Exception as e:  # noqa: BLE001
            if planner_kind == "device":
                raise  # the operator asked for that program specifically
            _log(f"device planner warmup failed ({type(e).__name__}: {e}); "
                 "using the host search")
            planner_kind = "host"  # don't pay the same failure again in-window

    inc = Path(args.incident)
    meta = json.loads((inc / "incident.json").read_text())
    victim = Path(meta["target"])
    t_start = time.perf_counter()

    # --trace: detect on a trace OTHER than the incident's own file — the
    # end-to-end wire artifact points this at the copy that crossed the
    # native daemon's HTTP/2 stream, so detection consumes daemon-delivered
    # bytes, not the simulator's local file
    trace = load_trace_jsonl(Path(args.trace) if args.trace
                             else inc / "trace.jsonl")
    store = SnapshotStore(inc / "store")
    manifest = store.load_manifest(meta["snapshot_id"])

    # --- detect -------------------------------------------------------------
    if args.model_dir:
        from nerrf_tpu.models import NerrfNet
        from nerrf_tpu.train.checkpoint import load_calibration, load_checkpoint

        params, model_cfg = load_checkpoint(args.model_dir)
        calib = load_calibration(args.model_dir)
        detection = model_detect(trace, params, NerrfNet(model_cfg),
                                 threshold=calib.get("node_threshold"))
    else:
        detection = heuristic_detect(trace)
    flagged = detection.flagged_files()
    _log(f"detect[{detection.detector}]: {len(flagged)}/{len(detection.file_scores)} "
         f"files flagged, {sum(1 for v in detection.proc_scores.values() if v > 0.5)} "
         "processes flagged")

    # --- plan ---------------------------------------------------------------
    domain = build_undo_domain(detection, manifest, root=str(victim))
    # `value` was created at boot (before the MTTR clock) so its
    # architecture is already compiled; fit_to_domain only retrains weights
    value.fit_to_domain(domain, num_rollouts=256, horizon=32, steps=200)
    planner = make_planner(domain, value, MCTSConfig(
        num_simulations=args.simulations), kind=planner_kind)
    plan = planner.plan()
    (inc / "plan.json").write_text(json.dumps(plan.to_dict(), indent=2))
    _log(f"plan[{type(planner).__name__}]: {len(plan.actions)} actions, "
         f"{plan.rollouts} rollouts @ {plan.rollouts_per_sec:.0f}/s")

    # --- sandbox gate: clone → replay the captured trace → rehearse --------
    if not args.no_gate:
        gate = SandboxGate(store, manifest).rehearse(plan, victim, trace=trace)
        (inc / "gate.json").write_text(json.dumps(gate.to_dict(), indent=2))
        _log(f"sandbox gate: approved={gate.approved} ({gate.reason})")
        if not gate.approved:
            return 3

    if args.dry_run:
        _log("dry run: stopping before execution")
        return 0

    # --- execute ------------------------------------------------------------
    ex = RollbackExecutor(store, manifest, victim)
    report = ex.execute(plan)
    mttr = time.perf_counter() - t_start
    out = report.to_dict()
    out["mttr_seconds"] = round(mttr, 3)
    (inc / "report.json").write_text(json.dumps(out, indent=2))
    _log(f"rollback: {report.files_restored} files restored "
         f"({report.mb_per_sec:.0f} MB/s), verified={report.verified}, "
         f"MTTR={mttr:.2f}s")
    return 0 if report.verified else 4


# --------------------------------------------------------------------------
def cmd_models(args) -> int:
    """Model lifecycle registry: publish → (shadow) → promote → rollback.
    Every action prints one JSON document; the registry layout and the
    promotion guardrails are documented in docs/model-lifecycle.md."""
    from nerrf_tpu.registry import ModelRegistry

    reg = ModelRegistry(args.registry)
    out: dict
    if args.models_cmd == "publish":
        if args.aot:
            # AOT sidecar at publish time: compile + serialize the serve
            # ladder's executables into <model-dir>/executables/ so every
            # pod booting this version skips the compile sweep.  Built
            # BEFORE publish so the sidecar rides the same atomic rename.
            from nerrf_tpu.utils import enable_compilation_cache

            enable_compilation_cache()
            from nerrf_tpu.compilecache import export_for_checkpoint

            export_for_checkpoint(args.model_dir, log=_log)
        version = reg.publish(args.lineage, args.model_dir,
                              source=args.source)
        out = {"lineage": args.lineage, "published": version,
               "path": str(reg.version_dir(args.lineage, version)),
               "executables": reg.executables_dir(
                   args.lineage, version) is not None}
        if args.promote:
            out["live"] = reg.promote(args.lineage, version)
    elif args.models_cmd == "list":
        lineages = [args.lineage] if args.lineage else reg.lineages()
        out = {"registry": str(reg.root),
               "lineages": {ln: reg.status(ln) for ln in lineages}}
    elif args.models_cmd == "promote":
        out = {"lineage": args.lineage,
               "live": reg.promote(args.lineage, args.version)}
    elif args.models_cmd == "rollback":
        out = {"lineage": args.lineage,
               "live": reg.rollback(args.lineage, args.version)}
    elif args.models_cmd == "status":
        out = reg.status(args.lineage)
    else:  # pragma: no cover — argparse enforces the choices
        _log(f"unknown models subcommand {args.models_cmd!r}")
        return 2
    print(json.dumps(out, indent=2))
    return 0


# --------------------------------------------------------------------------
def cmd_cache(args) -> int:
    """The persistent compile cache (docs/compile-cache.md): ``ls`` the
    entry inventory, ``prune`` to an LRU disk bound, ``verify`` entry
    integrity, and ``warm`` the serve bucket ladder into the cache so the
    next boot (pod, bench, queue step) deserializes instead of compiling."""
    from nerrf_tpu.compilecache import CompileCache, default_cache_dir

    root = args.cache_dir or default_cache_dir()
    if args.cache_cmd == "warm":
        # the provisioning sweep: boot a throwaway service through the
        # cache so every ladder bucket's executable lands on disk — the
        # CI/queue pre-flight runs this twice and asserts the second
        # sweep reports source=cache for every bucket
        from nerrf_tpu.utils import enable_compilation_cache

        enable_compilation_cache()
        from nerrf_tpu.models import JointConfig, NerrfNet
        from nerrf_tpu.serve import (
            OnlineDetectionService,
            ServeConfig,
            init_untrained_params,
        )

        cfg_kwargs = {}
        if args.buckets:
            cfg_kwargs["buckets"] = tuple(
                tuple(int(x) for x in b.split("x")) for b in args.buckets)
        cfg = ServeConfig(**cfg_kwargs)
        if args.model_dir:
            from nerrf_tpu.train.checkpoint import load_checkpoint

            params, model_cfg = load_checkpoint(args.model_dir)
            model = NerrfNet(model_cfg)
        else:
            # cache keys include the param pytree + architecture, so an
            # untrained sweep warms exactly the untrained-serve programs
            # (load tests, CI) — warming a real deployment needs its
            # checkpoint via --model-dir
            model = NerrfNet(JointConfig().small)
            params = init_untrained_params(model, cfg)
        cache = CompileCache(root=root, log=_log)
        svc = OnlineDetectionService(params, model, cfg=cfg,
                                     compile_cache=cache)
        svc.start(log=_log)
        svc.stop()
        print(json.dumps({
            "cache": str(cache.root),
            "warmup_seconds": svc.warmup_seconds,
            "source": svc.warmup_source,
        }, indent=2))
        if args.expect_cache:
            # the CI/queue pre-flight contract in one place: the sweep
            # must have deserialized EVERY ladder bucket (exit 1 on an
            # empty ladder or any non-cache source)
            bad = {t: s for t, s in svc.warmup_source.items()
                   if s != "cache"}
            if bad or not svc.warmup_source:
                _log(f"cache warm: --expect-cache FAILED — "
                     f"{bad or 'empty ladder'}")
                return 1
            _log(f"cache warm: {len(svc.warmup_source)} bucket(s) "
                 f"deserialized (source=cache)")
        return 0
    cache = CompileCache(root=root)
    if args.cache_cmd == "ls":
        entries = cache.entries()
        print(json.dumps({
            "cache": str(cache.root),
            "entries": entries,
            "total_bytes": sum(e["bytes"] for e in entries),
        }, indent=2))
        return 0
    if args.cache_cmd == "prune":
        evicted = cache.prune(max_bytes=args.max_bytes)
        entries = cache.entries()
        print(json.dumps({
            "cache": str(cache.root),
            "evicted": evicted,
            "kept": len(entries),
            "total_bytes": sum(e["bytes"] for e in entries),
        }, indent=2))
        return 0
    if args.cache_cmd == "verify":
        problems = cache.verify()
        print(json.dumps({
            "cache": str(cache.root),
            "entries": len(cache.entries()),
            "problems": problems,
        }, indent=2))
        return 1 if problems else 0
    _log(f"unknown cache subcommand {args.cache_cmd!r}")  # pragma: no cover
    return 2


# --------------------------------------------------------------------------
def cmd_quality(args) -> int:
    """The detection-quality plane's offline face (docs/quality.md):
    ``show`` renders a reference profile (checkpoint sidecar or bare
    JSON) or a flight bundle's live divergence table; ``compare`` PSIs
    two profiles against each other — score distribution, top-drifting
    window features, margin mass and alert-rate deltas."""
    from nerrf_tpu.quality import load_profile
    from nerrf_tpu.quality.sketch import psi, top_drifting

    def _load(path):
        """→ ("bundle", quality dict) | ("profile", QualityProfile)."""
        p = Path(path)
        if p.is_dir() and (p / "quality.json").is_file():
            return "bundle", json.loads((p / "quality.json").read_text())
        prof = load_profile(p)
        if prof is None:
            raise FileNotFoundError(
                f"{path} is neither a quality profile (no "
                f"quality_profile.json), nor a flight bundle with a "
                f"quality.json — the checkpoint may predate profiles")
        return "profile", prof

    if args.quality_cmd == "show":
        try:
            kind, obj = _load(args.path)
        except (FileNotFoundError, ValueError) as e:
            _log(str(e))
            return 2
        if kind == "bundle":
            if args.json:
                print(json.dumps(obj, indent=2))
                return 0
            from nerrf_tpu.flight.doctor import quality_section

            print("\n".join(quality_section(obj)))
            return 0
        if args.json:
            print(json.dumps(obj.to_dict(), indent=2))
            return 0
        s = obj.summary()
        print(f"quality profile (schema v{s['schema']}): "
              f"{s['windows']} windows / {s['node_scores']} node scores")
        print(f"  threshold {s['threshold']:g}  margin mass "
              f"{s['margin_mass']:g} (eps {s['margin_eps']:g})  "
              f"alert rate {s['alert_rate']:g}")
        q = s["score_quantiles"]
        print(f"  score quantiles p50/p90/p99: "
              f"{q['p50']}/{q['p90']}/{q['p99']}")
        for name in s["features"]:
            fq = obj.features[name].quantiles()
            print(f"  feature {name:<16} p50/p90/p99: "
                  f"{fq['p50']}/{fq['p90']}/{fq['p99']} "
                  f"({obj.features[name].total} samples)")
        return 0

    if args.quality_cmd == "compare":
        try:
            _, ref = _load(args.reference)
            _, other = _load(args.other)
        except (FileNotFoundError, ValueError) as e:
            _log(str(e))
            return 2
        if not hasattr(ref, "score") or not hasattr(other, "score"):
            _log("compare wants two PROFILES (use `show` for a bundle's "
                 "live table)")
            return 2
        score_psi = psi(ref.score, other.score)
        feats = top_drifting(ref.features, other.features)
        out = {
            "score_psi": round(score_psi, 4),
            "feature_psi": {k: round(v, 4) for k, v in feats},
            "margin_mass": {"reference": round(ref.margin_mass, 4),
                            "other": round(other.margin_mass, 4)},
            "alert_rate": {"reference": round(ref.alert_rate, 4),
                           "other": round(other.alert_rate, 4)},
            "windows": {"reference": ref.windows, "other": other.windows},
        }
        if args.json:
            print(json.dumps(out, indent=2))
        else:
            print(f"score PSI {score_psi:.4f} "
                  f"(<0.1 stable, 0.1-0.25 moderate, >0.25 major)")
            print("top drifting features:")
            for k, v in feats:
                print(f"  {k:<16} PSI {v:.4f}")
            print(f"margin mass {ref.margin_mass:.4f} -> "
                  f"{other.margin_mass:.4f}   alert rate "
                  f"{ref.alert_rate:.4f} -> {other.alert_rate:.4f}")
        if args.psi_threshold is not None:
            worst = max([score_psi] + [v for _, v in feats])
            if worst >= args.psi_threshold:
                _log(f"PSI {worst:.4f} >= {args.psi_threshold:g}")
                return 1
        return 0
    _log(f"unknown quality subcommand {args.quality_cmd!r}")
    return 2  # pragma: no cover — argparse enforces the choices


# --------------------------------------------------------------------------
def cmd_warmup(args) -> int:
    """Host-provisioning compile sweep: detector eval programs for every
    configured capacity bucket + the device planner, into the persistent
    compilation cache — so a COLD host's first incident pays zero XLA
    compile inside the MTTR window (the detector-side counterpart of the
    undo CLI's planner warmup; VERDICT r4 weak #7)."""
    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    import time as _t

    t0 = _t.perf_counter()
    out = {}
    if args.model_dir:
        from nerrf_tpu.models import NerrfNet
        from nerrf_tpu.pipeline import DETECTOR_WARMUP_BUCKETS, warmup_detector
        from nerrf_tpu.train.checkpoint import load_checkpoint

        params, model_cfg = load_checkpoint(args.model_dir)
        buckets = DETECTOR_WARMUP_BUCKETS
        if args.buckets:
            buckets = tuple(
                tuple(int(x) for x in b.split("x")) for b in args.buckets)
        out["detector"] = warmup_detector(params, NerrfNet(model_cfg),
                                          buckets=buckets, log=_log)
    try:
        from nerrf_tpu.planner import MCTSConfig
        from nerrf_tpu.planner.device_mcts import DeviceMCTS
        from nerrf_tpu.planner.value_net import ValueNet

        value = ValueNet.create()
        t1 = _t.perf_counter()
        DeviceMCTS.warmup_for(1, 1, cfg=MCTSConfig(num_simulations=800),
                              value_apply=value.apply_fn,
                              value_params=value.params)
        out["planner_seconds"] = round(_t.perf_counter() - t1, 1)
    except Exception as e:  # noqa: BLE001 — planner warmup is best-effort
        out["planner_error"] = f"{type(e).__name__}: {e}"
    out["wall_seconds"] = round(_t.perf_counter() - t0, 1)
    print(json.dumps(out, indent=2))
    return 0


# --------------------------------------------------------------------------
def _profile_model(args, cfg):
    """(params, model) for the profile subcommands: the checkpoint when
    given, else the untrained small detector (shapes and programs are
    what the cost/capture planes measure — weights don't matter)."""
    from nerrf_tpu.models import JointConfig, NerrfNet
    from nerrf_tpu.serve import init_untrained_params

    if getattr(args, "model_dir", None):
        from nerrf_tpu.train.checkpoint import load_checkpoint

        params, model_cfg = load_checkpoint(args.model_dir)
        return params, NerrfNet(model_cfg)
    model = NerrfNet(JointConfig().small)
    return init_untrained_params(model, cfg), model


def _profile_serve_cfg(args):
    from nerrf_tpu.serve import ServeConfig

    if getattr(args, "smoke", False):
        return ServeConfig(buckets=((64, 128, 32),))
    if getattr(args, "buckets", None):
        return ServeConfig(buckets=tuple(
            tuple(int(x) for x in b.split("x")) for b in args.buckets))
    return ServeConfig()


def cmd_profile(args) -> int:
    """Device-efficiency plane CLI (docs/device-efficiency.md):

    ``costs``   — the per-program cost/MFU table: analytic FLOPs, byte
    floor, roofline intensity for every serve bucket program + the flat
    train step; ``--measure N`` times real calls so the same invocation
    prints measured MFU on chip (null on CPU — never fabricated).
    ``capture`` — a jax.profiler trace: drive the serve ladder locally
    under the profiler, or pull from a live service started with
    ``--profiler-port`` (when the environment ships the collect client).
    """
    if args.profile_cmd == "costs":
        return _profile_costs(args)
    return _profile_capture(args)


def _profile_costs(args) -> int:
    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import numpy as np

    from nerrf_tpu.devtime import chip_peaks, serve_program_costs
    from nerrf_tpu.serve.service import warmup_batches
    from nerrf_tpu.train.loop import make_eval_fn
    from nerrf_tpu.utils import fetch_value

    cfg = _profile_serve_cfg(args)
    params, model = _profile_model(args, cfg)
    eval_fn = make_eval_fn(model)
    peaks = chip_peaks(jax.devices()[0])
    costs = serve_program_costs(eval_fn, params, cfg,
                                cross_check=args.cross_check)
    rows = {}
    for tag, cost in costs.items():
        rows[cost.program] = {**cost.to_dict(), "measured": None}
    if not args.no_train:
        from nerrf_tpu.devtime import train_step_cost
        from nerrf_tpu.serve.service import _tiny_trace
        from nerrf_tpu.train.data import windows_of_trace
        from nerrf_tpu.train.loop import TrainConfig

        samples = windows_of_trace(
            _tiny_trace("profile-costs"),
            cfg.dataset_config(sorted(cfg.buckets)[0]))
        if samples:
            arrays = {k: np.stack([s[k] for s in samples])
                      for k in samples[0]}
            tc = train_step_cost(model, TrainConfig(model=model.cfg),
                                 arrays, cross_check=args.cross_check)
            if tc is not None:
                rows[tc.program] = {**tc.to_dict(), "measured": None}
    if args.measure > 0:
        # real timed calls per bucket (compile excluded): the measured
        # MFU column — the first chip-side run of this command IS the
        # first non-null serve MFU number
        for _bucket, tag, batch in warmup_batches(cfg):
            program = f"serve_eval[{tag}]"
            if program not in rows:
                continue
            # nerrflint: ok[sync-in-hot-loop] per-bucket compile barrier before the timed measurement loop
            fetch_value(eval_fn(params, batch)["node_logit"])  # compile
            t0 = time.perf_counter()
            for _ in range(args.measure):
                # nerrflint: ok[sync-in-hot-loop] the sync IS the measurement (device seconds per call)
                fetch_value(eval_fn(params, batch)["node_logit"])
            per_call = (time.perf_counter() - t0) / args.measure
            flops = rows[program]["flops"]
            achieved = flops / per_call if per_call > 0 else None
            rows[program]["measured"] = {
                "seconds_per_call": round(per_call, 5),
                "achieved_tflops":
                    round(achieved / 1e12, 3) if achieved else None,
                "mfu": (round(achieved / (peaks.tflops_bf16 * 1e12), 5)
                        if achieved and peaks else None),
            }
    out = {
        "backend": jax.default_backend(),
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "peaks": ({"kind": peaks.kind,
                   "tflops_bf16": peaks.tflops_bf16,
                   "hbm_gbps": peaks.hbm_gbps,
                   "ridge_flops_per_byte":
                       round(peaks.ridge_flops_per_byte, 1)}
                  if peaks else None),
        "flops_authority": "analytic jaxpr counters (bench/flops.py); "
                           "cost_analysis recorded as cross-check only",
        "programs": rows,
    }
    if args.json:
        print(json.dumps(out, indent=2))
        return 0
    peak_s = (f"{peaks.tflops_bf16:g} TFLOP/s bf16, {peaks.hbm_gbps:g} GB/s"
              if peaks else "unknown (no chip-relative numbers)")
    print(f"device: {out['device_kind'] or out['backend']}  peak: {peak_s}")
    print(f"{'program':<28} {'Gflops/call':>12} {'MB floor':>9} "
          f"{'flops/B':>8} {'s/call':>8} {'MFU':>7}")
    for name, r in sorted(rows.items()):
        meas = r.get("measured") or {}
        mfu = meas.get("mfu")
        print(f"{name:<28} {r['flops'] / 1e9:>12.2f} "
              f"{r['bytes_accessed'] / 1e6:>9.1f} "
              f"{(r['intensity_flops_per_byte'] or 0):>8.1f} "
              f"{meas.get('seconds_per_call', '-'):>8} "
              f"{f'{mfu:.2%}' if mfu is not None else 'null':>7}")
    return 0


def _profile_capture(args) -> int:
    from nerrf_tpu.devtime import profiled, trace_summary

    if args.target:
        # remote capture from a service started with --profiler-port.
        # jax ships the collection client as jax.collect_profile, but it
        # needs the tensorboard profiler plugin — gate, never half-work
        try:
            import jax.collect_profile as _cp
        except Exception as e:  # noqa: BLE001 — gated optional dep
            _log(f"remote capture unavailable in this environment "
                 f"({type(e).__name__}: {e}); run `nerrf profile capture` "
                 f"without --target for a local driven capture, or use "
                 f"TensorBoard's profile plugin against the service's "
                 f"--profiler-port")
            return 2
        host, _, port = args.target.rpartition(":")
        try:
            # tracer levels mirror jax.collect_profile's own CLI defaults
            _cp.collect_profile(port=int(port),
                                duration_in_ms=int(args.seconds * 1e3),
                                host=host or "127.0.0.1", log_dir=args.out,
                                host_tracer_level=2, device_tracer_level=1,
                                python_tracer_level=1,
                                no_perfetto_link=True)
        except Exception as e:  # noqa: BLE001 — one-line failure, no trace
            _log(f"remote capture from {args.target} failed: "
                 f"{type(e).__name__}: {e}")
            return 1
        summary = trace_summary(args.out)
        print(json.dumps({"trace_dir": args.out, **(summary or {})}))
        return 0 if summary else 1
    # local driven capture: score the serve ladder's donor batches under
    # the profiler for --seconds, so the trace holds real device work
    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    from nerrf_tpu.serve.service import warmup_batches
    from nerrf_tpu.train.loop import make_eval_fn
    from nerrf_tpu.utils import fetch_value

    cfg = _profile_serve_cfg(args)
    params, model = _profile_model(args, cfg)
    eval_fn = make_eval_fn(model)
    donors = [(tag, batch) for _b, tag, batch in warmup_batches(cfg)]
    if not donors:
        _log("no warmup donor batches for the configured ladder")
        return 2
    for _tag, batch in donors:  # compile OUTSIDE the capture window
        # nerrflint: ok[sync-in-hot-loop] per-bucket compile barrier so the capture shows steady-state scoring, not compiles
        fetch_value(eval_fn(params, batch)["node_logit"])
    deadline = time.monotonic() + args.seconds
    with profiled(args.out) as active:
        if active is None:
            _log("profiler could not start (see profile_failed journal "
                 "record) — nothing captured")
            return 1
        while time.monotonic() < deadline:
            for _tag, batch in donors:
                # nerrflint: ok[sync-in-hot-loop] paced capture driver:
                fetch_value(eval_fn(params, batch)["node_logit"])
    summary = trace_summary(args.out)
    print(json.dumps({"trace_dir": args.out, **(summary or {})}))
    if summary:
        _log(f"trace captured: {summary['files']} file(s) in {args.out} — "
             f"load in Perfetto/TensorBoard")
    return 0 if summary else 1


# --------------------------------------------------------------------------
def cmd_trace(args) -> int:
    """Offline inspector for ``--trace-out`` artifacts: per-stage latency
    table (count, total/mean/p50/max ms, % of wall) from a Chrome-trace
    JSON file.  The same file loads in Perfetto / chrome://tracing for the
    timeline view; this is the terminal-sized summary."""
    from nerrf_tpu import tracing

    try:
        events = tracing.load_chrome_trace(args.file)
    except (OSError, ValueError) as e:
        # ValueError covers both JSONDecodeError and UnicodeDecodeError
        # (binary Perfetto traces are not the JSON flavor this reads)
        _log(f"cannot read trace {args.file}: {e}")
        return 2
    if not events:
        _log(f"no complete ('X') span events in {args.file}")
        return 1
    print(tracing.format_stage_table(events))
    return 0


# --------------------------------------------------------------------------
def cmd_lint(args) -> int:
    """Static analysis over the package's own ASTs (nerrflint): jax-purity,
    recompile-hazard, sync-in-hot-loop, lock-discipline, the concurrency
    tier (atomicity-violation, callback-under-lock, blocking-under-lock,
    thread-lifecycle), metrics-contract.
    Same engine as scripts/nerrflint.py and the tier-1 gate
    (tests/test_analysis.py); rule catalog in docs/static-analysis.md.
    Deliberately NO jax import — safe on any host and never takes the
    chip.  ``--deep`` adds the jaxpr-level program-contract tier
    (signature closure, donation, collectives, cache-key coverage): it
    imports jax but forces a virtual CPU backend, so it
    needs no accelerator either."""
    from nerrf_tpu.analysis.engine import main as lint_main

    argv = []
    if args.json:
        argv.append("--json")
    if args.list_rules:
        argv.append("--list-rules")
    if args.deep:
        argv.append("--deep")
    for rid in args.rule or ():
        argv += ["--rule", rid]
    if args.baseline:
        argv += ["--baseline", args.baseline]
    return lint_main(argv)


# --------------------------------------------------------------------------
def cmd_chaos(args) -> int:
    """Chaos plane (docs/chaos.md): the fault-point catalog, plan
    validation, and an example schedule — the game-day front door.  A plan
    is armed on a pod via ``NERRF_CHAOS_PLAN=<plan.json>`` (serve-detect
    reads it at boot) or ``serve-detect --chaos-plan``; this subcommand
    never arms anything itself.  No jax import — safe anywhere."""
    from nerrf_tpu import chaos

    if args.chaos_cmd == "sites":
        rows = sorted(chaos.SITES.items())
        if args.json:
            print(json.dumps(dict(rows), indent=2))
        else:
            for site, desc in rows:
                print(f"{site:<32} {desc}")
        return 0
    if args.chaos_cmd == "example":
        plan = chaos.FaultPlan(seed=7, faults=(
            chaos.FaultSpec(site="serve.poison_window", prob=0.05,
                            match={"stream": "s1"}),
            chaos.FaultSpec(site="ingest.wire_error", every=40),
            chaos.FaultSpec(site="serve.device_latency", every=9,
                            mode="stall", delay_sec=0.2,
                            after_sec=5.0, for_sec=20.0),
            chaos.FaultSpec(site="compilecache.corrupt_payload",
                            mode="corrupt", at=1),
        ))
        print(json.dumps(plan.to_dict(), indent=2))
        return 0
    # validate
    try:
        plan = chaos.load_plan(args.plan)
        chaos.validate_plan(plan)
    except (OSError, ValueError, TypeError) as e:
        _log(f"chaos plan {args.plan}: INVALID — {e}")
        return 1
    sites = sorted({s.site for s in plan.faults})
    print(json.dumps({"plan": args.plan, "valid": True, "seed": plan.seed,
                      "faults": len(plan.faults), "sites": sites},
                     indent=2))
    return 0


# --------------------------------------------------------------------------
def cmd_status(args) -> int:
    inc = Path(args.incident)
    stages = {
        "incident": inc / "incident.json",
        "plan": inc / "plan.json",
        "gate": inc / "gate.json",
        "report": inc / "report.json",
    }
    out = {}
    for name, p in stages.items():
        out[name] = json.loads(p.read_text()) if p.exists() else None
    state = (
        "recovered" if out["report"] and out["report"].get("verified")
        else "planned" if out["plan"]
        else "attacked" if out["incident"]
        else "empty"
    )
    print(json.dumps({"state": state, **out}, indent=2))
    return 0


def _load_any_trace(path: str, ground_truth=None):
    from nerrf_tpu.data.datasets import load_trace_csv, load_trace_parquet
    from nerrf_tpu.data.loaders import load_trace_jsonl

    p = Path(path)
    if p.suffix == ".csv":
        return load_trace_csv(p, ground_truth=ground_truth)
    if p.suffix == ".parquet":
        return load_trace_parquet(p, ground_truth=ground_truth)
    return load_trace_jsonl(p, ground_truth=ground_truth)


def cmd_serve(args) -> int:
    """Serve a trace over the Tracker wire protocol (+ /metrics endpoint):
    the replay flavor of the reference's tracker daemon, deployable as the
    tracker container in the K8s manifests."""
    import signal

    from nerrf_tpu.ingest.service import TraceReplayServer
    from nerrf_tpu.observability import MetricsServer

    if args.duration <= 0:
        # Block BEFORE spawning any thread: child threads inherit the mask,
        # so process-directed SIGTERM/SIGINT can only wake sigwait below.
        # Without this the kernel may deliver to a gRPC/metrics thread where
        # SIGTERM's default disposition hard-kills the process, skipping
        # cleanup.
        signal.pthread_sigmask(
            signal.SIG_BLOCK, {signal.SIGINT, signal.SIGTERM})

    trace = _load_any_trace(args.trace)
    host, _, port = args.address.rpartition(":")
    server = TraceReplayServer(trace.events, trace.strings,
                               address=f"{host or '0.0.0.0'}:{port}",
                               batch_size=args.batch_size)
    bound = server.start()
    metrics = MetricsServer(host="0.0.0.0", port=args.metrics_port) \
        if args.metrics_port >= 0 else None
    _log(f"serving {trace.events.num_valid} events on :{bound}"
         + (f", metrics on :{metrics.port}" if metrics else ""))
    try:
        if args.duration > 0:
            time.sleep(args.duration)
        else:
            signal.sigwait({signal.SIGINT, signal.SIGTERM})
    finally:
        server.stop()
        if metrics:
            metrics.close()
    return 0


def cmd_serve_detect(args) -> int:
    """The online AI pod: admit N concurrent Tracker streams, window each,
    and score cross-stream micro-batches through one warmed device program
    per capacity bucket (nerrf_tpu/serve, docs/serving.md).  Streams come
    from --target endpoints (live trackers) and/or --trace files (each
    served through an in-process TraceReplayServer, so the full wire
    protocol is exercised either way).  Readiness (/readyz on the metrics
    port) flips only after every configured bucket is compiled."""
    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    import dataclasses as _dc

    from nerrf_tpu.models import JointConfig, NerrfNet
    from nerrf_tpu.observability import MetricsServer
    from nerrf_tpu.serve import (
        OnlineDetectionService,
        ServeConfig,
        init_untrained_params,
    )

    cfg_kwargs = dict(
        batch_size=args.batch_size,
        batch_close_sec=args.close_ms / 1000.0,
        window_deadline_sec=args.deadline_sec,
        stream_queue_slots=args.queue_slots,
    )
    if args.buckets:
        cfg_kwargs["buckets"] = tuple(
            tuple(int(x) for x in b.split("x")) for b in args.buckets)
    cfg = ServeConfig(**cfg_kwargs)

    tuned_art = None
    if getattr(args, "tuned", None):
        # tuned-ladder boot (docs/tuning.md): the artifact's rung set
        # replaces the ladder (including any --buckets) and its routing
        # table rides into the model config below — warmup then compiles
        # exactly the tuned programs, admission admits exactly their
        # reachable shapes, so the zero-recompile contract is unchanged
        from nerrf_tpu.tune import TuneError, apply_to_serve_config, load_artifact

        try:
            tuned_art = load_artifact(args.tuned)
        except TuneError as e:
            _log(str(e))
            return 2
        cfg = apply_to_serve_config(tuned_art, cfg)
        _log(f"tuned ladder from {args.tuned}: {len(cfg.buckets)} rung(s), "
             f"routing {tuned_art.get('routing')}")

    # chaos plane (docs/chaos.md): arm a fault plan for a game day —
    # --chaos-plan wins, else $NERRF_CHAOS_PLAN (one env var on the pod).
    # Neither set → every fault point stays a free no-op.  A bad plan is
    # a one-line refusal to boot (the operator asked for faults the pod
    # cannot inject — serving WITHOUT them would fake the game day)
    from nerrf_tpu import chaos

    try:
        if args.chaos_plan:
            ctl = chaos.arm(chaos.load_plan(args.chaos_plan))
            _log(f"chaos: armed {len(ctl.plan.faults)} fault spec(s) "
                 f"from {args.chaos_plan} (seed {ctl.plan.seed})")
        else:
            chaos.arm_from_env(log=_log)
    except (OSError, ValueError, TypeError) as e:
        _log(f"chaos plan INVALID — {e} "
             f"(check it with `nerrf chaos validate`)")
        return 2

    compile_cache = None
    if not args.no_aot_cache:
        # persistent compile cache: warm-boot the bucket ladder from
        # serialized executables (this host's cache volume and/or the
        # booted version's executables/ sidecar).  Fail-open by contract —
        # a cold, corrupt, or read-only cache costs a live compile, never
        # readiness (docs/compile-cache.md).
        from nerrf_tpu.compilecache import CompileCache

        compile_cache = CompileCache(root=args.aot_cache, log=_log)
        _log(f"compile cache at {compile_cache.root}")

    manager = None
    executables_dir = None
    quality_profile = None
    if args.registry:
        # registry mode: boot from the lineage's LIVE version and keep a
        # ModelManager polling — retrained checkpoints published into the
        # lineage shadow-score and hot-swap in WITHOUT a pod restart or a
        # recompile (docs/model-lifecycle.md)
        from nerrf_tpu.registry import (
            ModelManager,
            ModelRegistry,
            RegistryConfig,
        )

        manager = ModelManager(
            ModelRegistry(args.registry), args.lineage,
            cfg=RegistryConfig(poll_sec=args.poll_sec), log=_log)
        params, model_cfg, calib, version = manager.boot()
        model = NerrfNet(model_cfg)
        if calib.get("node_threshold") is not None:
            cfg = _dc.replace(cfg, threshold=calib["node_threshold"])
        # the booted version's AOT sidecar (if it was published with one)
        # seeds the compile cache: first boot on a fresh pod deserializes
        # the shipped executables instead of compiling the ladder
        executables_dir = manager.store.executables_dir(args.lineage,
                                                        version)
        _log(f"registry boot: {args.lineage}/v{version} LIVE "
             f"from {args.registry}"
             + (" (AOT executables sidecar found)" if executables_dir
                else ""))
    elif args.model_dir:
        from nerrf_tpu.quality import load_profile
        from nerrf_tpu.train.checkpoint import load_calibration, load_checkpoint

        params, model_cfg = load_checkpoint(args.model_dir)
        model = NerrfNet(model_cfg)
        calib = load_calibration(args.model_dir)
        if calib.get("node_threshold") is not None:
            cfg = _dc.replace(cfg, threshold=calib["node_threshold"])
        try:
            # the quality plane's own loader VALIDATES (schema ceiling,
            # field shapes), so a malformed or newer-schema sidecar is a
            # one-line downgrade to no-baseline here — drift monitoring
            # is advisory and must never block serving
            quality_profile = load_profile(args.model_dir)
        except ValueError as e:
            _log(f"quality profile unreadable ({e}); serving without a "
                 f"drift baseline")
            quality_profile = None
    else:
        _log("no --model-dir: serving an UNTRAINED small detector "
             "(load testing only — scores carry no meaning)")
        model = NerrfNet(JointConfig().small)
        params = init_untrained_params(model, cfg)

    if tuned_art is not None:
        from nerrf_tpu.tune import apply_to_model_config

        model = NerrfNet(apply_to_model_config(tuned_art, model.cfg))

    service = OnlineDetectionService(params, model, cfg=cfg,
                                     compile_cache=compile_cache,
                                     executables_dir=executables_dir)
    if quality_profile is not None:
        # checkpoint-dir boot: bind the shipped drift baseline (registry
        # boots get theirs through manager.attach below, version-stamped)
        service.set_quality_profile(quality_profile)
    archive = None
    if args.archive_dir:
        # telemetry archive plane (docs/archive.md): every journal
        # record, cadenced metrics snapshots and the workload sketches
        # spool continuously to crash-safe segments — `nerrf report`
        # reconstructs SLO/capacity/drift/efficiency offline, and `nerrf
        # archive export --tune` emits the cost-model corpus.  Wired
        # BEFORE the recorder so bundles carry the archive position.
        from nerrf_tpu.archive import ArchiveConfig, ArchiveWriter

        archive = ArchiveWriter(ArchiveConfig(out_dir=args.archive_dir),
                                log=_log)
        service.attach_archive(archive)
        _log(f"telemetry archive spooling to {args.archive_dir}")
    responder = None
    respond_ctx = None
    if args.respond:
        # online incident-response tier (docs/response.md): every alert at
        # or above the calibrated-severity gate becomes an incident, a
        # vmapped DeviceMCTS plans micro-batches of them, and each plan
        # replays through the rollback sandbox gate before surfacing.
        # Warmed through the same compile cache as the serve ladder.
        from nerrf_tpu.respond import RespondConfig, ResponseRouter

        responder = ResponseRouter(
            RespondConfig(severity_min=args.respond_severity),
            cache=compile_cache)
        if args.respond_store and args.respond_root:
            # a snapshot handle for the served streams: with it, plans
            # are verifiable; without it every plan is quarantined
            # (fail closed), which is still the correct default
            from nerrf_tpu.respond import VerifyContext
            from nerrf_tpu.rollback.store import SnapshotStore

            snap_store = SnapshotStore(args.respond_store)
            snap_id = args.respond_snapshot or \
                (snap_store.list_manifests() or [None])[-1]
            if snap_id is None:
                _log(f"respond: no manifests in {args.respond_store} — "
                     f"plans will be quarantined unverified")
            else:
                respond_ctx = VerifyContext(
                    store=snap_store,
                    manifest=snap_store.load_manifest(snap_id),
                    victim_root=Path(args.respond_root))
                _log(f"respond: verifying against snapshot {snap_id} "
                     f"over {args.respond_root}")
        service.attach_respond(responder)
        responder.start()
        _log(f"respond tier armed: severity>={args.respond_severity:g}, "
             f"{len(responder.cfg.batch_slots)} batch programs warmed in "
             f"{responder.warmup_seconds:.1f}s")
    recorder = None
    uninstall_crash = None
    if args.flight_dir:
        # incident flight recorder (docs/flight-recorder.md): trailing-p99
        # breach / drop burst / shadow-disagreement / guardrail-veto
        # triggers dump self-contained bundles into --flight-dir, and the
        # excepthook+faulthandler hooks turn an uncaught crash into a
        # bundle too — wired BEFORE streams connect so startup failures
        # are already covered
        from nerrf_tpu.flight import (
            FlightConfig,
            FlightRecorder,
            install_crash_handlers,
        )

        recorder = FlightRecorder(
            FlightConfig(out_dir=args.flight_dir,
                         p99_breach_sec=args.deadline_sec,
                         profile_on_p99_sec=args.profile_on_breach_sec),
            info=service.flight_info, slo=service.slo,
            quality=service.quality_snapshot, archive=archive, log=_log)
        service.attach_flight(recorder)
        uninstall_crash = install_crash_handlers(recorder)
        _log(f"flight recorder armed: bundles in {args.flight_dir}"
             + (f" (+{args.profile_on_breach_sec:g}s profiler trace per "
                f"p99 breach)" if args.profile_on_breach_sec > 0 else ""))
    _profiler_server = None
    if args.profiler_port >= 0:
        # profiler server: `nerrf profile capture --target` / TensorBoard
        # pull traces from the live pod without touching the hot path.
        # The handle must stay referenced for the server's lifetime
        import jax

        _profiler_server = jax.profiler.start_server(args.profiler_port)
        _log(f"jax profiler server on :{args.profiler_port}")
    if manager is not None:
        manager.attach(service)
        manager.start_polling()
    metrics = None
    if args.metrics_port >= 0:
        # readiness is live from the first probe: k8s sees "booting" (503)
        # during the warmup sweep below, then "ready"
        metrics = MetricsServer(host="0.0.0.0", port=args.metrics_port,
                                ready_check=service.ready)
        _log(f"metrics on :{metrics.port} (/healthz, /readyz)")
    _log(f"warming {len(cfg.buckets)} bucket programs…")
    service.start(log=_log)

    replays = []
    targets = [(f"target{i}", t) for i, t in enumerate(args.target or [])]
    try:
        for i, path in enumerate(args.trace or []):
            from nerrf_tpu.ingest.service import TraceReplayServer

            tr = _load_any_trace(path)
            rs = TraceReplayServer(tr.events, tr.strings,
                                   batch_size=args.frame_events)
            port = rs.start()
            replays.append(rs)
            targets.append((f"trace{i}:{Path(path).stem}",
                            f"127.0.0.1:{port}"))
        if not targets:
            _log("nothing to serve: pass --target and/or --trace")
            return 2
        if responder is not None and respond_ctx is not None:
            for name, _addr in targets:
                responder.bind_context(name, respond_ctx)
        runs = [service.connect(name, addr, timeout=args.stream_timeout,
                                follow=args.follow)
                for name, addr in targets]
        _log(f"{len(runs)} streams admitted"
             + (" (follow: reconnect at stream end)" if args.follow else ""))
        deadline = time.monotonic() + args.duration if args.duration > 0 \
            else None
        for run in runs:
            run.done.wait(timeout=None if deadline is None
                          else max(deadline - time.monotonic(), 0.1))

        out_dir = Path(args.out) if args.out else None
        if out_dir:
            out_dir.mkdir(parents=True, exist_ok=True)
        summary = {"streams": {}, "alerts": 0}
        for run in runs:
            det = run.result
            entry = {"done": run.done.is_set(),
                     "error": repr(run.error) if run.error else None}
            if det is not None:
                entry.update(
                    detector=det.detector, threshold=det.threshold,
                    files_scored=len(det.file_scores),
                    files_flagged=len(det.flagged_files()))
                if out_dir:
                    safe = run.stream.replace("/", "_").replace(":", "_")
                    (out_dir / f"detect_{safe}.json").write_text(json.dumps({
                        "stream": run.stream,
                        "detector": det.detector,
                        "threshold": det.threshold,
                        "file_scores": det.file_scores,
                        "proc_scores": det.proc_scores,
                    }, indent=2))
            summary["streams"][run.stream] = entry
        alerts = service.sink.drain()
        summary["alerts"] = len(alerts)
        if out_dir:
            with (out_dir / "alerts.jsonl").open("w") as f:
                for a in alerts:
                    f.write(json.dumps({
                        "stream": a.stream, "window": a.window_idx,
                        "max_prob": round(a.max_prob, 4),
                        "hot": a.hot, "late": a.late,
                        "latency_ms": round(
                            (a.t_scored - a.t_admit) * 1e3, 1),
                    }) + "\n")
        from nerrf_tpu.observability import DEFAULT_REGISTRY

        summary["windows_scored"] = DEFAULT_REGISTRY.value(
            "serve_windows_scored_total")
        if service.live_version is not None:
            summary["model_version"] = f"v{service.live_version}"
        summary["admission_dropped"] = {
            reason: DEFAULT_REGISTRY.value(
                "serve_admission_dropped_total", labels={"reason": reason})
            for reason in ("backpressure", "oversize", "leave", "closed")}
        # per-bucket recompile counter summed over the served ladder: the
        # zero-recompile contract made scriptable (the tune smoke in
        # e2e.sh asserts this is 0 on a tuned boot)
        from nerrf_tpu.serve.config import bucket_tag as _btag

        summary["recompiles_after_warmup"] = sum(
            DEFAULT_REGISTRY.value("serve_recompiles_total",
                                   labels={"bucket": _btag(b)}) or 0
            for b in cfg.buckets)
        if responder is not None:
            responder.drain(timeout=30.0)
            summary["respond"] = responder.stats()
        print(json.dumps(summary, indent=2))
        return 0
    except BaseException as e:
        # a MAIN-thread crash would only reach sys.excepthook AFTER the
        # finally below has already uninstalled it — journal (→ bundle)
        # here, while the recorder is still subscribed.  Ctrl-C is a
        # routine shutdown, not an incident: an `exception` bundle per
        # interactive stop would evict real evidence under max_bundles
        if recorder is not None and not isinstance(
                e, (SystemExit, KeyboardInterrupt)):
            from nerrf_tpu.flight.journal import DEFAULT_JOURNAL
            from nerrf_tpu.flight.recorder import journal_exception

            journal_exception(DEFAULT_JOURNAL, type(e), e,
                              e.__traceback__, "main")
        raise
    finally:
        if manager is not None:
            manager.close()
        if responder is not None:
            responder.stop()
        service.stop()
        for rs in replays:
            rs.stop()
        if metrics:
            metrics.close()
        if recorder is not None:
            recorder.close()
        if archive is not None:
            # after the recorder: a crash bundle dumped during teardown
            # still stamps a live archive position; close() drains the
            # backlog and seals the tail segment
            archive.close()
        if uninstall_crash is not None:
            uninstall_crash()


def cmd_respond(args) -> int:
    """The incident-response corpus end to end, no serve pod needed: stage
    each adversarial family on disk (victim tree snapshotted FIRST), run
    detection on the attack trace, plan every incident through the
    batched vmapped planner, replay every plan through the rollback
    sandbox gate.  One JSON report; exit 1 if any family failed to
    produce a verified plan (docs/response.md)."""
    import tempfile

    from nerrf_tpu.pipeline import heuristic_detect
    from nerrf_tpu.respond import (
        FAMILIES,
        RespondConfig,
        ResponseRouter,
        stage_incident,
    )

    fams = tuple(args.family or FAMILIES)
    unknown = [f for f in fams if f not in FAMILIES]
    if unknown:
        _log(f"unknown family {unknown} (know {list(FAMILIES)})")
        return 2
    cfg = RespondConfig(num_simulations=args.sims,
                        verify=not args.no_verify)
    work = Path(args.work_dir) if args.work_dir else Path(
        tempfile.mkdtemp(prefix="nerrf_respond_"))
    work.mkdir(parents=True, exist_ok=True)
    _log(f"staging {len(fams)} families under {work}")
    router = ResponseRouter(cfg).start()
    try:
        for fam in fams:
            staged = stage_incident(work, fam, seed=args.seed,
                                    files=args.files)
            det = heuristic_detect(staged.trace)
            _log(f"{fam}: {len(det.flagged_files())} files flagged, "
                 f"{len(det.proc_scores)} procs")
            router.submit_detection(fam, det,
                                    context=staged.verify_context())
        drained = router.drain(
            timeout=cfg.timeout_seconds * len(fams) + 120.0)
        report = {
            "families": {vp.incident.stream: vp.to_dict()
                         for vp in router.results()},
            "stats": router.stats(),
            "drained": drained,
        }
    finally:
        router.stop()
    print(json.dumps(report, indent=2))
    complete = drained and len(report["families"]) == len(fams)
    verified = args.no_verify or all(
        v["verified"] for v in report["families"].values())
    clean = report["stats"]["recompiles"] == 0
    return 0 if (complete and verified and clean) else 1


def cmd_ingest(args) -> int:
    """Drain a tracker's StreamEvents into a trace store (the AI-side ingest
    pod: gRPC → native decode → time-bucketed segments).  Blocks are appended
    and flushed incrementally, so a dropped stream or deadline expiry loses
    nothing already received; --follow reconnects forever (daemon mode)."""
    import grpc

    from nerrf_tpu.graph.store import TraceStore
    from nerrf_tpu.ingest.service import TrackerClient
    from nerrf_tpu.observability import DEFAULT_REGISTRY, MetricsServer

    metrics = None
    if args.metrics_port >= 0:
        try:
            metrics = MetricsServer(host="0.0.0.0", port=args.metrics_port)
        except OSError:
            # port taken (another ingest/serve on this host): fall back to an
            # ephemeral port rather than refusing to ingest at all
            metrics = MetricsServer(host="0.0.0.0", port=0)
            _log(f"metrics port {args.metrics_port} in use; using ephemeral")
        _log(f"metrics on :{metrics.port}")
    total = 0
    segments = 0
    try:
        with TraceStore(args.store_dir, bucket_sec=args.bucket_sec) as st:
            # Durability flush on a wall-clock cadence, not per decoded frame:
            # every flush rewrites the active bucket's whole segment (delta
            # compaction), so per-frame flushing is O(rows²) disk traffic.
            # Memory stays bounded between flushes by the store's own
            # AUTO_FLUSH_ROWS.  At most --flush-sec of received-but-unflushed
            # events are lost on a crash (a dropped *stream* still loses
            # nothing: the finally-flush below runs per connection).
            last_flush = time.monotonic()
            while True:
                client = TrackerClient(args.target)
                try:
                    for events, strings in client.iter_blocks(
                            max_events=args.max_events or None,
                            timeout=args.timeout):
                        stored = st.append(events, strings)
                        total += stored
                        DEFAULT_REGISTRY.counter_inc(
                            "ingest_events_stored_total", stored,
                            help="events appended to the trace store")
                        now = time.monotonic()
                        if now - last_flush >= args.flush_sec:
                            segments += st.flush()
                            last_flush = now
                except grpc.RpcError as e:
                    _log(f"stream ended: {e.code().name}")
                finally:
                    segments += st.flush()
                    last_flush = time.monotonic()
                if not args.follow:
                    break
                time.sleep(args.reconnect_sec)
            out = {
                "events": total,
                "segments_written": segments,
                "segments_live": st.num_segments,
                "strings": st.num_strings,
                "engine": "native" if st.is_native else "python",
            }
    finally:
        if metrics:
            metrics.close()
    print(json.dumps(out))
    return 0


def cmd_archive(args) -> int:
    """Telemetry archive maintenance: segment inventory, retention prune,
    integrity verify, cross-host merge, and the tune-corpus export
    (docs/archive.md).  All offline — no backend, no live process."""
    from nerrf_tpu.archive import (
        export_tune,
        list_segments,
        merge_archives,
        verify_archive,
    )
    from nerrf_tpu.flight.journal import SchemaVersionError

    try:
        if args.archive_cmd == "ls":
            names = list_segments(args.dir)
            total = 0
            for name in names:
                p = Path(args.dir) / name
                size = p.stat().st_size if p.exists() else 0
                total += size
                state = "open" if name.endswith(".open") else "sealed"
                print(f"{name:<44} {size:>10}  {state}")
            print(f"{len(names)} segment(s), {total} bytes")
            return 0
        if args.archive_cmd == "prune":
            # out-of-band retention: sealed segments only — the dir may
            # belong to a LIVE writer whose .open tail must stay its own
            from nerrf_tpu.archive import prune_archive

            if not Path(args.dir).is_dir():
                raise FileNotFoundError(args.dir)
            print(json.dumps(prune_archive(args.dir, args.max_bytes)))
            return 0
        if args.archive_cmd == "verify":
            v = verify_archive(args.dir)
            if args.json:
                print(json.dumps(v, indent=2))
            else:
                for s in v["segments"]:
                    flags = []
                    if s["partial_tail"]:
                        flags.append("partial-tail")
                    if s["corrupt_lines"]:
                        flags.append(f"{s['corrupt_lines']} corrupt")
                    if s["error"]:
                        flags.append(s["error"])
                    print(f"{s['segment']:<44} {s['records']:>7} records  "
                          + (" ".join(flags) or "ok"))
                print(f"{'OK' if v['ok'] else 'DAMAGED'}: {v['records']} "
                      f"records / {v['bytes']} bytes in "
                      f"{len(v['segments'])} segment(s)")
            return 0 if v["ok"] else 1
        if args.archive_cmd == "merge":
            out = merge_archives(args.sources, args.out, log=_log)
            print(json.dumps(out))
            return 0
        if args.archive_cmd == "export" and args.replay:
            # learn-plane reader: the replay buffer → deterministic,
            # seedable training batches (docs/learning.md).  jax-free —
            # window lowering is pure numpy
            from nerrf_tpu.learn import (
                build_replay_dataset,
                iter_replay,
                replay_batches,
                replay_stats,
            )
            from nerrf_tpu.serve.config import ServeConfig
            from nerrf_tpu.train.data import DatasetConfig

            stats = replay_stats(args.dir)
            if not stats["windows"]:
                _log(f"refusing to export: replay buffer {args.dir} holds "
                     "no scored windows (serve with the learn plane "
                     "attached first)")
                return 1
            bucket = None
            if args.bucket:
                bucket = tuple(int(x) for x in
                               args.bucket.replace("x", ",").split(","))
            else:
                # shape authority from the buffer itself: replay records
                # carry the bucket serve admission lowered them into
                for rec in iter_replay(args.dir):
                    if rec.get("bucket"):
                        bucket = tuple(rec["bucket"])
                    break
            ds_cfg = (ServeConfig().dataset_config(bucket) if bucket
                      else DatasetConfig())
            ds, info = build_replay_dataset(
                args.dir, ds_cfg, seed=args.seed, limit=args.limit)
            batches = 0
            if ds is not None:
                batches = sum(1 for _ in replay_batches(
                    ds, args.batch_size, seed=args.seed))
            doc = {"replay_dir": str(args.dir), "bucket": list(bucket or ()),
                   "seed": args.seed, "batch_size": args.batch_size,
                   "batches": batches, "stats": stats, "dataset": info}
            if args.out and ds is not None:
                import numpy as np

                np.savez_compressed(args.out, **ds.arrays)
                _log(f"replay dataset written to {args.out} "
                     f"({info['windows']} windows, seed {args.seed})")
            print(json.dumps(doc, indent=2))
            return 0
        if args.archive_cmd == "export":
            corpus = export_tune(args.dir)
            # polite refusal, not a garbage corpus: an archive with no
            # scored windows or no per-bucket cost rows cannot feed a
            # fit — say so in one line and exit nonzero
            if not corpus["windows_observed"]:
                _log(f"refusing to export: archive {args.dir} holds no "
                     "observed windows (run a serve with --archive-dir "
                     "first)")
                return 1
            if not corpus.get("bucket_cost"):
                _log(f"refusing to export: archive {args.dir} has no "
                     "per-bucket cost table (device-stage telemetry "
                     "missing) — the tune fit would have nothing to "
                     "measure")
                return 1
            text = json.dumps(corpus, indent=2)
            if args.out:
                Path(args.out).write_text(text + "\n")
                _log(f"tune corpus written to {args.out} "
                     f"({corpus['windows_observed']} windows observed)")
            else:
                print(text)
            return 0
    except SchemaVersionError as e:
        _log(f"cannot read archive: {e}")
        return 2
    except FileNotFoundError as e:
        _log(f"not an archive directory: {e}")
        return 2
    return 2


def cmd_alerts(args) -> int:
    """Operator feedback on served alerts (docs/learning.md): label a
    window's alert tp/fp by its trace_id.  The disposition lands twice —
    an ``alert_disposition`` journal record (flight/archive evidence)
    and the replay buffer's sidecar, where the `export --replay` reader
    joins it into training labels by trace_id, last-wins."""
    from nerrf_tpu.flight.journal import DEFAULT_JOURNAL
    from nerrf_tpu.learn import append_disposition

    if args.alerts_cmd == "label":
        rec = append_disposition(args.replay_dir, args.trace_id,
                                 args.label, note=args.note)
        DEFAULT_JOURNAL.record(
            "alert_disposition", trace_id=args.trace_id,
            label=args.label, note=args.note,
            replay_dir=str(args.replay_dir))
        print(json.dumps(rec))
        return 0
    return 2  # pragma: no cover — argparse enforces the choices


def cmd_tune(args) -> int:
    """Fit the learned bucket ladder + per-rung kernel routing from an
    archived cost corpus and emit the versioned tuned-ladder artifact
    (docs/tuning.md).  Deterministic: same corpus → same artifact, so the
    tuned-vs-static comparison inside is reproducible evidence, not a
    wall-clock sample."""
    from nerrf_tpu.tune import (
        TuneError,
        load_kernel_bench_crossover,
        save_artifact,
        tune,
    )

    src = Path(args.corpus)
    try:
        if src.is_dir():
            # convenience: point at an archive dir and we export inline
            from nerrf_tpu.archive import export_tune

            corpus = export_tune(src)
        else:
            try:
                corpus = json.loads(src.read_text())
            except FileNotFoundError:
                _log(f"no such corpus file or archive directory: {src}")
                return 1
            except ValueError as e:
                _log(f"corpus {src} is not JSON ({e})")
                return 1

        model_cfg = None
        analytic = None
        if args.model_dir:
            # the checkpoint's real architecture sizes the cost model's
            # work terms, and its analytic devtime surface anchors
            # thin/missing buckets — both optional, both fail-open
            from nerrf_tpu.models import NerrfNet
            from nerrf_tpu.train.checkpoint import load_checkpoint

            params, model_cfg = load_checkpoint(args.model_dir)
            try:
                from nerrf_tpu.devtime.costmodel import serve_program_costs
                from nerrf_tpu.serve.config import ServeConfig
                from nerrf_tpu.train.loop import make_eval_fn

                costs = serve_program_costs(
                    make_eval_fn(NerrfNet(model_cfg)), params,
                    ServeConfig())
                analytic = {tag: c.flops for tag, c in costs.items()}
            except Exception as e:  # noqa: BLE001 — prior, not gate
                _log(f"analytic cost surface unavailable ({e}); fitting "
                     f"from measurements alone")

        kb = load_kernel_bench_crossover(args.kernel_bench)
        art = tune(corpus, model_cfg=model_cfg, analytic=analytic,
                   kernel_bench=kb, max_rungs=args.max_rungs)
    except TuneError as e:
        _log(f"refusing to tune: {e}")
        return 1

    exp = art["expected"]
    if args.out:
        save_artifact(args.out, art)
        _log(f"tuned ladder written to {args.out}: "
             f"{len(art['buckets'])} rung(s), expected "
             f"{exp['static_device_seconds_per_window']:.3g}s → "
             f"{exp['tuned_device_seconds_per_window']:.3g}s per window "
             f"({exp['improvement']:.1%} improvement)")
    if args.json or not args.out:
        print(json.dumps(art, indent=2))
    return 0


def cmd_report(args) -> int:
    """Offline fleet report over archived telemetry (docs/archive.md):
    SLO conformance, capacity headroom, drift, device efficiency and
    training health from segments alone — or, with --compare, a
    cross-run regression diff that exits 1 when the candidate regressed.
    --gate frames the diff as a queue pre-flight: one-line PASS/FAIL
    verdict, and a missing baseline passes with a note (first run before
    an artifact-of-record is banked)."""
    from nerrf_tpu.archive import CompareConfig, report_main

    cfg = CompareConfig(p99_ratio=args.p99_ratio,
                        cost_ratio=args.cost_ratio,
                        loss_ratio=args.loss_ratio,
                        rate_abs=args.rate_abs,
                        psi_breach=args.psi_breach)
    return report_main(args.dir, since=args.since, until=args.until,
                       compare=args.compare, as_json=args.json,
                       gate=args.gate, compare_cfg=cfg)


def cmd_doctor(args) -> int:
    """Two doctors behind one verb.  With a BUNDLE argument: the incident
    doctor — reconstruct a flight-recorder bundle's timeline + per-stage
    attribution offline, no live process needed (docs/flight-recorder.md).
    A telemetry ARCHIVE directory renders the offline fleet report
    instead (docs/archive.md).  Without an argument: the environment
    doctor (scripts/check_env.py): python deps, the JAX backend row,
    toolchain, native libs, capture, sandbox."""
    if args.bundle:
        from nerrf_tpu.archive import is_archive_dir
        from nerrf_tpu.flight.doctor import doctor_main

        if (not Path(args.bundle, "manifest.json").is_file()
                and is_archive_dir(args.bundle)):
            # an archive dir, not a bundle: same verb, the report reader
            from nerrf_tpu.archive import report_main

            return report_main([args.bundle], as_json=args.json)
        return doctor_main(args.bundle, tail=args.tail, as_json=args.json)
    import runpy
    import sys as _sys

    script = Path(__file__).resolve().parents[1] / "scripts" / "check_env.py"
    argv = ([str(script)] + (["--build"] if args.build else [])
            + (["--json"] if args.json else []))
    old = _sys.argv
    _sys.argv = argv
    try:
        runpy.run_path(str(script), run_name="__main__")
        return 0
    except SystemExit as e:
        # exit codes are not always ints: argparse errors carry strings,
        # bare sys.exit() carries None
        if isinstance(e.code, int):
            return e.code
        return 0 if e.code in (None, 0) else 1
    finally:
        _sys.argv = old


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="nerrf", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("simulate", help="seed victim files, snapshot, run attack")
    p.add_argument("--incident", required=True)
    p.add_argument("--files", type=int, default=45)
    p.add_argument("--seed", type=int, default=0)
    p.set_defaults(fn=cmd_simulate)

    p = sub.add_parser("train-detector", help="train + checkpoint a detector")
    p.add_argument("--model-dir", required=True)
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--traces", type=int, default=12)
    p.add_argument("--hidden", type=int, default=48)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--seed", type=int, default=21)
    p.add_argument("--ckpt-every", type=int, default=0,
                   help="checkpoint the full train state every N steps and "
                        "resume from the latest on restart (0 = off)")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="write a Chrome-trace JSON of the run's host spans "
                        "on exit (the loop runs the same either way)")
    p.add_argument("--publish", default=None, metavar="REGISTRY",
                   help="also publish the calibrated checkpoint into this "
                        "model registry (immutable version; promotion is "
                        "separate — see `nerrf models`)")
    p.add_argument("--lineage", default="default",
                   help="registry lineage to publish into (with --publish)")
    p.add_argument("--aot-cache", default=None, metavar="DIR",
                   help="persistent compile cache root (default: aot/ "
                        "under $JAX_COMPILATION_CACHE_DIR, else under the "
                        "checkout's .compile_cache/) — "
                        "a repeat run on an unchanged config deserializes "
                        "the train-step executable instead of recompiling")
    p.add_argument("--no-aot-cache", action="store_true",
                   help="disable the persistent compile cache")
    p.add_argument("--metrics-port", type=int, default=-1,
                   help="training-health /metrics + /healthz + /readyz "
                        "port (-1 disables; 0 = ephemeral); /readyz fails "
                        "before the first step and on a divergence halt "
                        "(docs/training-health.md)")
    p.add_argument("--flight-dir", default=None, metavar="DIR",
                   help="arm the training flight recorder: divergence/"
                        "starvation/stall bundles land here, readable "
                        "offline with `nerrf doctor <bundle>`")
    p.add_argument("--archive-dir", default=None, metavar="DIR",
                   help="spool the run's telemetry (journal, metrics "
                        "snapshots, step sketches) into a crash-safe "
                        "segmented archive `nerrf report` reads offline "
                        "(docs/archive.md)")
    p.set_defaults(fn=cmd_train_detector)

    p = sub.add_parser("models", help="model lifecycle registry: publish, "
                                      "list, promote, rollback, status")
    msub = p.add_subparsers(dest="models_cmd", required=True)

    def _models_common(mp, lineage_required=True):
        mp.add_argument("--registry", required=True, metavar="DIR",
                        help="registry root (the serve pods' --registry)")
        # `list` alone leaves --lineage optional (None = every lineage)
        mp.add_argument("--lineage", required=lineage_required, default=None,
                        help="model lineage name")
        mp.set_defaults(fn=cmd_models)

    mp = msub.add_parser("publish", help="copy a checkpoint in as the next "
                                         "immutable version (schema/feature "
                                         "gated)")
    _models_common(mp)
    mp.add_argument("--model-dir", required=True,
                    help="checkpoint directory to publish")
    mp.add_argument("--source", default=None,
                    help="provenance note stamped into the version sidecar")
    mp.add_argument("--promote", action="store_true",
                    help="also repoint LIVE at the new version immediately "
                        "(skips shadow scoring — prefer guarded promotion)")
    mp.add_argument("--aot", action="store_true",
                    help="compile + serialize the serve ladder's "
                         "executables into the version as an executables/ "
                         "sidecar — pods booting it skip the warmup "
                         "compile sweep (docs/compile-cache.md)")
    mp = msub.add_parser("list", help="lineages, versions, LIVE pointers")
    _models_common(mp, lineage_required=False)
    mp = msub.add_parser("promote", help="repoint LIVE at a version "
                                         "(atomic; pods hot-swap on their "
                                         "next poll)")
    _models_common(mp)
    mp.add_argument("--version", type=int, required=True)
    mp = msub.add_parser("rollback", help="one-command rollback: repoint "
                                          "LIVE at the previous (or given) "
                                          "version")
    _models_common(mp)
    mp.add_argument("--version", type=int, default=None,
                    help="explicit version to roll back to (default: the "
                         "LIVE pointer's recorded previous)")
    mp = msub.add_parser("status", help="one lineage's versions + LIVE")
    _models_common(mp)

    p = sub.add_parser("undo", help="detect, plan, rehearse and roll back")
    p.add_argument("--incident", required=True)
    p.add_argument("--model-dir", default=None,
                   help="trained detector checkpoint (default: heuristic)")
    p.add_argument("--simulations", type=int, default=800)
    p.add_argument("--planner", choices=("auto", "host", "device"),
                   default="auto",
                   help="host = batched-leaf MCTS; device = whole search "
                        "compiled on the accelerator (no per-batch round "
                        "trips); auto (default) = device when a chip is up "
                        "— plan time dominates MTTR, so the chip is the "
                        "KPI path")
    p.add_argument("--trace", default=None,
                   help="detect on this trace file instead of the "
                        "incident's own trace.jsonl (e2e wire artifact)")
    p.add_argument("--dry-run", action="store_true")
    p.add_argument("--no-gate", action="store_true")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="write a Chrome-trace JSON of the incident's "
                        "detect/plan/gate/execute spans")
    p.set_defaults(fn=cmd_undo)

    p = sub.add_parser("status", help="incident state")
    p.add_argument("--incident", required=True)
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("warmup", help="boot-time compile sweep (detector "
                                      "buckets + device planner) into the "
                                      "persistent cache")
    p.add_argument("--model-dir", default=None,
                   help="detector checkpoint to warm (skipped if absent)")
    p.add_argument("--buckets", nargs="*", default=None,
                   metavar="NxExS",
                   help="capacity buckets, e.g. 1024x2048x128 "
                        "4096x8192x512 (default: the configured ladder)")
    p.set_defaults(fn=cmd_warmup)

    p = sub.add_parser("serve", help="serve a trace over the Tracker protocol")
    p.add_argument("--trace", required=True,
                   help="trace file (.jsonl/.csv/.parquet)")
    p.add_argument("--address", default="0.0.0.0:50051")
    p.add_argument("--metrics-port", type=int, default=9090,
                   help="Prometheus /metrics port (-1 disables)")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--duration", type=float, default=0,
                   help="serve for N seconds then exit (0 = until signal)")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="write a Chrome-trace JSON of the serve session's "
                        "host spans on exit")
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser("serve-detect",
                       help="online detection service: score N tracker "
                            "streams through shared device micro-batches")
    p.add_argument("--model-dir", default=None,
                   help="trained detector checkpoint (default: an untrained "
                        "small model, for load testing only)")
    p.add_argument("--registry", default=None, metavar="DIR",
                   help="model registry root: boot from the lineage's LIVE "
                        "version and hot-swap newly promoted versions "
                        "in-place, no restart, no recompile (overrides "
                        "--model-dir; see docs/model-lifecycle.md)")
    p.add_argument("--lineage", default="default",
                   help="registry lineage to serve (with --registry)")
    p.add_argument("--poll-sec", type=float, default=10.0,
                   help="registry poll cadence for new/promoted versions")
    p.add_argument("--target", action="append", default=None,
                   metavar="HOST:PORT",
                   help="tracker endpoint to admit as one stream "
                        "(repeatable)")
    p.add_argument("--trace", action="append", default=None, metavar="FILE",
                   help="trace file to serve through an in-process replay "
                        "server and admit as one stream (repeatable)")
    p.add_argument("--buckets", nargs="*", default=None, metavar="NxExS",
                   help="capacity-bucket ladder, e.g. 256x512x128 "
                        "1024x2048x128 (default: the warmup ladder)")
    p.add_argument("--tuned", default=None, metavar="FILE",
                   help="tuned-ladder artifact from `nerrf tune`: serve on "
                        "its fitted bucket ladder + per-rung kernel "
                        "routing table (overrides --buckets; "
                        "docs/tuning.md)")
    p.add_argument("--batch-size", type=int, default=8,
                   help="padded device batch slots per launch")
    p.add_argument("--close-ms", type=float, default=50.0,
                   help="batch-close deadline: fire a partial batch after "
                        "the oldest window waited this long")
    p.add_argument("--deadline-sec", type=float, default=2.0,
                   help="per-window admit→alert SLO budget (late windows "
                        "still score, counted)")
    p.add_argument("--queue-slots", type=int, default=64,
                   help="per-stream bounded admission queue (drop-oldest)")
    p.add_argument("--frame-events", type=int, default=256,
                   help="events per wire frame for --trace replay servers")
    p.add_argument("--stream-timeout", type=float, default=300.0,
                   help="gRPC deadline per stream drain")
    p.add_argument("--follow", action="store_true",
                   help="resident mode (the serve pod): finalize and "
                        "reconnect each stream when it ends instead of "
                        "exiting — pair with a long --stream-timeout")
    p.add_argument("--duration", type=float, default=0,
                   help="stop waiting after N seconds (0 = until every "
                        "stream ends; with --follow that is forever)")
    p.add_argument("--metrics-port", type=int, default=9092,
                   help="Prometheus /metrics + /healthz + /readyz port "
                        "(-1 disables); default 9092 so serve (9090) and "
                        "ingest (9091) coexist on one host")
    p.add_argument("--out", default=None, metavar="DIR",
                   help="write per-stream detection JSON + alerts.jsonl")
    p.add_argument("--flight-dir", default=None, metavar="DIR",
                   help="arm the incident flight recorder: anomaly "
                        "triggers (p99 breach, drop burst, shadow "
                        "disagreement, guardrail veto, uncaught crash via "
                        "excepthook+faulthandler) dump self-contained "
                        "diagnostic bundles here, readable offline with "
                        "`nerrf doctor <bundle>`")
    p.add_argument("--archive-dir", default=None, metavar="DIR",
                   help="spool the service's telemetry continuously into "
                        "a crash-safe segmented archive here (journal "
                        "records, cadenced metrics snapshots, workload "
                        "sketches) — `nerrf report` reconstructs SLO/"
                        "capacity/drift/efficiency offline, and `nerrf "
                        "archive export --tune` emits the cost-model "
                        "corpus (docs/archive.md)")
    p.add_argument("--aot-cache", default=None, metavar="DIR",
                   help="persistent compile cache root (default: aot/ "
                        "under $JAX_COMPILATION_CACHE_DIR, else under the "
                        "checkout's .compile_cache/) — "
                        "warm boots deserialize the bucket ladder from it "
                        "instead of compiling (docs/compile-cache.md)")
    p.add_argument("--no-aot-cache", action="store_true",
                   help="disable the persistent compile cache (every boot "
                        "compiles the ladder live)")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="write a Chrome-trace JSON of the serve session's "
                        "host spans on exit")
    p.add_argument("--chaos-plan", default=None, metavar="FILE",
                   help="arm a chaos fault plan for this run (game day: "
                        "seeded fault injection at the named points, every "
                        "firing journaled; docs/chaos.md).  Default: "
                        "$NERRF_CHAOS_PLAN when set, else disarmed")
    p.add_argument("--profiler-port", type=int, default=-1,
                   help="start a jax.profiler server on this port so "
                        "`nerrf profile capture --target` / TensorBoard "
                        "can pull traces from the live service (-1 "
                        "disables)")
    p.add_argument("--profile-on-breach-sec", type=float, default=0.0,
                   help="with --flight-dir: embed this many seconds of "
                        "live jax.profiler trace into every p99-breach "
                        "bundle (jax_trace/, summarized by `nerrf "
                        "doctor`); 0 disables")
    p.add_argument("--respond", action="store_true",
                   help="arm the online incident-response tier: alerts at "
                        "or above --respond-severity become incidents, a "
                        "batched vmapped planner emits undo plans, and "
                        "every plan is sandbox-verified before surfacing "
                        "(docs/response.md)")
    p.add_argument("--respond-severity", type=float, default=0.5,
                   help="calibrated-severity admission floor for the "
                        "respond tier (0..1; the demux-boundary number "
                        "alert consumers also see)")
    p.add_argument("--respond-store", default=None, metavar="DIR",
                   help="snapshot store for plan verification; without it "
                        "every plan is quarantined unverified (fail "
                        "closed)")
    p.add_argument("--respond-snapshot", default=None, metavar="ID",
                   help="manifest id in --respond-store to verify against "
                        "(default: the latest)")
    p.add_argument("--respond-root", default=None, metavar="DIR",
                   help="live tree the verified plans would roll back "
                        "(rehearsals run on a clone, never on this tree)")
    p.set_defaults(fn=cmd_serve_detect)

    p = sub.add_parser("respond",
                       help="incident-response corpus end to end: stage "
                            "adversarial families on disk, detect, plan "
                            "in vmapped batches, sandbox-verify every "
                            "plan (docs/response.md)")
    p.add_argument("--family", action="append", default=None,
                   help="attack family to stage (repeatable; default all: "
                        "mass-rename, exfil-staging, cron-persistence, "
                        "log-tamper)")
    p.add_argument("--seed", type=int, default=0,
                   help="deterministic corpus seed (same seed = same "
                        "victims, same damage, same trace)")
    p.add_argument("--files", type=int, default=6,
                   help="victim files per family")
    p.add_argument("--sims", type=int, default=96,
                   help="MCTS simulation budget per batched search")
    p.add_argument("--no-verify", action="store_true",
                   help="skip sandbox verification (throughput probing "
                        "only — plans surface UNVERIFIED)")
    p.add_argument("--work-dir", default=None, metavar="DIR",
                   help="where victim trees + snapshots are staged "
                        "(default: a fresh temp dir)")
    p.set_defaults(fn=cmd_respond)

    p = sub.add_parser("chaos", help="chaos plane: fault-point catalog, "
                                     "plan validation, example schedule "
                                     "(docs/chaos.md)")
    chsub = p.add_subparsers(dest="chaos_cmd", required=True)
    chp = chsub.add_parser("sites", help="list every armed-able fault "
                                         "point and what it simulates")
    chp.add_argument("--json", action="store_true",
                     help="machine-readable catalog")
    chp.set_defaults(fn=cmd_chaos)
    chp = chsub.add_parser("validate", help="parse + validate a plan "
                                            "file; exit 1 when invalid")
    chp.add_argument("plan", help="fault plan JSON "
                                  "(see `nerrf chaos example`)")
    chp.set_defaults(fn=cmd_chaos)
    chp = chsub.add_parser("example", help="print a commented-by-shape "
                                           "example plan to stdout")
    chp.set_defaults(fn=cmd_chaos)

    p = sub.add_parser("quality", help="detection-quality plane: reference "
                                       "profiles and drift tables "
                                       "(docs/quality.md)")
    qsub = p.add_subparsers(dest="quality_cmd", required=True)
    qp = qsub.add_parser("show", help="render a reference profile "
                                      "(checkpoint dir or profile JSON) "
                                      "or a flight bundle's live "
                                      "divergence table")
    qp.add_argument("path", help="checkpoint dir / quality_profile.json / "
                                 "flight bundle dir")
    qp.add_argument("--json", action="store_true",
                    help="machine-readable output")
    qp.set_defaults(fn=cmd_quality)
    qp = qsub.add_parser("compare", help="PSI two reference profiles: "
                                         "score distribution, top-"
                                         "drifting features, margin/"
                                         "alert-rate deltas")
    qp.add_argument("reference", help="the baseline profile "
                                      "(checkpoint dir or JSON)")
    qp.add_argument("other", help="the profile to judge against it")
    qp.add_argument("--json", action="store_true",
                    help="machine-readable output")
    qp.add_argument("--psi-threshold", type=float, default=None,
                    metavar="X", help="exit 1 when any PSI >= X "
                                      "(CI gating)")
    qp.set_defaults(fn=cmd_quality)

    p = sub.add_parser("cache", help="persistent compile cache: list, "
                                     "prune, verify, pre-warm")
    csub = p.add_subparsers(dest="cache_cmd", required=True)

    def _cache_common(cp):
        cp.add_argument("--cache-dir", default=None, metavar="DIR",
                        help="cache root (default: aot/ under "
                             "$JAX_COMPILATION_CACHE_DIR, else under the "
                             "checkout's .compile_cache/)")
        cp.set_defaults(fn=cmd_cache)

    cp = csub.add_parser("ls", help="entry inventory (program, bytes, "
                                    "last use), LRU-oldest first")
    _cache_common(cp)
    cp = csub.add_parser("prune", help="evict LRU entries past the disk "
                                       "bound")
    _cache_common(cp)
    cp.add_argument("--max-bytes", type=int, default=None,
                    help="disk bound to prune to (default: the cache's "
                         "built-in 2 GiB)")
    cp = csub.add_parser("verify", help="integrity check every entry "
                                        "(missing files, truncation, "
                                        "fingerprint mismatch); exit 1 on "
                                        "problems")
    _cache_common(cp)
    cp = csub.add_parser("warm", help="compile the serve bucket ladder "
                                      "into the cache (provisioning / CI "
                                      "pre-flight; run twice and the "
                                      "second sweep must report "
                                      "source=cache)")
    _cache_common(cp)
    cp.add_argument("--model-dir", default=None,
                    help="checkpoint whose serve programs to warm "
                         "(default: the untrained small model — cache "
                         "keys include the params, so warm the model you "
                         "will serve)")
    cp.add_argument("--buckets", nargs="*", default=None, metavar="NxExS",
                    help="capacity-bucket ladder to warm (default: the "
                         "full serve ladder)")
    cp.add_argument("--expect-cache", action="store_true",
                    help="exit 1 unless EVERY ladder bucket resolved "
                         "source=cache (the CI/queue pre-flight's second "
                         "sweep)")

    p = sub.add_parser("profile", help="device-efficiency plane: per-"
                                       "program cost/MFU table, jax "
                                       "profiler capture "
                                       "(docs/device-efficiency.md)")
    psub = p.add_subparsers(dest="profile_cmd", required=True)
    pp = psub.add_parser("costs", help="per-program cost table: analytic "
                                       "FLOPs / byte floor / roofline "
                                       "intensity for the serve ladder + "
                                       "flat train step; --measure adds "
                                       "timed calls → measured MFU (null "
                                       "off-chip, never fabricated)")
    pp.add_argument("--model-dir", default=None,
                    help="checkpoint whose programs to cost (default: the "
                         "untrained small detector — shapes are what "
                         "matter)")
    pp.add_argument("--buckets", nargs="*", default=None, metavar="NxExS",
                    help="capacity-bucket ladder (default: the serve "
                         "ladder)")
    pp.add_argument("--smoke", action="store_true",
                    help="one tiny bucket (CPU-pinned CI pre-flight)")
    pp.add_argument("--measure", type=int, default=0, metavar="N",
                    help="time N real calls per bucket after compile "
                         "(the measured-MFU column; 0 = analytic only)")
    pp.add_argument("--cross-check", action="store_true",
                    help="also record XLA cost_analysis FLOPs/bytes per "
                         "program (pays one compile each; recorded as "
                         "cross-check, never the MFU numerator)")
    pp.add_argument("--no-train", action="store_true",
                    help="skip the flat train-step row")
    pp.add_argument("--json", action="store_true")
    pp.set_defaults(fn=cmd_profile)
    pp = psub.add_parser("capture", help="capture a jax.profiler trace "
                                         "(Perfetto/TensorBoard readable): "
                                         "drive the serve ladder locally, "
                                         "or pull from a live service's "
                                         "--profiler-port")
    pp.add_argument("--out", required=True, metavar="DIR",
                    help="trace output directory")
    pp.add_argument("--seconds", type=float, default=3.0,
                    help="capture duration")
    pp.add_argument("--target", default=None, metavar="HOST:PORT",
                    help="live service's --profiler-port endpoint (needs "
                         "the jax collect client; gated with a one-line "
                         "error when the environment lacks it)")
    pp.add_argument("--model-dir", default=None,
                    help="checkpoint to drive in local mode")
    pp.add_argument("--buckets", nargs="*", default=None, metavar="NxExS")
    pp.add_argument("--smoke", action="store_true",
                    help="one tiny bucket (fast local capture)")
    pp.set_defaults(fn=cmd_profile)

    p = sub.add_parser("trace", help="per-stage latency table from a "
                                     "--trace-out Chrome-trace file")
    p.add_argument("--file", required=True,
                   help="Chrome-trace JSON produced by --trace-out (or any "
                        "trace-event file)")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser("lint", help="static analysis over nerrf_tpu's own "
                                    "ASTs (purity, recompile, sync, lock "
                                    "discipline, the concurrency tier, "
                                    "metrics contract); --deep adds the "
                                    "jaxpr-level program contracts")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    p.add_argument("--deep", action="store_true",
                   help="also verify the jaxpr-level program contracts "
                        "(signature closure, donation, collectives, "
                        "cache-key coverage) — abstract tracing "
                        "on a virtual CPU backend, no devices needed")
    p.add_argument("--rule", action="append", default=None, metavar="ID",
                   help="run only this rule (repeatable)")
    p.add_argument("--baseline", default=None, metavar="FILE",
                   help="suppression file (default: .nerrflint-baseline)")
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser("archive", help="telemetry archive: segment "
                                       "inventory, retention prune, "
                                       "integrity verify, cross-host "
                                       "merge, tune-corpus export "
                                       "(docs/archive.md)")
    asub = p.add_subparsers(dest="archive_cmd", required=True)
    ar = asub.add_parser("ls", help="segment inventory (name, bytes, "
                                    "sealed/open), oldest first")
    ar.add_argument("dir", help="archive directory (a serve/train run's "
                                "--archive-dir)")
    ar.set_defaults(fn=cmd_archive)
    ar = asub.add_parser("prune", help="enforce a retention bound now: "
                                       "delete oldest sealed segments "
                                       "past --max-bytes")
    ar.add_argument("dir")
    ar.add_argument("--max-bytes", type=int, required=True,
                    help="total archive size to prune down to")
    ar.set_defaults(fn=cmd_archive)
    ar = asub.add_parser("verify", help="integrity check every segment "
                                        "(a torn final line is the "
                                        "tolerated crash shape; mid-"
                                        "segment damage exits 1)")
    ar.add_argument("dir")
    ar.add_argument("--json", action="store_true")
    ar.set_defaults(fn=cmd_archive)
    ar = asub.add_parser("merge", help="merge N archive directories into "
                                       "a fresh one (cross-host "
                                       "aggregation: records interleave "
                                       "by time, sketches stay "
                                       "attributable per run)")
    ar.add_argument("sources", nargs="+", help="archive directories to "
                                               "merge")
    ar.add_argument("--out", required=True, help="merged archive "
                                                 "directory (created)")
    ar.set_defaults(fn=cmd_archive)
    ar = asub.add_parser("export", help="emit the tune-ready corpus: the "
                                        "observed window-size "
                                        "distribution + per-bucket "
                                        "measured cost table the `nerrf "
                                        "tune` cost-model fit consumes")
    ar.add_argument("dir")
    ar.add_argument("--tune", action="store_true",
                    help="the cost-model corpus (the default export; "
                         "the flag names the schema)")
    ar.add_argument("--replay", action="store_true",
                    help="read `dir` as a learn-plane replay buffer "
                         "instead: lower its scored windows (with "
                         "disposition labels joined by trace_id) into "
                         "deterministic, seedable training batches "
                         "(docs/learning.md)")
    ar.add_argument("--seed", type=int, default=0,
                    help="replay shuffle/batch seed (same buffer + same "
                         "seed = bit-identical batches)")
    ar.add_argument("--limit", type=int, default=None,
                    help="cap the replay windows lowered (applied after "
                         "the seeded shuffle)")
    ar.add_argument("--batch-size", type=int, default=8,
                    help="replay batch size (inventory only — the "
                         "trainer slices its own)")
    ar.add_argument("--bucket", default=None, metavar="N,E,S",
                    help="padded shape to lower replay windows into "
                         "(default: the bucket stamped in the buffer's "
                         "first record)")
    ar.add_argument("--out", default=None, metavar="FILE",
                    help="write the corpus JSON (or, with --replay, the "
                         "stacked dataset .npz) here instead of stdout")
    ar.set_defaults(fn=cmd_archive)

    p = sub.add_parser("alerts", help="operator feedback on served "
                                      "alerts: tp/fp dispositions that "
                                      "join the replay buffer's label "
                                      "stream (docs/learning.md)")
    alsub = p.add_subparsers(dest="alerts_cmd", required=True)
    al = alsub.add_parser("label", help="record one disposition by "
                                        "trace_id (journal record + "
                                        "replay-buffer sidecar)")
    al.add_argument("trace_id", help="the alert's trace_id (alert "
                                     "records, `nerrf doctor` timeline)")
    al.add_argument("label", choices=["tp", "fp"],
                    help="true positive (the window really was an "
                         "attack) or false positive")
    al.add_argument("--note", default=None,
                    help="free-text context stored with the disposition")
    al.add_argument("--replay-dir", default="replay-buffer", metavar="DIR",
                    help="the replay buffer whose sidecar receives the "
                         "label (default: ./replay-buffer)")
    al.set_defaults(fn=cmd_alerts)

    p = sub.add_parser("tune", help="fit a learned bucket ladder + "
                                    "per-rung kernel routing from an "
                                    "archived cost corpus; emits the "
                                    "tuned-ladder artifact serve-detect "
                                    "--tuned and the AOT re-export "
                                    "consume (docs/tuning.md)")
    p.add_argument("corpus", help="tune corpus JSON (`nerrf archive "
                                  "export --tune --out`) or an archive "
                                  "directory to export inline")
    p.add_argument("--out", default=None, metavar="FILE",
                   help="write the tuned-ladder artifact here (default: "
                        "print to stdout)")
    p.add_argument("--model-dir", default=None, metavar="DIR",
                   help="checkpoint whose architecture sizes the cost "
                        "model and whose analytic devtime surface anchors "
                        "thin buckets (default: the stock detector "
                        "config, measurements only)")
    p.add_argument("--max-rungs", type=int, default=None,
                   help="rung-count bound for the ladder search "
                        "(default: the static ladder's graph-rung count)")
    p.add_argument("--kernel-bench",
                   default="benchmarks/results/kernel_bench_cpu.json",
                   metavar="FILE",
                   help="kernel microbenchmark artifact whose measured "
                        "dense/fused crossover calibrates the routing "
                        "prior (missing file: the authored constant)")
    p.add_argument("--json", action="store_true",
                   help="print the artifact JSON even with --out")
    p.set_defaults(fn=cmd_tune)

    p = sub.add_parser("report", help="offline fleet report over archived "
                                      "telemetry: SLO/capacity/drift/"
                                      "efficiency/train health from "
                                      "segments alone; --compare diffs "
                                      "two runs (docs/archive.md)")
    p.add_argument("dir", nargs="*", default=[],
                   help="archive director(ies) — multiple dirs merge "
                        "into one report")
    p.add_argument("--compare", nargs=2, default=None,
                   metavar=("BASELINE", "CANDIDATE"),
                   help="diff two archive dirs and exit 1 when the "
                        "candidate regressed (p99, breach/drop rate, "
                        "per-bucket device cost, drift, train loss)")
    p.add_argument("--gate", action="store_true",
                   help="continuous-regression framing for --compare: "
                        "one-line GATE PASS/FAIL verdict, and a missing "
                        "baseline passes with a note (first run before "
                        "an artifact-of-record is banked)")
    from nerrf_tpu.archive.report import CompareConfig as _CmpCfg
    p.add_argument("--p99-ratio", type=float,
                   default=_CmpCfg.p99_ratio, metavar="R",
                   help="flag when candidate e2e p99 > baseline ×R "
                        "(default %(default)s)")
    p.add_argument("--cost-ratio", type=float,
                   default=_CmpCfg.cost_ratio, metavar="R",
                   help="flag when per-bucket device seconds/batch > "
                        "baseline ×R (default %(default)s)")
    p.add_argument("--loss-ratio", type=float,
                   default=_CmpCfg.loss_ratio, metavar="R",
                   help="flag when final train loss > baseline ×R "
                        "(default %(default)s)")
    p.add_argument("--rate-abs", type=float,
                   default=_CmpCfg.rate_abs, metavar="A",
                   help="flag when breach/drop rate > baseline +A "
                        "(default %(default)s)")
    p.add_argument("--psi-breach", type=float,
                   default=_CmpCfg.psi_breach, metavar="P",
                   help="flag when score-drift PSI crosses P in the "
                        "candidate only (default %(default)s)")
    p.add_argument("--since", type=float, default=None, metavar="UNIX",
                   help="only records at/after this unix timestamp")
    p.add_argument("--until", type=float, default=None, metavar="UNIX",
                   help="only records at/before this unix timestamp")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.set_defaults(fn=cmd_report)

    p = sub.add_parser("doctor", help="diagnose the environment, read a "
                                      "flight-recorder incident bundle, "
                                      "or report over a telemetry "
                                      "archive directory")
    p.add_argument("bundle", nargs="?", default=None,
                   help="flight bundle directory (bundle-<utc>-<trigger>): "
                        "print the incident timeline + per-stage "
                        "attribution offline; omit for the environment "
                        "doctor")
    p.add_argument("--tail", type=int, default=None,
                   help="only the last N journal records of the timeline")
    p.add_argument("--build", action="store_true",
                   help="also build missing native libraries (env mode)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable summary")
    p.set_defaults(fn=cmd_doctor)

    p = sub.add_parser("ingest", help="drain a tracker into a trace store")
    p.add_argument("--target", required=True, help="tracker host:port")
    p.add_argument("--store-dir", required=True)
    p.add_argument("--bucket-sec", type=float, default=30.0)
    p.add_argument("--max-events", type=int, default=0)
    p.add_argument("--timeout", type=float, default=30.0)
    p.add_argument("--follow", action="store_true",
                   help="reconnect and keep draining forever (daemon mode)")
    p.add_argument("--reconnect-sec", type=float, default=2.0)
    p.add_argument("--flush-sec", type=float, default=5.0,
                   help="durability flush cadence (seconds)")
    p.add_argument("--metrics-port", type=int, default=9091,
                   help="Prometheus /metrics port (-1 disables). Default "
                        "9091 so serve (9090) + ingest coexist on one host; "
                        "the K8s ingest pod passes 9090 explicitly")
    p.set_defaults(fn=cmd_ingest)

    args = ap.parse_args(argv)
    trace_out = getattr(args, "trace_out", None)
    if trace_out:
        # clear first so the file holds THIS command's spans (embedded
        # callers may run several commands in one process); the spans
        # record either way, --trace-out only writes the ring at exit
        from nerrf_tpu import tracing

        tracing.DEFAULT_TRACER.clear()
    try:
        return args.fn(args)
    finally:
        if trace_out:
            try:
                path = tracing.DEFAULT_TRACER.write(trace_out)
            except OSError as e:
                # must not mask the command's own outcome/exception with a
                # write failure at the very end of a long run
                _log(f"could not write trace to {trace_out}: {e}")
            else:
                _log(f"{len(tracing.DEFAULT_TRACER.records())} spans "
                     f"written to {path} — inspect with `nerrf trace "
                     f"--file {path}` or load in Perfetto/chrome://tracing")


if __name__ == "__main__":
    raise SystemExit(main())
