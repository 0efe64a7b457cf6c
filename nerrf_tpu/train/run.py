"""Experiment runner: one command from a named config to trained artifacts.

The reference's planned entry point was ``ai/train.py`` (`/root/reference/
README.md:72-76`, never written).  This is ours, driven entirely by the
experiment registry (BASELINE.json's configs — see nerrf_tpu/config.py):

    python -m nerrf_tpu.train.run --experiment toy-graphsage --out /tmp/run
    python -m nerrf_tpu.train.run --experiment joint-100h    --out ...
    python -m nerrf_tpu.train.run --experiment multihost-online --out ...
        # dp×tp sharded training over all visible devices

Produces under --out: the experiment config as run, a model checkpoint
(self-describing, loadable by `nerrf undo --model-dir`), and metrics.json
with the quality gates evaluated on the held-out split.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def _log(msg: str) -> None:
    print(f"[run] {msg}", file=sys.stderr, flush=True)


def run_experiment(name_or_path: str, out_dir: str | Path,
                   num_steps: int | None = None,
                   ckpt_every: int = 0, sharded: bool | None = None,
                   calibrate: bool = True,
                   publish_to: str | None = None,
                   lineage: str = "default",
                   compile_cache=None,
                   metrics_port: int = -1,
                   flight_dir: str | None = None,
                   archive_dir: str | None = None) -> dict:
    """``metrics_port`` ≥ 0 / ``flight_dir`` arm the training-health plane
    (docs/training-health.md): a /metrics+/readyz endpoint with the
    train-aware ready check (503 before the first step and on a
    divergence halt) and train-side flight triggers dumping
    doctor-readable bundles.  ``archive_dir`` spools the run's journal +
    metrics snapshots + step-cadence sketches to a crash-safe telemetry
    archive `nerrf report` reads offline (docs/archive.md).  All off
    (the defaults) costs the loop nothing."""
    from nerrf_tpu.trainwatch import training_health

    with training_health(metrics_port=metrics_port, flight_dir=flight_dir,
                         archive_dir=archive_dir, log=_log) as monitor:
        return _run_experiment(name_or_path, out_dir, num_steps, ckpt_every,
                               sharded, calibrate, publish_to, lineage,
                               compile_cache, monitor)


def _kernel_path(model_cfg, num_nodes: int) -> dict:
    """The routes the step's ops take in a program traced in this process,
    one-device or sharded alike: functions of the backend and this node
    bucket.  Stamped into the log and metrics.json so a steps/s figure
    names the ops that produced it."""
    from nerrf_tpu.ops.segment import active_impls, gather_rows_route

    return {**active_impls(),
            "gather_rows": gather_rows_route(num_nodes),
            "gnn_aggregation": model_cfg.gnn.resolved_aggregation(num_nodes),
            "lstm_impl": model_cfg.lstm.resolved_impl()}


def _halted_report(exp, cfg, out: "Path", monitor, steps_per_sec) -> dict:
    """The divergence-halt exit: a run the monitor stopped has NaN
    weights — saving, calibrating, or publishing them would hand a
    poisoned checkpoint to the registry.  Write a metrics.json that says
    exactly why there is no model, with a failing gate so the caller
    exits non-zero.  The restart pointer lives in the flight bundle."""
    step, reason = monitor.diverged
    report = {
        "experiment": exp.name,
        "num_steps": cfg.num_steps,
        "steps_per_sec": round(steps_per_sec, 3),
        "metrics": {},
        "diverged": {"step": step, "reason": reason},
        "gates": {"not_diverged": False},
    }
    (out / "metrics.json").write_text(json.dumps(report, indent=2) + "\n")
    _log(f"training diverged at step {step} ({reason}); NOT saving a "
         f"checkpoint — restart from the last good checkpoint (see the "
         f"flight bundle)")
    return report


def _run_experiment(name_or_path, out_dir, num_steps, ckpt_every, sharded,
                    calibrate, publish_to, lineage, compile_cache,
                    monitor) -> dict:
    import dataclasses

    import jax

    from nerrf_tpu.config import get_experiment
    from nerrf_tpu.train import build_dataset
    from nerrf_tpu.train.checkpoint import save_checkpoint

    exp = get_experiment(name_or_path)
    cfg = exp.train
    if num_steps is not None:
        cfg = dataclasses.replace(cfg, num_steps=num_steps)
    if monitor is not None and not cfg.telemetry:
        # the health plane is armed: turn the in-step telemetry on with
        # it (divergence detection without grad/update norms is
        # loss-only).  A distinct compile-cache fingerprint by design —
        # telemetry changes the step's lowered program and output treedef
        cfg = dataclasses.replace(cfg, telemetry=True)
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    exp.save(out / "experiment.json")

    t0 = time.time()
    corpus_extra = {}
    n_dev = len(jax.devices())

    # --- stream pretraining: event tokens in packed documents ---------------
    if exp.stream is not None and exp.stream.vocab_size:
        return _run_stream_experiment(exp, cfg, out, n_dev, compile_cache, t0)

    # --- disk-sharded corpus path (the true 100 h run) ----------------------
    if exp.corpus_dir:
        cdir = Path(exp.corpus_dir)
        if not cdir.is_absolute():
            cdir = Path(__file__).resolve().parents[2] / cdir
        if (cdir / "manifest.json").exists():
            from nerrf_tpu.train.corpus import ShardedCorpus
            from nerrf_tpu.train.loop import train_sharded_stream

            sc = ShardedCorpus(cdir)
            _log(f"experiment {exp.name}: disk corpus {sc.hours:.1f}h, "
                 f"{sc.train_windows} train windows "
                 f"({len(sc.train_shards)} shards)")
            # shard shapes are authoritative (the manifest's auto-fit); a
            # config drifting from them misleads every downstream consumer
            # (bench shapes, capacity bench) — fail loud, not silent
            cap = sc.manifest.get("graph_capacity")
            g = exp.dataset.graph
            if cap and (cap["max_nodes"] != g.max_nodes
                        or cap["max_edges"] != g.max_edges):
                _log(f"WARNING: corpus capacities {cap} != experiment config "
                     f"({g.max_nodes}n/{g.max_edges}e) — training uses the "
                     f"corpus shapes; update the config/regenerate to align")
            eval_ds = sc.eval_dataset()
            _log(f"eval split: {len(eval_ds)} held-out-trace windows")
            res = train_sharded_stream(
                sc, cfg, eval_ds=eval_ds, log=_log,
                ckpt_dir=(out / "train_state") if ckpt_every > 0 else None,
                save_every=ckpt_every, compile_cache=compile_cache,
                monitor=monitor)
            metrics, steps_per_sec, params = (
                res.metrics, res.steps_per_sec, res.state.params)
            if monitor is not None and monitor.diverged is not None:
                return _halted_report(exp, cfg, out, monitor, steps_per_sec)
            corpus_extra = {
                "corpus_hours": round(sc.hours, 2),
                "corpus_train_windows": sc.train_windows,
                "corpus_eval_windows": int(sc.manifest["eval_windows"]),
                "kernel_path": _kernel_path(
                    cfg.model, (cap or {}).get("max_nodes", g.max_nodes)),
            }
            return _finish(exp, cfg, out, n_dev, metrics, steps_per_sec,
                           params, t0, res.history, corpus_extra,
                           calibrate=calibrate, publish_to=publish_to,
                           lineage=lineage)
        _log(f"corpus_dir {cdir} not generated "
             f"(python scripts/gen_corpus.py --out {cdir}) — falling back "
             f"to the in-memory corpus "
             f"({exp.corpus.num_traces}×{exp.corpus.duration_sec:.0f}s = "
             f"{exp.corpus.num_traces * exp.corpus.duration_sec / 3600:.1f}h)")

    _log(f"experiment {exp.name}: building corpus "
         f"({exp.corpus.num_traces} traces × {exp.corpus.duration_sec:.0f}s)")
    train_traces, eval_traces = exp.build_corpus()
    train_ds = build_dataset(train_traces, exp.dataset)
    eval_ds = build_dataset(eval_traces, exp.dataset) if eval_traces else None
    _log(f"dataset: {len(train_ds)} train windows"
         + (f" / {len(eval_ds)} eval" if eval_ds else ""))
    want_sharded = (exp.mesh.tp * exp.mesh.sp > 1 or
                    (exp.mesh.dp not in (1, -1))) if sharded is None else sharded
    num_nodes = exp.dataset.graph.max_nodes
    if n_dev > 1 and not want_sharded:
        # dp=-1 means "all remaining devices" to MeshConfig.resolve but is
        # read as "not sharded" here, so joint-100h / joint-dense
        # (dp=-1,tp=1,sp=1) use one device of a multi-chip host
        _log(f"mesh {exp.mesh} is read as unsharded: training on 1 of "
             f"{n_dev} devices")
    if want_sharded and n_dev > 1:
        from nerrf_tpu.models import NerrfNet
        from nerrf_tpu.parallel import (
            init_sharded_state,
            make_mesh,
            make_sharded_train_step,
            shard_batch,
        )

        mesh = make_mesh(exp.mesh)
        kernel_path = _kernel_path(cfg.model, num_nodes)
        _log(f"sharded training over {n_dev} devices "
             f"(mesh {dict(mesh.shape)}) kernel_path={kernel_path}")
        model = NerrfNet(cfg.model)
        state = init_sharded_state(model, cfg, train_ds.arrays, mesh)
        step = make_sharded_train_step(model, cfg, mesh,
                                       compile_cache=compile_cache)
        # the layout as placed, not as configured: a tp axis that silently
        # replicates, or a batch that lands on one device, must show
        sharding = corpus_extra["sharding"] = {
            "mesh": {k: int(v) for k, v in mesh.shape.items()},
            "tp_sharded_leaves": sum(
                "tp" in str(leaf.sharding.spec)
                for leaf in jax.tree_util.tree_leaves(state.params)),
        }
        corpus_extra["kernel_path"] = kernel_path
        import numpy as np

        rng = jax.random.PRNGKey(cfg.seed)
        order = np.random.default_rng(cfg.seed)
        b = max(cfg.batch_size, n_dev)
        t_start = None
        steps_done = 0
        history = []
        for i in range(cfg.num_steps):
            idx = order.choice(len(train_ds), size=b, replace=len(train_ds) < b)
            batch = shard_batch(mesh, {k: v[idx] for k, v in train_ds.arrays.items()})
            state, loss, aux, rng = step(state, batch, rng)
            if i == 0:
                # floating the loss is the step-0 compile barrier
                history.append({"step": 0, "loss": float(loss)})
                sharding["batch_devices"] = len(
                    batch["node_feat"].sharding.device_set)
                _log(f"sharding as placed: {sharding}")
                t_start = time.perf_counter()
            steps_done = i + 1
            if monitor is not None and (i % cfg.eval_every == 0
                                        or i == cfg.num_steps - 1):
                # same cadence/sync contract as the other loops: the
                # monitor observes at logged steps, where the loss is
                # floated anyway — /readyz flips ready after step 0
                # instead of 503ing a healthy multi-hour sharded run
                from nerrf_tpu.train.loop import (
                    _loss_components,
                    _telemetry_floats,
                )

                monitor.observe_step(
                    i, float(loss), telemetry=_telemetry_floats(aux),
                    components=_loss_components(aux))
                if monitor.should_halt:
                    _log(f"trainwatch: halting sharded run at step {i} — "
                         f"{monitor.diverged[1]}")
                    break
        history.append({"step": steps_done - 1, "loss": float(loss)})
        if monitor is not None:
            monitor.finish()
        steps_per_sec = max(steps_done - 1, 1) / max(
            time.perf_counter() - (t_start or 0), 1e-9)
        if monitor is not None and monitor.diverged is not None:
            return _halted_report(exp, cfg, out, monitor, steps_per_sec)
        if jax.process_count() > 1:
            # host-side eval pulls full arrays, which only exists per-process
            # in a multi-controller run; report the (replicated) final loss
            # and leave ranked eval to a single-process job on the checkpoint
            _log("multi-process run: reporting final loss; run eval "
                 "single-process from the saved checkpoint")
            metrics = {"final_loss": float(np.asarray(jax.device_get(loss)))}
        else:
            from nerrf_tpu.train.loop import evaluate, make_eval_fn

            # eval, calibration and the checkpoint are one-device work: on
            # a host copy they run the backend's own kernels, where params
            # left committed to the mesh would drag each program onto it
            state = state.replace(params=jax.device_get(state.params))
            metrics = evaluate(make_eval_fn(model), state.params,
                               eval_ds or train_ds, cfg.batch_size)
        params = state.params
    elif ckpt_every > 0:
        from nerrf_tpu.train.elastic import train_elastic

        res = train_elastic(train_ds, eval_ds, cfg,
                            ckpt_dir=out / "train_state",
                            save_every=ckpt_every, log=_log,
                            compile_cache=compile_cache, monitor=monitor)
        metrics, steps_per_sec, params, history = (
            res.metrics, res.steps_per_sec, res.state.params, res.history)
    else:
        from nerrf_tpu.train.loop import train_nerrfnet

        res = train_nerrfnet(train_ds, eval_ds, cfg, log=_log,
                             compile_cache=compile_cache, monitor=monitor)
        metrics, steps_per_sec, params, history = (
            res.metrics, res.steps_per_sec, res.state.params, res.history)

    if monitor is not None and monitor.diverged is not None:
        return _halted_report(exp, cfg, out, monitor, steps_per_sec)
    corpus_extra.setdefault("kernel_path",
                            _kernel_path(cfg.model, num_nodes))
    return _finish(exp, cfg, out, n_dev, metrics, steps_per_sec, params, t0,
                   history, corpus_extra, calibrate=calibrate,
                   publish_to=publish_to, lineage=lineage)


def _run_stream_experiment(exp, cfg, out: Path, n_dev, compile_cache,
                           t0) -> dict:
    """The stream encoder's pretraining (docs/stream-backbone.md): corpus ->
    event tokens -> packed documents, resident on the device -> the
    trainer's cached, traced, scheduled step (`train/stream.py`)."""
    import jax

    from nerrf_tpu.data.stream import PackConfig, build_packed_streams
    from nerrf_tpu.train.checkpoint import save_stream_checkpoint
    from nerrf_tpu.train.stream import train_stream

    pack = exp.stream_data or PackConfig()
    _log(f"experiment {exp.name}: building corpus "
         f"({exp.corpus.num_traces} traces x {exp.corpus.duration_sec:.0f}s)")
    traces, _ = exp.build_corpus()
    arrays, waste = build_packed_streams(traces, exp.stream.vocab_size, pack)
    _log(f"dataset: {pack.num_seqs} packed sequences of {pack.seq_len} "
         f"event tokens, packing waste {waste:.4f}; stack "
         f"{list(exp.stream.stack)}")
    res = train_stream(arrays, exp.stream, cfg, log=_log,
                       compile_cache=compile_cache,
                       tokens_per_row=pack.seq_len)
    save_stream_checkpoint(out / "model", res.state.params, exp.stream)
    first, last = res.history[0]["loss"], res.history[-1]["loss"]
    report = {
        "experiment": exp.name,
        "backend": jax.default_backend(),
        "devices": n_dev,
        "num_steps": cfg.num_steps,
        "steps_per_sec": round(res.steps_per_sec, 4),
        "loss": {"first": first, "last": last},
        "history": res.history,
        "metrics": {k: round(float(v), 4) for k, v in res.metrics.items()},
        "pack_waste": round(waste, 6),
        "gates": {"loss_fell": bool(last < first)},
        "wall_seconds": round(time.time() - t0, 1),
    }
    (out / "metrics.json").write_text(json.dumps(report, indent=2) + "\n")
    _log(f"done: loss {first:.4f} -> {last:.4f} at "
         f"{res.steps_per_sec:.3f} steps/s")
    return report


def _finish(exp, cfg, out: Path, n_dev, metrics, steps_per_sec, params,
            t0, history, extra, calibrate: bool = True,
            publish_to: str | None = None,
            lineage: str = "default") -> dict:
    import jax

    from nerrf_tpu.train.checkpoint import save_checkpoint

    # weights FIRST: calibration below is best-effort post-processing and
    # must never be able to lose a finished training run
    save_checkpoint(out / "model", params, cfg.model)
    # the held-out-calibrated file-detector operating point travels with
    # the weights (shared helper: checkpoint.calibrate_and_resave guards
    # the untrained-node-head and multi-controller cases)
    from nerrf_tpu.train.checkpoint import calibrate_and_resave

    # calibrate=False: callers whose assertions don't involve the operating
    # threshold (the virtual-mesh CI test) skip the ~9-trace held-out
    # calibration sweep — on a 1-core host it multiplies the test's wall
    # time several times over; every artifact producer keeps the default
    calibration = (calibrate_and_resave(out / "model", params, cfg.model,
                                        node_loss_weight=cfg.node_loss_weight,
                                        log=_log)
                   if calibrate else None)
    published = None
    if publish_to and jax.process_count() != 1:
        # multi-controller: every process would race to publish the same
        # version; say so instead of silently dropping the request
        _log(f"registry publish skipped on a {jax.process_count()}-process "
             f"run — publish the checkpoint from one host: nerrf models "
             f"publish --registry {publish_to} --model-dir {out / 'model'}")
    elif publish_to:
        # the publish hook runs AFTER calibrate_and_resave so the version
        # carries its operating threshold; best-effort — a registry failure
        # must never lose a finished training run (the checkpoint is
        # already safe under --out)
        try:
            from nerrf_tpu.registry import ModelRegistry

            published = ModelRegistry(publish_to).publish(
                lineage, out / "model",
                source=f"nerrf_tpu.train.run --experiment {exp.name}")
            _log(f"published {out / 'model'} as {lineage}/v{published} "
                 f"in {publish_to}")
        except Exception as e:  # noqa: BLE001
            _log(f"registry publish failed ({type(e).__name__}: {e}); "
                 f"checkpoint remains at {out / 'model'}")
    report = {
        "experiment": exp.name,
        "backend": jax.default_backend(),
        "devices": n_dev,
        "num_steps": cfg.num_steps,
        "steps_per_sec": round(steps_per_sec, 3),
        # oldest and newest logged steps (step 0 and the last one, unless
        # the bounded history of a very long run dropped its head)
        "loss": {"first": history[0]["loss"], "last": history[-1]["loss"]}
        if history else None,
        "metrics": {k: round(float(v), 4) for k, v in metrics.items()},
        "calibration": calibration,
        # A head's gate only applies when the experiment trains that head:
        # lstm-impact runs with edge/node weights 0 and toy-graphsage with
        # seq weight 0 — an untrained head's gate could never pass and would
        # fail successful runs of those registry experiments.
        "gates": {
            **({"edge_auc>=0.90": bool(metrics.get("edge_auc", 0) >= 0.90)}
               if cfg.edge_loss_weight > 0 else {}),
            **({"seq_f1>=0.95": bool(metrics.get("seq_f1", 0) >= 0.95)}
               if cfg.seq_loss_weight > 0 else {}),
        },
        "wall_seconds": round(time.time() - t0, 1),
        **({"published_version": published} if published else {}),
        **extra,
    }
    (out / "metrics.json").write_text(json.dumps(report, indent=2) + "\n")
    _log(f"done: {report['metrics']} at {steps_per_sec:.1f} steps/s")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="nerrf_tpu.train.run", description=__doc__)
    ap.add_argument("--experiment", required=True,
                    help="registry name or experiment JSON path")
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=None,
                    help="override the experiment's num_steps")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="elastic full-state checkpoints every N steps")
    ap.add_argument("--publish", default=None, metavar="REGISTRY",
                    help="publish the calibrated checkpoint into this model "
                         "registry after training (see docs/model-lifecycle.md)")
    ap.add_argument("--lineage", default="default",
                    help="registry lineage to publish into (with --publish)")
    ap.add_argument("--aot-cache", default=None, metavar="DIR",
                    help="persistent compile cache root (default: aot/ "
                         "under $JAX_COMPILATION_CACHE_DIR, else under the "
                         "checkout's .compile_cache/) — "
                         "repeat runs on an unchanged config deserialize "
                         "the train-step executable instead of recompiling")
    ap.add_argument("--no-aot-cache", action="store_true",
                    help="disable the persistent compile cache (every run "
                         "pays the full train-step compile)")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="training-health /metrics + /healthz + /readyz "
                         "port (-1 disables; 0 = ephemeral).  /readyz is "
                         "train-aware: 503 before the first completed "
                         "step and after a divergence halt "
                         "(docs/training-health.md)")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="arm the training flight recorder: "
                         "train_divergence / train_starvation / "
                         "train_stall triggers dump self-contained "
                         "bundles here (loss/grad history tail, run "
                         "fingerprints, last-good checkpoint pointer), "
                         "readable offline with `nerrf doctor <bundle>`")
    ap.add_argument("--archive-dir", default=None, metavar="DIR",
                    help="spool the run's telemetry (journal records, "
                         "cadenced metrics snapshots, step-cadence "
                         "workload sketches) into a crash-safe segmented "
                         "archive here — `nerrf report` reconstructs the "
                         "run's health offline (docs/archive.md)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write the run's host spans (the set-up timeline "
                         "before the first step included) as Chrome-trace "
                         "JSON at exit; `nerrf trace --file FILE` reads it "
                         "(docs/operations.md, \"Time to first step\")")
    args = ap.parse_args(argv)
    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    # Multi-host: join the cluster BEFORE any backend use.  Set
    # NERRF_COORDINATOR/NERRF_NUM_PROCESSES/NERRF_PROCESS_ID per process
    # (architecture.mdx:165-189's cross-node deploy, the jax way).
    from nerrf_tpu.parallel import init_distributed

    if init_distributed():
        import jax

        _log(f"distributed: process {jax.process_index()}/"
             f"{jax.process_count()}, {jax.device_count()} global devices")
    compile_cache = None
    if not args.no_aot_cache:
        from nerrf_tpu.compilecache import CompileCache

        compile_cache = CompileCache(root=args.aot_cache, log=_log)
        _log(f"compile cache at {compile_cache.root}")
    try:
        report = run_experiment(args.experiment, args.out, args.steps,
                                args.ckpt_every, publish_to=args.publish,
                                lineage=args.lineage,
                                compile_cache=compile_cache,
                                metrics_port=args.metrics_port,
                                flight_dir=args.flight_dir,
                                archive_dir=args.archive_dir)
    finally:
        if args.trace_out:
            from nerrf_tpu.tracing import DEFAULT_TRACER

            _log(f"trace written to {DEFAULT_TRACER.write(args.trace_out)}")
    return 0 if all(report["gates"].values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())
