"""Probe for the serve cells that are still to come: boot
`OnlineDetectionService` on a configuration's one bucket at several batch
sizes, ascending, in one process; push the same seeded trace through it on a
few streams; print one JSON line per batch size with what a serve cell has
to be sized from:

* the allocator's ``bytes_in_use`` / ``peak_bytes_in_use`` and the loaded
  programs' ``bytes_reserved`` / ``peak_bytes_reserved`` (a process's peaks
  never fall, hence ascending; PERF.md, Findings PR 25, "memory");
* seconds per bucket call (the service's own device call, timed to
  ``block_until_ready``), calls and windows scored;
* host seconds per admitted window (the caller's time inside ``feed``:
  windowing, graph lowering, admission);
* window -> scored seconds, median and worst, against the configured
  deadline.

No reference, no verdict: this sizes a cell, it proves nothing.

    python3 chipbench/probes/serve_memory.py --config joint-dense --batches 8,32,128
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def one_trace(config: dict, seed: int):
    from chipbench import datagen
    from nerrf_tpu.data import make_corpus

    c = config["corpus"]
    (trace,) = make_corpus(
        1, attack_fraction=1.0, base_seed=datagen.corpus_base_seed(seed),
        duration_sec=c["duration_sec"],
        num_target_files=c["num_target_files"],
        benign_rate_hz=c["benign_rate_hz"])
    return trace


def serve_once(config, params, model, trace, batch: int, streams: int,
               device, log) -> dict:
    import jax

    from nerrf_tpu.observability import MetricsRegistry
    from nerrf_tpu.serve import OnlineDetectionService, ServeConfig

    ds = config["dataset"]
    bucket = (ds["graph"]["max_nodes"], ds["graph"]["max_edges"],
              ds["max_seqs"])
    cfg = ServeConfig(
        buckets=(bucket,), batch_size=batch,
        window_sec=ds["graph"]["window_sec"],
        stride_sec=ds["graph"]["stride_sec"], seq_len=ds["seq_len"],
        min_events=ds["min_events"])
    window_log: list = []
    registry = MetricsRegistry(namespace=f"probe_b{batch}")
    svc = OnlineDetectionService(params, model, cfg=cfg, registry=registry,
                                 window_log=window_log)
    calls = []
    real = svc._run_eval

    def timed(p, b):
        t = time.perf_counter()
        out = jax.block_until_ready(real(p, b))
        calls.append(time.perf_counter() - t)
        return out

    svc._run_eval = timed
    t0 = time.perf_counter()
    svc.start(log=log)
    boot_s = time.perf_counter() - t0
    warm_calls = len(calls)
    events, fields = trace.events, dataclasses.fields(trace.events)
    n_events = len(getattr(events, fields[0].name))
    feed_s, closed = 0.0, 0
    try:
        for s in range(streams):
            svc.join(f"s{s}")
        for lo in range(0, n_events, 4096):
            block = type(events)(**{f.name: getattr(events, f.name)[lo:lo + 4096]
                                    for f in fields})
            for s in range(streams):
                t = time.perf_counter()
                closed += svc.feed(f"s{s}", block, trace.strings)
                feed_s += time.perf_counter() - t
        for s in range(streams):
            svc.leave(f"s{s}", timeout=300.0)
    finally:
        svc.stop()
    stats = device.memory_stats() or {}
    lat = sorted(e[2] for e in window_log)
    steady = calls[warm_calls:]
    return {
        "batch": batch, "bucket": list(bucket), "streams": streams,
        "events_per_stream": n_events, "boot_s": boot_s,
        "windows_closed_in_feed": closed, "windows_scored": len(lat),
        "late": sum(1 for e in window_log if e[3]),
        "dropped_at_admission": {
            reason: registry.value("serve_admission_dropped_total",
                                   labels={"reason": reason})
            for reason in ("oversize", "backpressure", "quarantined",
                           "shed")},
        "bucket_calls": len(steady),
        "bucket_call_s_median": statistics.median(steady) if steady else None,
        "bucket_call_s_max": max(steady) if steady else None,
        "host_feed_s_per_window": feed_s / closed if closed else None,
        "window_to_scored_s_median": lat[len(lat) // 2] if lat else None,
        "window_to_scored_s_max": lat[-1] if lat else None,
        "deadline_s": cfg.window_deadline_sec,
        "memory": {k: int(stats[k]) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved", "bytes_limit") if k in stats},
    }


def main(argv=None, rehearsal: dict | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--batches", required=True)
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--seed", type=int, default=20260930)
    args = ap.parse_args(argv)
    from chipbench import rehearse, run
    from chipbench.traffic import train_resident as tr

    config = run._load_json(ROOT / "chipbench" / "configs"
                            / f"{args.config}.json", "the configuration")
    if rehearsal is not None:
        config = rehearse.merge(config, rehearsal["config"])
    from nerrf_tpu.config import from_dict
    from nerrf_tpu.models.joint import NerrfNet
    from nerrf_tpu.train.loop import TrainConfig

    dev, _ = run.find_device(1, rehearsal is not None)
    if rehearsal is None:
        run.enable_caches()
    model = NerrfNet(from_dict(TrainConfig, config["train"]).model)
    params = tr.make_weights(config, args.seed)
    t = time.perf_counter()
    trace = one_trace(config, args.seed)
    run.say(f"trace: {time.perf_counter() - t:.1f}s")
    for batch in sorted(int(b) for b in args.batches.split(",")):
        try:
            out = serve_once(config, params, model, trace, batch,
                             args.streams, dev, run.say)
        except Exception as e:  # noqa: BLE001 - a probe reports and goes on
            out = {"batch": batch, "error": repr(e)[:600]}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
