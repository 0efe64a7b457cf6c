#!/usr/bin/env python3
"""Graph-layer capacity proof at real-eBPF window density (VERDICT r1 item 7).

The docs project ~25 k syscall events per 45 s window for live capture
(`/root/reference/docs/content/docs/threat-model.mdx:121-137`); the training
defaults are 256 nodes / 512 edges.  This bench answers, with numbers:

  1. what a 25 k-event window actually needs (exact node/edge counts),
  2. lowering time and drop counts across the capacity ladder,
  3. whether GraphConfig.fit's auto-bucketing achieves zero drops.

Writes benchmarks/results/graph_capacity.json.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _log(m):
    print(f"[cap] {m}", file=sys.stderr, flush=True)


def bench_builder(report: dict) -> None:
    from nerrf_tpu.data.labels import derive_event_labels
    from nerrf_tpu.data.synth import SimConfig, simulate_trace
    from nerrf_tpu.graph import GraphConfig, build_window_graph
    from nerrf_tpu.graph.builder import measure_window

    tr = simulate_trace(SimConfig(duration_sec=90.0, benign_rate_hz=550.0,
                                  num_target_files=45, attack=True,
                                  attack_start_sec=30.0, seed=5))
    labels = derive_event_labels(tr)
    ev = tr.events
    lo = int(ev.ts_ns[ev.valid].min())
    hi = lo + 45 * 10**9
    need_n, need_e = measure_window(ev, lo, hi)
    report["window"] = {
        "events": int(((ev.ts_ns >= lo) & (ev.ts_ns < hi) & ev.valid).sum()),
        "needs_nodes": need_n, "needs_edges": need_e,
    }
    _log(f"25k window needs {need_n} nodes / {need_e} edges")

    ladder = []
    for n, e in [(256, 512), (512, 1024), (1024, 2048), (2048, 4096),
                 (4096, 8192)]:
        t0 = time.perf_counter()
        _, stats = build_window_graph(ev, tr.strings, lo, hi,
                                      GraphConfig(max_nodes=n, max_edges=e),
                                      labels=labels)
        ladder.append({
            "max_nodes": n, "max_edges": e,
            "lowering_ms": round((time.perf_counter() - t0) * 1e3, 1),
            "dropped_nodes": stats.dropped_nodes,
            "dropped_events": stats.dropped_events,
            "event_drop_pct": round(
                100.0 * stats.dropped_events / max(stats.num_events, 1), 1),
        })
        _log(f"  {ladder[-1]}")
    report["capacity_ladder"] = ladder

    fit = GraphConfig().fit(ev, lo, hi)
    t0 = time.perf_counter()
    _, stats = build_window_graph(ev, tr.strings, lo, hi, fit, labels=labels)
    report["auto_fit"] = {
        "max_nodes": fit.max_nodes, "max_edges": fit.max_edges,
        "lowering_ms": round((time.perf_counter() - t0) * 1e3, 1),
        "dropped_nodes": stats.dropped_nodes,
        "dropped_events": stats.dropped_events,
    }
    _log(f"auto-fit → {report['auto_fit']}")

    # training-corpus density: are the defaults justified there?
    tr_small = simulate_trace(SimConfig(duration_sec=90.0, benign_rate_hz=40.0,
                                        num_target_files=24, attack=True,
                                        attack_start_sec=30.0, seed=6))
    ev2 = tr_small.events
    lo2 = int(ev2.ts_ns[ev2.valid].min())
    n2, e2 = measure_window(ev2, lo2, lo2 + 45 * 10**9)
    # judge against what the flagship experiment ACTUALLY trains at
    from nerrf_tpu.config import EXPERIMENTS

    g = EXPERIMENTS["joint-100h"].dataset.graph
    report["training_density_window"] = {
        "needs_nodes": n2, "needs_edges": e2,
        "configured": [g.max_nodes, g.max_edges],
        "fits": bool(n2 <= g.max_nodes and e2 <= g.max_edges)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="benchmarks/results/graph_capacity.json")
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU")
    args = ap.parse_args(argv)

    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    report: dict = {"generated": time.strftime("%Y-%m-%d %H:%M:%S")}
    bench_builder(report)
    from nerrf_tpu.ops.segment import active_impls

    # the routes a step's ops take on this backend
    report["kernel_path"] = active_impls()
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps({k: report[k] for k in ("window", "auto_fit")}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
