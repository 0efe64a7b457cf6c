"""Probe: where one cell's set-up goes, from the program's own span ring.

Runs the cell once through `run.run_cell` (so set-up is the benchmark's own)
and prints, for the spans of set-up alone (`program_spans.split_run`): the
five parts of `chipbench/setup_timeline.py`; the per-stage table `nerrf trace`
prints (`tracing.format_stage_table`: `compile_resolve.<stage>` rows and
`jit_compile` included); each `compile_resolve` stage by stage with its
``bytes``; the `jit_compile` seconds by function; and the longest stretches
no program span covers, each with the spans on either side: what the
benchmark's own work between the program's calls looks like from inside.
The first run of a call is cold (no compile cache), a second run of the same
cell in the same call is warm: run it twice for both.

    python3 chipbench/probes/setup_timeline.py --workload train-1024 --seed 1

Also written to ``chiprun_out/setup_timeline_<cell>_<tag>.txt``.
"""

from __future__ import annotations

import time

T_TOP = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def as_events(spans) -> list:
    return [{"name": s.name, "ts": s.t0 * 1e6, "dur": s.dur * 1e6,
             "id": s.id, "parent": s.parent, "args": s.args} for s in spans]


def holes(spans, lo: float, hi: float, least: float = 0.25) -> list:
    """[(start, seconds, span that ended last before it, span that starts
    it off)] for the stretches of [lo, hi] that no span covers."""
    out, cur, last = [], lo, "(process start)"
    for s in sorted(spans, key=lambda s: s.t0):
        if s.t0 - cur >= least:
            out.append((cur, s.t0 - cur, last, s.name))
        if s.t0 + s.dur > cur:
            cur, last = s.t0 + s.dur, s.name
    if hi - cur >= least:
        out.append((cur, hi - cur, last, "(the window's first call)"))
    return out


def report(steps: int) -> str:
    from nerrf_tpu import tracing

    from chipbench import program_spans as ps
    from chipbench import setup_timeline as st

    ring = ps.program_ring()
    known, process_start = st.program_start()
    parts = st.timeline(ring, steps, process_start) if known else None
    if parts is None:
        return "the program's ring holds no whole run, or no timeline"
    split = ps.split_run(ring, steps)
    setup, start = split["setup"], split["window"][0].t0
    lines = ["set-up, seconds: " + "  ".join(
        f"{k} {'None' if v is None else format(v, '.3f')}"
        for k, v in parts.items()), "",
        tracing.format_stage_table(as_events(setup)), ""]
    by_id = {s.id: s for s in setup}
    for r in (s for s in setup if s.name == ps.RESOLVE):
        stages = "  ".join(
            f"{s.name.split('.', 1)[1]} {s.dur:.3f}"
            + (f" ({s.args['bytes']} B)" if "bytes" in s.args else "")
            + (" adopted" if s.args.get("adopted") else "")
            for s in setup if s.parent == r.id)
        lines.append(f"compile_resolve {r.args.get('program')} "
                     f"{r.args.get('source')}:{r.args.get('reason')} "
                     f"at {r.t0:.2f} s, {r.dur:.3f} s: {stages}")
    by_fun = defaultdict(lambda: [0, 0.0])
    for s in setup:
        if s.name == st.JIT:
            inside = by_id.get(s.parent)
            where = ("in " + inside.name) if inside is not None else "alone"
            key = (str(s.args.get("fun")), s.args.get("stage"), where)
            by_fun[key][0] += 1
            by_fun[key][1] += s.dur
    lines += ["", "jit_compile by function (summed; nested traces count in "
              "their callers too):"]
    for (fun, stage, where), (n, secs) in sorted(
            by_fun.items(), key=lambda kv: -kv[1][1])[:25]:
        lines.append(f"  {secs:8.3f} s  {n:4d} x  {stage:<16} {fun}  [{where}]")
    lines += ["", "stretches of 0.25 s or more that no program span covers "
              "(start, seconds, after, before):"]
    lo = process_start if parts["preprogram"] is not None else 0.0
    for at, secs, after, before in holes(setup, lo, start):
        lines.append(f"  {at:8.2f}  {secs:7.3f}  {after} -> {before}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tag", default="run")
    args = ap.parse_args(argv)
    from chipbench import run        # its clock (`setup_s`) starts here

    # what the program's tracer cannot see inside `preprogram`: timed here,
    # in the order `run.py` pays it
    t0 = time.perf_counter()
    import jax

    t1 = time.perf_counter()
    jax.devices()
    t2 = time.perf_counter()
    try:
        result = run.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace))
    except run.BenchError as e:
        run.say(f"error: {e}")
        return 1
    e2e = result["extras"].get("end_to_end") or {
        k: v["value"] for k, v in result["metrics"].items()}
    steps = int(result["attempted"])
    from nerrf_tpu.tracing import DEFAULT_TRACER

    other = DEFAULT_TRACER.chrome_trace()["otherData"]
    started = other["epoch_anchor_unix_sec"] + (
        other.get("process_start_sec") or 0.0)
    before = (f"before the program's first import: process start to the "
              f"probe's first line {T_TOP - started:.3f} s, `import jax` "
              f"{t1 - t0:.3f} s, the backend's start (`jax.devices()`) "
              f"{t2 - t1:.3f} s\n")
    text = (f"{args.workload} seed {args.seed} ({args.tag}): setup_s "
            f"{e2e['setup_s']:.3f}, train_windows_per_s "
            f"{e2e['train_windows_per_s']:.4f}, correct {result['correct']}, "
            f"aot {result['extras'].get('aot')}\n"
            + "metrics: " + json.dumps({k: v["value"] for k, v in
                                        result["metrics"].items()})
            + "\n" + before + report(steps))
    print(text, flush=True)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"setup_timeline_{args.workload}_{args.tag}.txt").write_text(
        text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
