"""Probe: run one cell with ``--trace 1`` and write down what the profiler's
trace looks like (planes, lines, sample events with every stat) before the
harness deletes it, so that `trace_reduce` can be written against what the
chip really emits.  Output: ``chiprun_out/trace_dump_<cell>.txt`` and the
raw reduced-trace sample ``chiprun_out/trace_sample_<cell>.json``.

    python3 chipbench/probes/trace_dump.py --workload train-1024 --seed 1 --seconds 12
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def dump(trace_dir: str, out_txt: Path, out_json: Path) -> None:
    from jax.profiler import ProfileData

    from chipbench import trace_reduce

    path = trace_reduce.find_xplane(trace_dir)
    data = ProfileData.from_file(path)
    with open(out_txt, "w") as f:
        f.write(f"{path} {Path(path).stat().st_size} bytes\n")
        for plane in data.planes:
            lines = list(plane.lines)
            f.write(f"PLANE {plane.name!r} lines={len(lines)}\n")
            for line in lines:
                events = list(line.events)
                f.write(f"  LINE {line.name!r} events={len(events)}\n")
                step = max(1, len(events) // 40)
                for e in events[::step][:60]:
                    f.write(f"    {e.name!r} start={e.start_ns} "
                            f"dur={e.duration_ns} stats={dict(e.stats)}\n")
    raw = trace_reduce.read_xplane(path)
    from chipbench.work import nerrfnet as work

    with open(out_txt, "a") as f:
        dump_scopes(path, raw, f, work.SCOPE_GROUPS)
    save_hlo_and_op_totals(path, raw, out_txt.with_suffix(""))
    small = {"host_spans": raw["host_spans"][:200],
             "device_planes": raw["device_planes"],
             "devices": [{"name": d["name"], "ops": d["ops"][:4000],
                          "modules": d["modules"][:50]}
                         for d in raw["devices"]]}
    out_json.write_text(json.dumps(small))


def dump_scopes(path: str, raw: dict, f, groups) -> None:
    """Device seconds by scope group, as `trace_reduce` attributes them, and
    the largest operations it could not attribute."""
    from chipbench import trace_reduce

    scope_s = trace_reduce.reduce(raw, groups)["scope_s"] or {}
    total = sum(scope_s.values())
    f.write("\nSCOPES (chipbench.hlo_scopes over the metadata plane)\n")
    for program, table in raw["op_scopes"].items():
        f.write(f"  program {program!r}: {len(table)} instructions named\n")
    for group, secs in sorted(scope_s.items(), key=lambda kv: -kv[1]):
        f.write(f"  {group}: {secs:.6f} s ({100 * secs / total:.2f} %)\n")
    loose: dict = {}
    for dev in raw["devices"]:
        leaf = trace_reduce._leaf_ops(dev["ops"])
        for (text, _start, dur), group in zip(leaf, trace_reduce.op_groups(
                leaf, dev["modules"], raw["op_scopes"], groups)):
            if group in ("other", "unresolved"):
                key = f"{group} {trace_reduce.short_name(text)}"
                loose[key] = loose.get(key, 0.0) + dur
    f.write("  largest operations outside the groups:\n")
    for key, dur in sorted(loose.items(), key=lambda kv: -kv[1])[:40]:
        f.write(f"    {dur / 1e9:.6f} s  {key}\n")


def save_hlo_and_op_totals(path: str, raw: dict, stem: Path) -> None:
    """The programs' serialized HloProto (``<stem>.<n>.hlo.pb``) and the
    device's leaf-op seconds by instruction (``<stem>.ops.json``): enough to
    work on the op -> scope resolution without the chip."""
    from chipbench import hlo_scopes, trace_reduce

    for n, (program, blob) in enumerate(hlo_scopes.hlo_protos(path).items()):
        if len(blob) > 100_000:
            Path(f"{stem}.{n}.hlo.pb").write_bytes(bytes(blob))
    totals: dict = {}
    for dev in raw["devices"]:
        for text, _s, dur in trace_reduce._leaf_ops(dev["ops"]):
            row = totals.setdefault(trace_reduce.instruction_of(text),
                                    [0, 0.0, trace_reduce.short_name(text)])
            row[0] += 1
            row[1] += dur
    Path(f"{stem}.ops.json").write_text(json.dumps(
        {"modules": [d["modules"] for d in raw["devices"]], "ops": totals}))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from chipbench import run

    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    cleanup = run.Context.cleanup

    def dump_then_cleanup(self):
        for d in self._tmp:
            try:
                dump(d, out / f"trace_dump_{args.workload}.txt",
                     out / f"trace_sample_{args.workload}.json")
            except Exception as e:  # noqa: BLE001 - a probe reports and goes on
                print(f"trace dump failed: {e!r}", file=sys.stderr)
        cleanup(self)

    run.Context.cleanup = dump_then_cleanup
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"])


if __name__ == "__main__":
    raise SystemExit(main())
