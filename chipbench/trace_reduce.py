"""From a profiler trace to the numbers the per-layer readers use.

Two stages, so that the arithmetic can be checked on a small recorded trace
without a profiler (tests/chipbench/test_chipbench_trace_reduce.py):

* `read_xplane(path)` opens the ``.xplane.pb`` JAX's profiler wrote and
  returns the *raw trace*, plain lists and dicts (JSON-able):

      {"devices": [{"name": "/device:TPU:0",
                    "ops": [[name, start_ns, dur_ns], ...],
                    "modules": [[name, start_ns, dur_ns], ...]}, ...],
       "host_spans": [[name, start_ns, dur_ns], ...],
       "op_scopes": {program: {instruction: [op_name, ...]}}}

  ``ops`` are the events of a device's "XLA Ops" line; ``modules`` the
  executions of whole programs ("XLA Modules"); ``host_spans`` the
  benchmark's own
  ``jax.profiler.TraceAnnotation`` spans named in ``HOST_SPANS``;
  ``op_scopes`` the `jax.named_scope` paths of each program's instructions
  (`chipbench.hlo_scopes`).  On a
  backend without device planes (the CPU rehearsal) the host threads' XLA
  op events stand in, and the result says so (``"device_planes": false``).

* `reduce(raw, groups)` turns that into busy time, idle share, the device
  operations that took most time, device time by group of scopes, the step
  program's executions and the longest idle gaps with the host span each
  falls in.

What a v5e's trace looks like (read by hand, PR 25): one plane
``/device:TPU:0`` with lines "Steps", "XLA Modules" (one event per
execution, named ``jit_flat_step(<fingerprint>)``), "XLA Ops" (9,100 events
per `train-1024` step; an event's name is the whole HLO instruction text,
its stats carry offsets only) and "Async XLA Ops" (copies that overlap, not
counted as busy).  ``jax.named_scope`` names do NOT reach the op events
(only a Pallas call is named after its scope); they are read from the
programs' HLO in the trace's metadata plane instead (`chipbench.hlo_scopes`)
and joined to the events by instruction name.  Host
``TraceAnnotation`` spans sit on the ``/host:CPU`` plane's ``python3`` line,
on the same clock as the device events.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Iterable, List, Sequence, Tuple

from chipbench import hlo_scopes

HOST_SPANS = ("dispatch", "wait_inflight")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                           f"{trace_dir}")
    return files[-1]


def _events(line):
    return [[e.name, float(e.start_ns), float(e.duration_ns)]
            for e in line.events]


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
            modules = (_events(lines["XLA Modules"])
                       if "XLA Modules" in lines else [])
            if ops or modules:
                devices.append({"name": plane.name, "ops": ops,
                                "modules": modules})
    host_spans, host_ops = [], []
    for plane in planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_SPANS:
                    host_spans.append([e.name, float(e.start_ns),
                                       float(e.duration_ns)])
                elif not devices and any(k == "hlo_op" for k, _ in e.stats):
                    host_ops.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    device_planes = bool(devices)
    if not devices and host_ops:
        devices = [{"name": "/host:CPU (no device plane)", "ops": host_ops,
                    "modules": []}]
    return {"devices": devices, "host_spans": host_spans,
            "device_planes": device_planes,
            "op_scopes": hlo_scopes.scopes_of_trace(path)}


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by [start, end) intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle stretches of [lo, hi) not covered by any interval."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out


def short_name(hlo_text: str, limit: int = 96) -> str:
    """``%fusion.12 = bf16[8,128]{...} fusion(...), kind=kLoop`` ->
    ``fusion.12 bf16[8,128] kLoop``: enough to tell operations apart in a
    ledger line."""
    head, sep, rest = hlo_text.partition(" = ")
    if not sep:
        return hlo_text[:limit]
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    kind = ""
    if "kind=" in rest:
        kind = " " + rest.split("kind=", 1)[1].split(",", 1)[0].split(")")[0]
    return f"{head.lstrip('%')} {shape}{kind}"[:limit]


def _span_namer(host_spans):
    """-> f(lo, hi): the name of the host span that holds the midpoint of
    [lo, hi), or ``none``.  The benchmark's spans follow one another and do
    not nest, so the last one that starts before the midpoint is the only
    candidate (a bisection: a trace has ~1e5 gaps)."""
    spans = sorted((start, start + dur, name) for name, start, dur in host_spans)
    starts = [s[0] for s in spans]

    def name_of(lo: float, hi: float) -> str:
        mid = 0.5 * (lo + hi)
        i = bisect.bisect_right(starts, mid) - 1
        return spans[i][2] if i >= 0 and mid < spans[i][1] else "none"

    return name_of


def _leaf_ops(ops):
    """Drop events that only wrap others (a ``while`` around its body's
    ops): an op that fully contains a later-starting op is a container, and
    counting both would count the time twice."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    keep = []
    for i, op in enumerate(ops):
        end = op[1] + op[2]
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt[1] < end and nxt[1] + nxt[2] <= end \
                and op[2] > 0:
            continue
        keep.append(op)
    return keep


def instruction_of(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.partition(" = ")[0].lstrip("%")


def group_of(op_names: Sequence[str], groups) -> str:
    """The group most of an operation's scope paths fall in.  ``groups`` is
    an ordered list of ``[name, [substring, ...]]``; a path belongs to the
    first group one of whose substrings it holds.  ``other`` where no path
    is of any group, ``unresolved`` where the operation has no path."""
    if not op_names:
        return "unresolved"
    votes: dict = {}
    for path in op_names:
        for name, patterns in groups:
            if any(p in path for p in patterns):
                votes[name] = votes.get(name, 0) + 1
                break
    return max(votes, key=votes.get) if votes else "other"


def op_groups(leaf_ops, modules, op_scopes, groups) -> List[str]:
    """The scope group of each of ``leaf_ops``, in order.  An operation's
    scopes are looked up in the program whose execution ("XLA Modules"
    event) holds it; one that lies in no recorded execution (the CPU
    rehearsal records none) in the only program that has an instruction of
    its name, if there is just one."""
    runs = sorted((start, start + dur, name) for name, start, dur in modules)
    starts = [r[0] for r in runs]
    owners: dict = {}
    for program, table in op_scopes.items():
        for inst in table:
            owners[inst] = program if inst not in owners else None
    cache: dict = {}
    out = []
    for text, start, _dur in leaf_ops:
        inst = instruction_of(text)
        i = bisect.bisect_right(starts, start) - 1
        program = (runs[i][2] if i >= 0 and start < runs[i][1]
                   else owners.get(inst))
        key = (program, inst)
        if key not in cache:
            cache[key] = group_of(
                op_scopes.get(program, {}).get(inst, ()), groups)
        out.append(cache[key])
    return out


def reduce(raw: dict, groups=None) -> dict:
    """Reduce a raw trace.  The window runs from the first to the last
    device operation; the step program is the module with the most device
    time.  ``groups`` (see `group_of`) asks for device time by scope
    group, ``scope_s``; without it, or where the trace kept no program,
    ``scope_s`` is None."""
    per_device = []
    by_scope = bool(groups) and bool(raw.get("op_scopes"))
    for dev in raw["devices"]:
        ops = dev["ops"]
        if not ops:
            continue
        lo = min(o[1] for o in ops)
        hi = max(o[1] + o[2] for o in ops)
        spans = [(o[1], o[1] + o[2]) for o in ops]
        by_name: dict = {}
        leaf = _leaf_ops(ops)
        for name, _start, dur in leaf:
            name = short_name(name)
            by_name[name] = by_name.get(name, 0.0) + dur
        by_scope_ns: dict = {}
        if by_scope:
            for op, group in zip(leaf, op_groups(
                    leaf, dev["modules"], raw["op_scopes"], groups)):
                by_scope_ns[group] = by_scope_ns.get(group, 0.0) + op[2]
        name_of = _span_namer(raw["host_spans"])
        idle = [(a, b, name_of(a, b)) for a, b in gaps(spans, lo, hi)]
        totals: dict = {}
        for name, _start, dur in dev["modules"]:
            totals[name] = totals.get(name, 0.0) + dur
        step = max(totals, key=totals.get) if totals else None
        per_device.append({
            "window_ns": hi - lo, "busy_ns": union_length(spans),
            "by_name_ns": by_name, "idle_gaps": idle,
            "by_scope_ns": by_scope_ns,
            "step_durations_ns": [m[2] for m in dev["modules"]
                                  if m[0] == step and m[1] >= lo
                                  and m[1] + m[2] <= hi]})
    if not per_device:
        return {"devices": 0}
    n = len(per_device)
    window_s = sum(d["window_ns"] for d in per_device) / n / 1e9
    busy_s = sum(d["busy_ns"] for d in per_device) / n / 1e9
    names: dict = {}
    gaps_by_span: dict = {}
    steps: List[float] = []
    scopes: dict = {}
    for d in per_device:
        for k, v in d["by_name_ns"].items():
            names[k] = names.get(k, 0.0) + v / n / 1e9
        for k, v in d["by_scope_ns"].items():
            scopes[k] = scopes.get(k, 0.0) + v / n / 1e9
        for a, b, span in d["idle_gaps"]:
            gaps_by_span[span] = gaps_by_span.get(span, 0.0) + (b - a) / n / 1e9
        steps.extend(x / 1e9 for x in d["step_durations_ns"])
    return {
        "devices": n, "device_planes": raw.get("device_planes", True),
        "window_s": window_s, "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "leaf_op_s": sum(names.values()),
        "scope_s": scopes if by_scope else None,
        "top_ops": sorted(names.items(), key=lambda kv: -kv[1])[:10],
        "idle_by_span_s": sorted(gaps_by_span.items(),
                                 key=lambda kv: -kv[1])[:10],
        "step_s": sorted(steps),
    }
