"""Pipeline-wide span tracing: where did the step time go?

The metrics registry (`nerrf_tpu.observability`) answers "how many"; this
module answers "where did the time go" — the load-bearing question for a
TPU training/inference stack, where the failure mode is an idle accelerator
hidden behind a healthy-looking throughput counter (the first-class signal
of the GPU/TPU GNN benchmarking and Podracer literatures: host-blocked vs
device vs data-wait vs padding waste).

Zero-dependency by design (stdlib only, like the registry): `span()` is a
thread-safe context manager that records host-side spans into a bounded
ring buffer and **dual-writes** every span into the metrics registry as a
``stage_latency_seconds{stage=...}`` histogram — one instrumentation point
keeps Prometheus and traces consistent by construction.

Exports are Chrome trace-event JSON (`chrome://tracing` / Perfetto
loadable: ``{"traceEvents": [{"ph": "X", ...}]}``), so a host trace drops
into the same UI as an XLA device trace taken with
`observability.trace_profile`.  Device-side mirroring: model code wraps the
GNN layers / LSTM scan / the aggregate in `jax.named_scope`, and
``span(..., device=True)`` opens a `jax.profiler.TraceAnnotation` of the
span's name — inert without a profiler session, and with one the span sits
on the ``/host:CPU`` plane on the device events' clock.

Every span has an ``id`` and the ``parent`` that was the innermost open span
of its thread when it started (None at the top of a thread), so a span's
**self time** — its duration less what its children cover — can be read
(`self_time`, the ``self_ms`` column of `nerrf trace`).

Span naming scheme (dot-separated, coarse → fine; `docs/operations.md`,
"Time to first step", reads the set-up names as one timeline):

    ingest_decode      EventBatch frame → native decode (ingest client)
    tracker_stream     one StreamEvents subscription, start → drain (ingest)
    graph_lower        one window of events → padded GraphBatch (builder)
    trace_lower        one trace → all its padded window samples: labels,
                       the windows' graph_lower children, the per-file
                       sequences (train.data.windows_of_trace)
    store_compact      trace-store delta → bucket segments
    store_query        trace-store window read
    bucket_pad         trace → capacity-bucketed padded window samples
    detect_score       one chunk of padded windows through the eval program
    calibrate          held-out file-threshold calibration
    data_wait          host blocked waiting for input data
    corpus_simulate    one synthetic trace simulated (data.make_corpus)
    stream_tokenize    one trace's events → token ids (data.stream)
    stream_pack        documents → packed [num_seqs, seq_len] sequences
    dataset_upload     host arrays → device, chunked (device_put_chunked)
    module_import      one of the program's heavy modules imported (what it
                       brings in after the tracer's epoch); args: module
    compile_resolve    one executable obtained: fingerprint, cache read,
                       deserialize or compile, persist (CompileCache);
                       args: program, source, reason.  Its stages are its
                       children:
      compile_resolve.fingerprint   avals, environment_key() with its
                                    source_digest(), the key's hash
      compile_resolve.read          entry found and read; args: bytes,
                                    adopted (a seed entry was copied in)
      compile_resolve.deserialize   deserialize_and_load
      compile_resolve.lower         jit_fn.lower(...)
      compile_resolve.compile       lowered.compile()
      compile_resolve.serialize     serialize_executable.serialize
      compile_resolve.persist       write + rename + prune; args: bytes
    jit_compile        one of JAX's own trace / lower / backend-compile /
                       persistent-cache-retrieval durations, recorded when
                       it ended (`record`); args: stage, fun
    train_setup        the loop's state built (model.init) and, with
                       phase="step_fns", its step functions made
    step_build         the resident step made: its dataset_upload child
                       and the jit wrappers (make_train_step_scheduled)
    train_loop         the stepping loop, first call to last wait
    train_step_call    one call of a train step: the program's Python
                       plus the runtime's call; args: call (0-based)
    train_step_execute the resolved executable's call alone (child of
                       train_step_call)
    train_step_wait    the loop blocked on a device result at a sync it
                       has anyway (step-0 barrier, logged step, end)
    devtime_cost       the analytic cost trace behind the MFU gauges
    eval               held-out evaluation pass
    checkpoint         full-state checkpoint save
    mcts_plan          one planner search; mcts_leaf_eval = device batch
    serve_admit        one stream window measured/lowered/enqueued (serve)
    serve_batch_close  a bucket's shared batch assembled (occupancy/deadline)
    serve_device_score one shared padded batch through the eval program
    serve_demux        scored batch fanned back to streams + alert sink
    registry_shadow_score  a shadow version scored beside the live one
    registry_swap      the live parameters swapped for a registry version

The ring buffer records unconditionally (bounded memory, ~µs overhead)
and there is no switch: no span syncs with the device, so recording never
changes how a loop runs.  "Tracing off" is "no profiler session and no
``--trace-out``"; the device's side of a step is the profiler's to give.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional

# The one histogram every span dual-writes into (per-stage label).
STAGE_HISTOGRAM = "stage_latency_seconds"
_STAGE_HELP = "host-side span latency per pipeline stage"

# Latency buckets sized for the pipeline's spread: µs-scale decodes up to
# multi-minute compiles/evals.
STAGE_BUCKETS = (0.0005, 0.002, 0.01, 0.05, 0.25, 1.0, 5.0, 30.0, 120.0)


class Span:
    """One recorded host-side region.  ``t0``/``dur`` are perf-counter
    seconds relative to the owning tracer's epoch; ``id`` is unique within
    the tracer and ``parent`` is the id of the innermost span open on the
    same thread when this one started (None at the top); ``args`` is the
    mutable attribute dict the ``with`` body may extend (exported verbatim
    into the Chrome event's ``args``)."""

    __slots__ = ("name", "t0", "dur", "tid", "args", "id", "parent")

    def __init__(self, name: str, args: Dict, id: int,
                 parent: Optional[int]) -> None:
        self.name = name
        self.t0 = 0.0
        self.dur = 0.0
        self.tid = threading.get_ident()
        self.args = args
        self.id = id
        self.parent = parent


def _process_age() -> Optional[float]:
    """Seconds since this process started, from the kernel's own record:
    ``/proc/self/stat``'s start time (clock ticks since boot, 10 ms) against
    ``CLOCK_BOOTTIME``.  None where the platform has no such record: never
    a guess."""
    try:
        with open("/proc/self/stat") as f:
            stat = f.read()
        # the fields after "(comm)", which may itself hold spaces
        start_ticks = int(stat[stat.rindex(")") + 2:].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def _annotation(name: str, stats: Dict):
    """An entered `jax.profiler.TraceAnnotation`, or None where jax is not
    imported yet (this module must not force backend init) or refuses."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        ann = jax.profiler.TraceAnnotation(name, **stats)
        ann.__enter__()
        return ann
    except Exception:
        return None


class Tracer:
    """Thread-safe ring-buffered span recorder with Chrome-trace export."""

    def __init__(self, capacity: int = 65536, registry=None) -> None:
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=capacity)
        self._registry = registry
        self._thread_names: Dict[int, str] = {}
        # perf_counter origin for span timestamps; the wall-clock anchor
        # travels in the export so traces from different processes can be
        # aligned offline
        self._t0_perf = time.perf_counter()
        self._t0_epoch = time.time()
        # when the process started, in seconds relative to the epoch above
        # (negative); what lies between is not the tracer's to span
        age = _process_age()
        self.process_start: Optional[float] = None if age is None else -age
        self._ids = itertools.count(1)
        self._open = threading.local()  # .stack: ids of this thread's open spans

    # -- recording -----------------------------------------------------------

    def _reg(self):
        if self._registry is None:
            from nerrf_tpu.observability import DEFAULT_REGISTRY

            self._registry = DEFAULT_REGISTRY
        return self._registry

    def _open_stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def _append(self, sp: Span) -> None:
        with self._lock:
            self._spans.append(sp)
            # latest name wins: CPython recycles thread idents, so a
            # cached dead thread's name must not label a new thread
            self._thread_names[sp.tid] = threading.current_thread().name
        self._reg().histogram_observe(
            STAGE_HISTOGRAM, sp.dur, buckets=STAGE_BUCKETS,
            labels={"stage": sp.name}, help=_STAGE_HELP)

    @contextlib.contextmanager
    def span(self, stage: str, device: bool = False, **args):
        """Record a host-side span named ``stage``.

        Always records (ring buffer + ``stage_latency_seconds`` histogram);
        the yielded :class:`Span` exposes ``args`` for attributes the body
        learns mid-flight.  ``device=True`` additionally opens a
        `jax.profiler.TraceAnnotation` of the same name (only when jax is
        already imported — this module must not force backend init), so the
        region shows up host-side in an XLA profiler trace, on the device
        events' clock; a ``call`` argument travels with it as a stat, so a
        host call and the execution it started can be matched there.
        """
        stack = self._open_stack()
        sp = Span(stage, args, next(self._ids), stack[-1] if stack else None)
        stats = {"call": args["call"]} if "call" in args else {}
        ann = _annotation(stage, stats) if device else None
        stack.append(sp.id)
        t0 = time.perf_counter()
        sp.t0 = t0 - self._t0_perf
        try:
            yield sp
        finally:
            sp.dur = time.perf_counter() - t0
            stack.pop()
            if ann is not None:
                with contextlib.suppress(Exception):
                    ann.__exit__(None, None, None)
            self._append(sp)

    def record(self, name: str, dur: float, device: bool = False,
               **args) -> Span:
        """Append a span that ended now and lasted ``dur`` seconds: for an
        event the program is told of only when it is over (a `jax.monitoring`
        duration).  Ids, the parent (the innermost span open on this thread
        now) and the dual-write are `span`'s.  ``device=True`` leaves an
        instant `jax.profiler.TraceAnnotation` of the name at the event's
        end, its duration beside it as the stat ``dur_us`` (an annotation
        cannot be dated back)."""
        stack = self._open_stack()
        sp = Span(name, args, next(self._ids), stack[-1] if stack else None)
        sp.dur = max(float(dur), 0.0)
        sp.t0 = time.perf_counter() - self._t0_perf - sp.dur
        if device:
            ann = _annotation(name, {"dur_us": int(sp.dur * 1e6)})
            if ann is not None:
                with contextlib.suppress(Exception):
                    ann.__exit__(None, None, None)
        self._append(sp)
        return sp

    # -- inspection / export -------------------------------------------------

    def records(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON object (Perfetto / chrome://tracing)."""
        pid = os.getpid()
        with self._lock:
            spans = list(self._spans)
            names = dict(self._thread_names)
        events: List[dict] = [{
            "ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": "nerrf host"},
        }]
        for tid, tname in names.items():
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name", "args": {"name": tname}})
        for s in spans:
            ev = {
                "name": s.name, "ph": "X", "pid": pid, "tid": s.tid,
                "ts": round(s.t0 * 1e6, 3),       # µs, tracer-epoch origin
                "dur": round(s.dur * 1e6, 3),
                "id": s.id, "parent": s.parent,
            }
            if s.args:
                ev["args"] = dict(s.args)
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "nerrf_tpu.tracing",
                "epoch_anchor_unix_sec": self._t0_epoch,
                # seconds from the epoch back to the process's start
                # (negative), None where the platform does not say
                "process_start_sec": self.process_start,
            },
        }

    def write(self, path) -> str:
        """Write the Chrome-trace JSON to ``path`` (returns the path)."""
        path = os.fspath(path)
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


# The process-wide tracer every pipeline component records into (the span
# analogue of observability.DEFAULT_REGISTRY).
DEFAULT_TRACER = Tracer()


def span(stage: str, device: bool = False, **args):
    """``DEFAULT_TRACER.span`` — the one-import instrumentation point."""
    return DEFAULT_TRACER.span(stage, device=device, **args)


def record(name: str, dur: float, device: bool = False, **args) -> Span:
    """``DEFAULT_TRACER.record``: a span that has already ended."""
    return DEFAULT_TRACER.record(name, dur, device=device, **args)


# -- trace-file analysis (the `nerrf trace` subcommand's engine) -------------


def load_chrome_trace(path) -> List[dict]:
    """Complete ("X") events from a Chrome-trace JSON file — accepts both
    the object form ({"traceEvents": [...]}) and a bare event list."""
    with open(os.fspath(path)) as f:
        data = json.load(f)
    events = data.get("traceEvents", []) if isinstance(data, dict) else data
    if not isinstance(events, list):
        return []
    return [e for e in events if isinstance(e, dict) and e.get("ph") == "X"]


def self_time(events: Iterable[dict]) -> List[float]:
    """Self time of each "X" event, in µs and in the events' order: its
    duration less the part of its interval that its children (the events
    whose ``parent`` is its ``id``) cover — overlapping children count
    once, a child is clipped to its parent.  An event from a file written
    before spans had ids has no children and keeps its whole duration."""
    events = list(events)
    children: Dict[int, List[dict]] = {}
    for e in events:
        if e.get("parent") is not None:
            children.setdefault(e["parent"], []).append(e)
    out = []
    for e in events:
        lo, dur = float(e["ts"]), float(e.get("dur", 0.0))
        kids = children.get(e.get("id"), ())
        out.append(dur * (1.0 - coverage(kids, lo, lo + dur)) if kids else dur)
    return out


def stage_summary(events: Iterable[dict]) -> Dict[str, dict]:
    """Per-stage latency stats from "X" events: count, total/mean/p50/max
    ms, and ``self_ms``, the stage's total `self_time`."""
    events = list(events)
    by_name: Dict[str, List[float]] = {}
    self_us: Dict[str, float] = {}
    for e, own in zip(events, self_time(events)):
        by_name.setdefault(e["name"], []).append(float(e.get("dur", 0.0)))
        self_us[e["name"]] = self_us.get(e["name"], 0.0) + own
    out: Dict[str, dict] = {}
    for name, durs in by_name.items():
        durs.sort()
        n = len(durs)
        out[name] = {
            "count": n,
            "total_ms": sum(durs) / 1e3,
            "self_ms": self_us[name] / 1e3,
            "mean_ms": sum(durs) / n / 1e3,
            "p50_ms": durs[n // 2] / 1e3,
            "max_ms": durs[-1] / 1e3,
        }
    return out


def wall_clock_us(events: Iterable[dict]) -> float:
    """Trace extent: max(ts+dur) − min(ts) over the "X" events, in µs."""
    lo, hi = None, None
    for e in events:
        t0 = float(e["ts"])
        t1 = t0 + float(e.get("dur", 0.0))
        lo = t0 if lo is None else min(lo, t0)
        hi = t1 if hi is None else max(hi, t1)
    return 0.0 if lo is None else hi - lo


def coverage(events: Iterable[dict],
             lo_us: Optional[float] = None,
             hi_us: Optional[float] = None) -> float:
    """Fraction of [lo, hi] covered by the union of span intervals (nested
    and overlapping spans count once).  Defaults to the trace's own extent —
    the acceptance check "spans cover ≥ X% of wall-clock"."""
    ivals = sorted(
        (float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0.0)))
        for e in events
    )
    if not ivals:
        return 0.0
    if lo_us is None:
        lo_us = ivals[0][0]
    if hi_us is None:
        hi_us = max(b for _, b in ivals)
    if hi_us <= lo_us:
        return 0.0
    covered = 0.0
    cur_a, cur_b = None, None
    for a, b in ivals:
        a, b = max(a, lo_us), min(b, hi_us)
        if b <= a:
            continue
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                covered += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        covered += cur_b - cur_a
    return covered / (hi_us - lo_us)


def format_stage_table(events: Iterable[dict]) -> str:
    """Human-readable per-stage latency table (sorted by total time)."""
    events = list(events)
    summary = stage_summary(events)
    wall_ms = wall_clock_us(events) / 1e3
    header = (f"{'stage':<24} {'count':>7} {'total_ms':>10} {'self_ms':>10} "
              f"{'mean_ms':>9} {'p50_ms':>9} {'max_ms':>9} {'%wall':>6}")
    lines = [header, "-" * len(header)]
    for name, s in sorted(summary.items(), key=lambda kv: -kv[1]["total_ms"]):
        pct = 100.0 * s["total_ms"] / wall_ms if wall_ms > 0 else 0.0
        lines.append(
            f"{name:<24} {s['count']:>7} {s['total_ms']:>10.2f} "
            f"{s['self_ms']:>10.2f} {s['mean_ms']:>9.3f} {s['p50_ms']:>9.3f} {s['max_ms']:>9.2f} "
            f"{pct:>5.1f}%")
    lines.append(f"wall: {wall_ms:.2f} ms, span coverage: "
                 f"{100.0 * coverage(events):.1f}%")
    return "\n".join(lines)
