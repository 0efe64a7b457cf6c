"""The program's own spans, read in process after the window.

`chipbench/run.py` executes each per-layer reader in the program's process
(`read_metric`), so a reader may read what the program keeps in memory: the
span ring of `nerrf_tpu.tracing.DEFAULT_TRACER` (65,536 spans; a run
records under a thousand, so nothing of a run is evicted before it is
read).  A span there has ``name``, ``t0`` and ``dur`` (seconds on the
tracer's clock), ``args``, an ``id`` and the ``parent`` that was open on its
thread when it started.  Nothing else of the program is read from here, and
nothing is written: no reader clears the ring.

Which spans belong to the run.  A step object numbers its calls from 0
(`train_step_call`'s ``call``), and a run drives one step object: the run's
calls are those from the last ``call == 0`` on.  Of them the last
``counters["steps"]`` are the window's (after the window the benchmark calls
the program's step no more: the reference is the benchmark's own code) and
the `WARMUP_CALLS` before them are set-up's.  Set-up is every span that
ended before the window's first call started and, in a process that has run
a cell before (the CPU tests do), started after that earlier run's last
step call ended.  Where the run has fewer than ``steps + WARMUP_CALLS``
calls, or the ring is not the program's (the parent of the PR that brought
these spans has none), every reader returns None: never a number from the
wrong calls.

Span -> metric: `train_step_call` -> ``step_call_ms.train`` and, less its
children (`train_step_execute`, a `compile_resolve` if one happens),
``step_call_self_ms.train``; `compile_resolve` ->
``step_resolves_in_window.train``.  Set-up's spans are read by
`chipbench/setup_timeline.py`.
"""

from __future__ import annotations

from chipbench.trace_reduce import union_length

STEP_CALL = "train_step_call"
RESOLVE = "compile_resolve"
WARMUP_CALLS = 3


def program_ring() -> list:
    """The spans the program has recorded in this process, oldest first;
    [] where the program has no tracer."""
    try:
        from nerrf_tpu.tracing import DEFAULT_TRACER
    except ImportError:
        return []
    return list(DEFAULT_TRACER.records())


def split_run(spans: list, steps: int):
    """-> {"window": the window's step calls, "setup": set-up's spans,
    "after": the spans that started at or after the window's first call},
    or None where ``spans`` do not hold the whole run (see the module's
    docstring)."""
    calls = [i for i, s in enumerate(spans) if s.name == STEP_CALL]
    firsts = [i for i in calls if spans[i].args.get("call") == 0]
    if steps < 1 or not firsts:
        return None
    run_calls = [i for i in calls if i >= firsts[-1]]
    if len(run_calls) < steps + WARMUP_CALLS:
        return None
    window = [spans[i] for i in run_calls[-steps:]]
    if [s.args.get("call") for s in window] != list(
            range(len(run_calls) - steps, len(run_calls))):
        return None
    earlier = [spans[i] for i in calls if i < firsts[-1]]
    lo = earlier[-1].t0 + earlier[-1].dur if earlier else float("-inf")
    start = window[0].t0
    return {"window": window,
            "setup": [s for s in spans
                      if s.t0 >= lo and s.t0 + s.dur <= start],
            "after": [s for s in spans if s.t0 >= start]}


def of_run(run: dict):
    """`split_run` of the program's ring for the run a reader was given."""
    steps = int((run.get("counters") or {}).get("steps") or 0)
    return split_run(program_ring(), steps)


def self_seconds(span, spans: list) -> float:
    """``span``'s duration less the part of its interval that its children
    cover (each child clipped to it, overlaps counted once)."""
    lo, hi = span.t0, span.t0 + span.dur
    covered = union_length(
        (max(c.t0, lo), min(c.t0 + c.dur, hi)) for c in spans
        if getattr(c, "parent", None) == span.id and c.t0 < hi
        and c.t0 + c.dur > lo)
    return span.dur - covered


def mean_call_ms(run: dict, self_only: bool = False):
    """Mean duration (or self time) of the window's step calls, ms."""
    parts = of_run(run)
    if parts is None:
        return None
    calls = parts["window"]
    if self_only:
        seconds = [self_seconds(c, parts["after"]) for c in calls]
    else:
        seconds = [c.dur for c in calls]
    return 1e3 * sum(seconds) / len(seconds)


def resolves_in_window(run: dict):
    parts = of_run(run)
    if parts is None:
        return None
    return float(sum(1 for s in parts["after"] if s.name == RESOLVE))

