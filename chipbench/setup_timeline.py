"""The time before the first step, as the program's own tracer tells it.

One family of five readers (`layer_metrics/setup_timeline_<part>_s.train.py`;
all would move ``setup_s``), read in the program's process after the window
like `chipbench/program_spans.py`'s (whose `split_run` says which spans of the
ring are set-up's); `chipbench/probes/setup_timeline.py` prints the same
set-up span by span.  Set-up's extent runs
from the process's start (`Tracer.process_start`: the kernel's record of it,
on the tracer's clock) to the start of the window's first `train_step_call`:

    extent = preprogram + union(every program span in set-up) + unspanned

``preprogram``  process start to the tracer's epoch, which is the program's
                first import: interpreter, the caller's own imports,
                ``import jax``, the backend's start where it precedes the
                program
``data``        union of the program's data spans (`DATA_SPANS`)
``resolve``     union of `compile_resolve`
``jit``         union of the `jit_compile` spans that no `compile_resolve`
                is an ancestor of: what JAX traced, lowered, compiled or read
                from its own cache for jitted functions nothing fronts
``unspanned``   what is left of the extent: neither the program nor its
                tracer can name it (the caller's own work between the
                program's calls, a wait on the device outside every span)

``data``, ``resolve`` and ``jit`` are parts of the union, not all of it
(warm-up step calls, `train_setup`, `step_build`, `module_import` are in it
too), so the five do not add up to the extent; the identity above does.

Every reader returns None on a ring that lacks what it needs, never a number
from the wrong spans: a program without `Tracer.process_start` (the parent of
the PR that brought this file) gives None five times; ``preprogram`` and
``unspanned`` give None in a process that has run a cell before (the CPU
tests do: the time since that run's last step is its reference's, not this
run's set-up) and ``preprogram`` where the platform has no record of the
process's start; ``jit`` where the ring holds no `jit_compile` at all (no
listener ever fired).
"""

from __future__ import annotations

from chipbench.program_spans import (RESOLVE, STEP_CALL, program_ring,
                                     split_run)
from chipbench.trace_reduce import union_length

JIT = "jit_compile"
DATA_SPANS = ("corpus_simulate", "trace_lower", "graph_lower",
              "stream_tokenize", "stream_pack", "dataset_upload")


def program_start():
    """(has the program a set-up timeline at all, `Tracer.process_start`)."""
    try:
        from nerrf_tpu.tracing import DEFAULT_TRACER
    except ImportError:
        return False, None
    if not hasattr(DEFAULT_TRACER, "process_start"):
        return False, None
    return True, DEFAULT_TRACER.process_start


def _union(spans, hi: float) -> float:
    """Seconds of [0, hi] that ``spans`` cover, overlaps counted once."""
    return union_length((max(s.t0, 0.0), min(s.t0 + s.dur, hi))
                        for s in spans if s.t0 + s.dur > 0.0 and s.t0 < hi)


def outside_resolve(spans: list) -> list:
    """The `jit_compile` spans of ``spans`` that have no `compile_resolve`
    among their ancestors (which are looked up in ``spans``)."""
    by_id = {s.id: s for s in spans}
    out = []
    for s in spans:
        if s.name != JIT:
            continue
        up = by_id.get(s.parent)
        while up is not None and up.name != RESOLVE:
            up = by_id.get(up.parent)
        if up is None:
            out.append(s)
    return out


def timeline(spans: list, steps: int, process_start):
    """-> the five seconds and the two sides of the identity (``extent``,
    ``spanned``) for the run that ``spans`` end with, each None where it
    cannot be told; None where ``spans`` do not hold a whole run."""
    parts = split_run(spans, steps)
    if parts is None:
        return None
    setup, start = parts["setup"], parts["window"][0].t0
    first = [s for s in setup if s.name == STEP_CALL
             and s.args.get("call") == 0][-1]
    fresh = not any(s.name == STEP_CALL and s.t0 < first.t0 for s in spans)
    out = {
        "preprogram": (-process_start
                       if fresh and process_start is not None else None),
        "data": _union([s for s in setup if s.name in DATA_SPANS], start),
        "resolve": _union([s for s in setup if s.name == RESOLVE], start),
        "jit": (_union(outside_resolve(setup), start)
                if any(s.name == JIT for s in spans) else None),
        "spanned": _union(setup, start),
        "extent": None, "unspanned": None,
    }
    if fresh:
        out["extent"] = start + (out["preprogram"] or 0.0)
        out["unspanned"] = start - out["spanned"]
    return out


def read(run: dict, part: str):
    """One part of the run's timeline, for a reader file."""
    known, process_start = program_start()
    if not known:
        return None
    steps = int((run.get("counters") or {}).get("steps") or 0)
    parts = timeline(program_ring(), steps, process_start)
    return None if parts is None else parts[part]
