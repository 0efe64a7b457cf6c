"""The neighbourhood aggregate's share of its roofline: least time for its
required work (`chipbench/work/<model>_aggregate.py`: from shapes, the same
whatever implements it; bytes bound it at both buckets) over the device
time of every operation traced under a ``sage_aggregate`` scope: the fused
Pallas kernel on one route, ``adj @ msg`` on the other, and their
transposes.  None where the trace has no such group."""

import importlib

from chipbench import roofline


def read(run):
    trace = run.get("trace") or {}
    seconds = (trace.get("scope_s") or {}).get("sage_aggregate", 0.0)
    windows = trace.get("windows_in_trace")
    if seconds <= 0.0 or not windows:
        return None
    work = importlib.import_module(
        f"chipbench.work.{run['config']['model']}_aggregate").train_work(
            run["config"])
    least, _bound = roofline.least_seconds(work["flops"], work["bytes"],
                                           run["peaks"])
    return 100.0 * windows * least / seconds
