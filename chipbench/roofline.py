"""A kernel's share of its roofline.

The least time the chip could take for a piece of required work is the
larger of its operations over the peak FLOP/s and its bytes over the peak
HBM bytes/s (`chipbench/peaks.json`); the share is that least time over the
device time the trace shows for it.  Required work comes from shapes
(`chipbench/work/<model>.py`), never from what an implementation executed,
so a share cannot pass 100 % unless the work is counted too high or the
time leaves out operations; nothing here clips it.
"""

from __future__ import annotations


def least_seconds(flops: float, moved: float, peaks: dict):
    """-> (seconds, "flops" or "bytes": which of the two bounds)."""
    by_flops = flops / peaks["flops_per_s_bf16"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")


def share(run: dict, name: str):
    """Percent of its roofline that the work ``name`` of the run's model
    (``counters["train_work_per_window"][name]``) reached in the traced
    window, or None where the trace has no time for its scope groups."""
    work = (run["counters"].get("train_work_per_window") or {}).get(name)
    scope_s = (run.get("trace") or {}).get("scope_s")
    windows = (run.get("trace") or {}).get("windows_in_trace")
    if not work or not scope_s or not windows:
        return None
    seconds = sum(scope_s.get(g, 0.0) for g in work["groups"])
    if seconds <= 0.0:
        return None
    least, _bound = least_seconds(work["flops"], work["bytes"], run["peaks"])
    return 100.0 * windows * least / seconds
