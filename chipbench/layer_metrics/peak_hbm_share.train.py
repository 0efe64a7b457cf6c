"""The run's ``memory_peak_bytes`` (the allocator's peak plus what the
loaded program reserves beside it) over the chip's HBM bytes (peaks file),
in percent."""


def read(run):
    peak = run["counters"].get("memory_peak_bytes")
    if not peak:
        return None
    return 100.0 * peak / run["peaks"]["hbm_bytes"]
