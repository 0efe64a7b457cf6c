"""The whole step's share of the chip's bf16 peak: required training FLOPs
per window (`chipbench/work/<model>.py`, from shapes) x windows per second
of this run's window / peak.  Needs no scope in the trace, so it bounds any
claim even when a later change takes a kernel off the path."""


def read(run):
    c = run["counters"]
    flops = (c.get("train_flops_per_window") or {}).get("total")
    rate = c.get("windows_per_s")
    if not flops or not rate:
        return None
    return 100.0 * flops * rate / run["peaks"]["flops_per_s_bf16"]
