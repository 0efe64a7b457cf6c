"""Device milliseconds a step under ``dsa_topk``: the exact selection of
each query's 2048 keys (the counting passes over the index scores, forward
and in every recomputation).  None where the trace has no such scope."""


def read(run):
    trace = run.get("trace") or {}
    seconds = (trace.get("scope_s") or {}).get("dsa_topk")
    steps = run["counters"].get("steps")
    if not seconds or not steps:
        return None
    return 1e3 * seconds / steps
