"""Device milliseconds a step around the experts' products: under
``moe_router`` (softmax, top-8, renormalisation), ``moe_dispatch`` (ranks,
offsets, the buffer's row map) and ``moe_combine`` (the gather back and the
weighted sum).  None where the trace has none of these scopes."""

SCOPES = ("moe_router", "moe_dispatch", "moe_combine")


def read(run):
    scope_s = (run.get("trace") or {}).get("scope_s") or {}
    seconds = sum(scope_s.get(name, 0.0) for name in SCOPES)
    steps = run["counters"].get("steps")
    if not seconds or not steps:
        return None
    return 1e3 * seconds / steps
