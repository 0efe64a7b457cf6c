"""Median device duration of the step program's executions inside the
traced window (profiler trace, "XLA Modules"), ms."""


def read(run):
    steps = (run.get("trace") or {}).get("step_s") or []
    if not steps:
        return None
    return 1e3 * steps[len(steps) // 2]
