"""Seconds JAX spent tracing, lowering and compiling during set-up
(`jax.monitoring` stage durations before the window starts)."""


def read(run):
    value = run["counters"].get("setup_compile_s")
    return None if value is None else float(value)
