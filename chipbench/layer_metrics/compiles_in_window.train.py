"""Backend compilations JAX reported (`jax.monitoring`) between the start
and the end of the measured window.  Expected: 0."""


def read(run):
    value = run["counters"].get("compiles_in_window")
    return None if value is None else float(value)
