"""Seconds of set-up JAX spent tracing, lowering, compiling or reading its own
cache for jitted functions no `CompileCache` fronts: the union of the
program's `jit_compile` spans that no `compile_resolve` is an ancestor of.
(`chipbench/setup_timeline.py`)"""

from chipbench import setup_timeline


def read(run):
    return setup_timeline.read(run, "jit")
