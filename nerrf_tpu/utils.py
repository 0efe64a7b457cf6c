"""Small host-side utilities shared by the bench, doctor, and entry points.

Only stdlib at module level: CLI startup must not pay a jax import for
commands that never touch a device.
"""

from __future__ import annotations

import os


def sync_result(x):
    """Wait for a jitted call's output to actually exist, and return it.

    A completion barrier by *fetching*: a device-to-host copy cannot finish
    before the program that produces the value, and one XLA program's
    outputs materialize together, so fetching the smallest output leaf
    proves the whole call ran.  Equivalent to ``jax.block_until_ready`` as a
    barrier (chip_smoke.py times both and requires them to agree); it costs
    one small transfer more.
    """
    import jax
    import numpy as np

    leaves = [l for l in jax.tree_util.tree_leaves(x)
              if hasattr(l, "dtype") and hasattr(l, "size")]
    if leaves:
        np.asarray(jax.device_get(min(leaves, key=lambda l: l.size)))
    return x


def fetch_value(x):
    """Device-to-host copy of ``x`` as numpy — the value-returning flavor of
    ``sync_result``.  Use for scalars/small arrays whose value the caller
    needs anyway; use ``sync_result`` when only completion matters."""
    import jax
    import numpy as np

    return np.asarray(jax.device_get(x))


def compile_cache_dir() -> str:
    """The one root both compile caches live under — JAX's persistent cache
    at the root, the AOT `compilecache.CompileCache` in ``aot/`` below it.

    ``$JAX_COMPILATION_CACHE_DIR`` when set (the operator or the chip tool
    places the cache; code then sets no directory of its own), else one
    fixed, git-ignored directory in the checkout: the path is part of JAX's
    cache key, so a directory that moves between runs never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".compile_cache")


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache under `compile_cache_dir`.

    Every chip-side consumer (train runs, the bench, the offline
    benchmarks, the device planner) compiles the same handful of programs,
    each tens of seconds; the disk cache makes process N's compile pay
    forward to process N+1.

    Called explicitly by chip-side entry points — not at package import,
    which must stay jax-free for CLI startup latency.  With
    JAX_COMPILATION_CACHE_DIR set JAX reads the variable itself and this
    function touches nothing; NERRF_NO_COMPILE_CACHE=1 is the tests' off
    switch.  Only compiles above jax's default time threshold are
    persisted, so CPU test runs don't spray sub-second entries onto disk.

    It is also an entry point's first word to the compile layer, so JAX's
    own compiles are recorded as ``jit_compile`` spans from here on
    (`compilecache.cache.install_jit_listener`; a `CompileCache` built
    later finds the listener installed)."""
    from nerrf_tpu.compilecache.cache import install_jit_listener

    install_jit_listener()
    if os.environ.get("NERRF_NO_COMPILE_CACHE") == "1":
        return
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    cache = compile_cache_dir()
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
