"""Traffic kind `train_resident`: the trainer's scheduled resident step.

In the words the generator implements: make the corpus with the
configuration's recipe (`chipbench.datagen`) from the cell's ``corpus_seed``,
exactly ``windows`` windows, the same for every ``--seed`` (the fused SAGE
kernel's time depends on a window's edges: 0.8 % from corpus to corpus, which
is the whole bound); draw the order in which they are trained from ``--seed``
(`make_idx_table`); make the weights on the device from the seed
(`chipbench.reference.params`); build the state and the step as
`train_nerrfnet`'s resident branch does (``TrainState.create`` with
`make_tx`; `make_train_step_scheduled(model, cfg, arrays, idx_table)`;
`cache_train_step(CompileCache(), ..., "train_step_scheduled")`); drive that
one object through its first three steps (set-up: they compile or load, and
the comparison keeps what they produced); then, for ``--seconds``, call
``train_step(state, rng)`` with at most ``in_flight`` steps in flight (the
trainer's own loop never waits; the bound keeps the window's end near
``--seconds``); stop
dispatching when the clock passes ``--seconds``, block on the last state and
divide the windows trained by the time actually elapsed.

Once the window has closed and the peak memory has been read, the program's
state is dropped and the plain reference follows the same three steps
(`follow_reference`); `chipbench.compare` holds the two together.

Parameters of a mix (``chipbench/traffic/<mix>.json``, ``"generator":
"train_resident"``): ``batch``, ``windows`` (resident dataset, a multiple of
batch), ``traces``, ``corpus_seed``, ``in_flight`` (steps dispatched and not
yet done, at most), ``table_rows`` (rows of the index table, fixed so that
every seed compiles the same program).  Of a cell
(``chipbench/workloads/<cell>.json``): ``trace_seconds`` (length of the
traced window with ``--trace 1``), ``reference_block`` (windows per
reference block), ``limits`` (of the numbers compared for `correct`).
"""

from __future__ import annotations

import collections
import contextlib
import gc
import importlib
import math
import time

import numpy as np

from chipbench import compare, datagen

WARMUP_STEPS = 3          # the steps the reference follows
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """When each of JAX's own backend compilations ended."""

    def __init__(self) -> None:
        self.ends = []   # perf_counter at the end of each

    def listen(self) -> None:
        import jax.monitoring as mon

        def on_duration(event, secs, **_):
            if event == _BACKEND_COMPILE:
                self.ends.append(time.perf_counter())

        mon.register_event_duration_secs_listener(on_duration)

    def backend_compiles(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.ends if lo <= t < hi)


def seed_words(seed: int):
    return np.random.SeedSequence(int(seed)).generate_state(4)


def make_idx_table(seed: int, rows: int, windows: int, batch: int):
    """[rows, batch] int32, in epochs: every ``windows / batch`` consecutive
    rows are one seeded permutation of all the resident windows, so each row
    holds distinct windows and every seed trains the same windows equally
    often, in another order (a kernel whose time depends on a window's edges
    then takes the same time whatever the seed)."""
    if windows < batch or windows % batch or rows % (windows // batch):
        raise RuntimeError(
            f"dataset smaller than the batch, or not whole epochs: {windows} "
            f"windows, batch {batch}, {rows} table rows")
    rng = np.random.default_rng([int(seed), 0x1d8])
    epochs = rows // (windows // batch)
    return np.concatenate([rng.permutation(windows).reshape(-1, batch)
                           for _ in range(epochs)]).astype(np.int32)


def step_keys(seed: int, steps: int):
    """(rng0, [dropout key of step k]): the chain the step walks, ``rng,
    dropout_rng = split(rng)`` per step, computed by the benchmark so that
    the reference never asks the program for a key."""
    import jax

    rng0 = jax.random.PRNGKey(int(seed_words(seed)[1]) & 0x7FFFFFFF)
    rng, keys = rng0, []
    for _ in range(steps):
        rng, dk = jax.random.split(rng)
        keys.append(dk)
    return rng0, keys


def make_weights(config: dict, seed: int):
    import jax

    from chipbench.reference import params as rparams

    return rparams.make_params(
        config, jax.random.PRNGKey(int(seed_words(seed)[2]) & 0x7FFFFFFF))


def build_step(config: dict, batch: int, arrays: dict, idx_table, params,
               cache_root=None, log=None):
    """-> (state, train_step, infos): the program's scheduled resident step
    behind its AOT cache, and a fresh state around ``params``.
    ``TrainConfig.seed`` and ``num_steps`` stay the configuration's: they
    are in the AOT key, ``--seed`` travels through arguments only."""
    import dataclasses

    from flax.training import train_state
    from nerrf_tpu.compilecache import CompileCache
    from nerrf_tpu.config import from_dict
    from nerrf_tpu.models.joint import NerrfNet
    from nerrf_tpu.train.loop import (TrainConfig, cache_train_step,
                                      make_train_step_scheduled, make_tx)

    cfg = dataclasses.replace(from_dict(TrainConfig, config["train"]),
                              batch_size=batch)
    model = NerrfNet(cfg.model)
    state = train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=make_tx(cfg))
    step = make_train_step_scheduled(model, cfg, arrays, idx_table)
    got = step.tail[1].shape
    if tuple(got) != tuple(idx_table.shape) or got[1] != batch:
        raise RuntimeError(f"the step received an index table {got}, "
                           f"the cell trains a batch of {batch}")
    cached = cache_train_step(CompileCache(root=cache_root, log=log), step,
                              model, cfg, "train_step_scheduled")
    return state, cached, cached.infos


def _adam_mu(opt_state):
    """The first-moment tree of the optimizer state (the one node that has
    ``mu``)."""
    import jax

    found = []

    def visit(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
            return
        if isinstance(node, (tuple, list)):
            for child in node:
                visit(child)

    visit(opt_state)
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return jax.device_get(found[0])


def executable_temp_bytes(train_step) -> int:
    """Scratch (XLA temp) bytes in the plan of the step's compiled
    executable, from its own memory analysis: a diagnostic beside the
    runtime's readings (PERF.md, Findings PR 25, "memory": the plan says
    6.78 GB where the runtime reserves 4.19 GB).  0 where the executable
    cannot be reached or says nothing."""
    total = 0
    step_cache = getattr(train_step, "_sc", None)
    for fn, _info in getattr(step_cache, "_fns", {}).values():
        try:
            total = max(total, int(fn.memory_analysis().temp_size_in_bytes))
        except Exception:  # noqa: BLE001 - a live jit fallback has none
            pass
    return total


def timed_window(train_step, state, rng, seconds: float, spans: bool,
                 in_flight: int = 2):
    """Drive the step for ``seconds`` with at most ``in_flight`` steps
    dispatched and not yet done.
    -> (state, rng, steps, elapsed, losses, dispatch seconds per call)."""
    import jax

    if spans:
        span = jax.profiler.TraceAnnotation
    else:
        span = lambda name: contextlib.nullcontext()  # noqa: E731
    pending = collections.deque()
    losses, dispatch = [], []
    t0 = time.perf_counter()
    while True:
        t_a = time.perf_counter()
        if t_a - t0 >= seconds:
            break
        with span("dispatch"):
            state, loss, _aux, rng = train_step(state, rng)
        dispatch.append(time.perf_counter() - t_a)
        pending.append(loss)
        losses.append(loss)
        if len(pending) >= in_flight:
            with span("wait_inflight"):
                pending.popleft().block_until_ready()
    with span("wait_inflight"):
        jax.block_until_ready(state)
    elapsed = time.perf_counter() - t0
    return state, rng, len(losses), elapsed, losses, dispatch


def follow_reference(config: dict, cell: dict, arrays: dict, idx_table,
                     seed: int, precision: str = "f32", rows=None):
    """The plain reference through the first steps -> {"losses",
    "grad_norms", "update_norms", "leaves"}.  ``rows`` (a function of the
    step's row indices) is how a test or a probe plants a fault such as
    "half of the batch left out"."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import adamw

    ref = importlib.import_module(f"chipbench.reference.{config['model']}")
    steps = WARMUP_STEPS
    _, keys = step_keys(seed, steps)
    params0 = make_weights(config, seed)
    fn = ref.make_loss_and_grad(config["train"]["model"], config["train"],
                                precision)
    params, opt = params0, adamw.init(params0)
    losses, first_grad = [], None
    for k in range(steps):
        idx = np.asarray(idx_table[k % len(idx_table)])
        if rows is not None:
            idx = rows(idx)
        batch = {name: jnp.asarray(v[idx]) for name, v in arrays.items()
                 if name != "node_key"}
        block = min(int(cell["reference_block"]), len(idx))
        loss, grads = ref.loss_and_grad(fn, params, batch, keys[k], block)
        params, opt, clipped = adamw.update(params, grads, opt,
                                            config["train"])
        losses.append(float(loss))
        if first_grad is None:
            first_grad = compare.leaf_norms(clipped)
    delta = jax.tree_util.tree_map(lambda a, b: a - b, params, params0)
    names, update_norms = compare.leaf_norms(delta)
    return {"losses": losses, "grad_norms": first_grad[1],
            "update_norms": update_norms, "leaves": names}


def program_phase(ctx, arrays, idx_table, compile_log):
    """Everything that holds the program's device state; returns host data
    only, so that the state is gone when the reference starts."""
    import jax

    config, cell, seed = ctx.config, ctx.cell, ctx.seed
    batch = int(cell["batch"])
    t0 = time.perf_counter()
    params = make_weights(config, seed)
    params0 = jax.device_get(params)
    state, train_step, infos = build_step(
        config, batch, arrays, idx_table, params,
        cache_root=ctx.cache_root, log=ctx.log)
    rng, _ = step_keys(seed, 0)

    # the first steps, through the window's own call: the object compiled
    # here is the one the window drives
    losses, mu1 = [], None
    for k in range(WARMUP_STEPS):
        state, loss, _aux, rng = train_step(state, rng)
        losses.append(float(loss))
        if k == 0:
            mu1 = _adam_mu(state.opt_state)
        ctx.log(f"warm-up step {k + 1}: loss {losses[-1]:.6f} "
                f"({time.perf_counter() - t0:.1f}s since weights)")
    params3 = jax.device_get(state.params)
    from chipbench.reference import adamw

    grad1 = jax.tree_util.tree_map(lambda m: m / (1.0 - adamw.B1), mu1)
    delta = jax.tree_util.tree_map(lambda a, b: a - b, params3, params0)
    prog = {"losses": losses,
            "grad_norms": compare.leaf_norms(grad1)[1],
            "update_norms": compare.leaf_norms(delta)[1]}
    del params0, params3, mu1, grad1, delta

    seconds = ctx.seconds
    trace_dir = None
    if ctx.trace:
        seconds = min(seconds, float(cell["trace_seconds"]))
        trace_dir = ctx.make_trace_dir()
        # the device's events and the benchmark's own annotations: Python's
        # call tracer would add ~15,000 host events per step and slow the
        # dispatching thread
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    window_start = time.perf_counter()
    try:
        state, rng, steps, elapsed, win_losses, dispatch = timed_window(
            train_step, state, rng, seconds, spans=ctx.trace,
            in_flight=int(cell["in_flight"]))
    finally:
        if ctx.trace:
            jax.profiler.stop_trace()
    window_end = time.perf_counter()
    win_losses = [float(x) for x in win_losses]
    stats = ctx.device.memory_stats() or {}
    allocator_peak = int(stats.get("peak_bytes_in_use", 0))
    reserved = int(stats.get("peak_bytes_reserved", 0))
    plan = executable_temp_bytes(train_step)
    out = {
        "prog": prog, "steps": steps, "elapsed": elapsed,
        "window_start": window_start, "window_end": window_end,
        "failed": sum(1 for x in win_losses if not math.isfinite(x)),
        "last_loss": win_losses[-1] if win_losses else None,
        "dispatch": dispatch, "trace_dir": trace_dir,
        "memory_peak_bytes": allocator_peak + reserved,
        "memory_stats": {k: int(v) for k, v in stats.items()
                         if isinstance(v, (int, float))},
        "executable_plan_temp_bytes": plan,
        "aot": [f"{i.source}:{i.reason}" if i.reason else i.source
                for i in infos],
        "compiles_in_window": compile_log.backend_compiles(
            window_start, window_end),
    }
    ctx.log(f"window: {steps} steps of {batch} in {elapsed:.3f}s = "
            f"{steps * batch / elapsed:.3f} windows/s; peak "
            f"{out['memory_peak_bytes'] / 1e9:.3f} GB (allocator "
            f"{allocator_peak / 1e9:.3f} + reserved {reserved / 1e9:.3f}; "
            f"the compiler's plan had {plan / 1e9:.3f} of scratch); aot {out['aot']}; "
            f"compiles in window {out['compiles_in_window']}")
    del state, train_step
    return out


def run(ctx) -> dict:
    """One run of a `train_resident` cell -> the harness's run record."""
    config, cell = ctx.config, ctx.cell
    batch, windows = int(cell["batch"]), int(cell["windows"])
    compile_log = CompileLog()
    compile_log.listen()

    t = time.perf_counter()
    arrays = datagen.make_windows(config, int(cell["corpus_seed"]),
                                  int(cell["traces"]), windows)
    ctx.log(f"data: {windows} windows from {cell['traces']} traces in "
            f"{time.perf_counter() - t:.1f}s")
    idx_table = make_idx_table(ctx.seed, int(cell["table_rows"]), windows,
                               batch)

    rec = program_phase(ctx, arrays, idx_table, compile_log)
    gc.collect()

    t = time.perf_counter()
    ref = follow_reference(config, cell, arrays, idx_table, ctx.seed)
    reference_s = time.perf_counter() - t
    ctx.log(f"reference: {len(ref['losses'])} steps in {reference_s:.1f}s")
    worst: dict = {}
    numbers = compare.compare_training(rec["prog"], ref, worst)
    correct, table, not_compared = compare.verdict(numbers, cell["limits"])
    if rec["failed"] or rec["steps"] == 0:
        correct = False

    work = importlib.import_module(f"chipbench.work.{config['model']}")
    setup_s = rec["window_start"] - ctx.t_start
    rate = rec["steps"] * batch / rec["elapsed"] if rec["elapsed"] > 0 else 0.0
    return {
        "correct": correct, "compared": table,
        "attempted": rec["steps"], "failed": rec["failed"],
        "end_to_end": {"train_windows_per_s": rate, "setup_s": setup_s},
        "memory_peak_bytes": rec["memory_peak_bytes"],
        "trace_dir": rec["trace_dir"],
        "counters": {
            "batch": batch, "steps": rec["steps"],
            "window_s": rec["elapsed"], "windows_per_s": rate,
            "dispatch_s": rec["dispatch"],
            "compiles_in_window": rec["compiles_in_window"],
            "memory_peak_bytes": rec["memory_peak_bytes"],
            "train_flops_per_window": work.train_flops(config),
            "train_work_per_window": work.train_work(config),
            "scope_groups": work.SCOPE_GROUPS,
        },
        "extras": {
            "aot": rec["aot"], "reference_s": reference_s,
            "last_loss": rec["last_loss"],
            "memory_stats": rec["memory_stats"],
            "executable_plan_temp_bytes": rec["executable_plan_temp_bytes"],
            "program_losses": rec["prog"]["losses"],
            "reference_losses": ref["losses"],
            "worst_leaf": worst, "not_compared": not_compared,
            "bytes_per_window": (rec["memory_peak_bytes"] / batch
                                 if batch else None),
        },
    }
