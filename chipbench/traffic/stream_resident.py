"""Traffic kind `stream_resident`: the stream pretrainer's scheduled resident
step over packed event-token sequences.

In the words the generator implements: simulate the mix's ``traces`` with
the configuration's corpus recipe from the mix's fixed ``corpus_seed`` (the
same for every ``--seed``: the attention's time depends on the documents'
lengths); tokenize, cut into documents of lognormal length and pack them
first-fit with the program's own `nerrf_tpu.data.stream.build_packed_streams`
into exactly ``num_seqs`` sequences of ``seq_len`` tokens, resident on the
device; draw the order in which they are trained from ``--seed``, in epochs
and within pairs of sequences of like required work (`make_order_table`);
make the weights on the device from the seed (`chipbench.reference.phi4flash.make_params`, under the program's
parameter names); build the state and the step as `python -m
nerrf_tpu.train.run` does for a stream experiment (``TrainState.create``
with `make_tx`; `nerrf_tpu.train.stream.make_stream_step`: the scheduled
resident step behind `cache_train_step`); drive that one object through its
first three steps (set-up: they compile or load, and the comparison keeps
what they produced); then, for ``--seconds``, call ``train_step(state,
rng)`` with at most ``in_flight`` steps in flight (`train_resident`'s own
window, with its ``dispatch`` / ``wait_inflight`` annotations), block on
the last state and divide the sequences trained by the time elapsed.  One
"window" of ``train_windows_per_s`` is one packed sequence.

Once the window has closed and the peak memory has been read, the program's
state is dropped and the plain reference follows the same three steps
(`follow_reference`); `chipbench.compare` holds the two together.

Parameters of a mix (``"generator": "stream_resident"``): ``batch``,
``seq_len``, ``num_seqs`` (a multiple of batch), ``traces``,
``corpus_seed``, ``doc_median``, ``doc_sigma``, ``doc_min`` (the documents'
lognormal lengths, clipped to ``[doc_min, seq_len]``), ``in_flight``,
``table_rows``.  Of a cell: ``trace_seconds``, ``limits``.
"""

from __future__ import annotations

import gc
import importlib
import math
import time

import numpy as np

from chipbench import compare, datagen
from chipbench.traffic import train_resident as tr

WARMUP_STEPS = tr.WARMUP_STEPS


def stream_config_of(config: dict):
    """The benchmark's configuration file -> the program's `StreamConfig`:
    the published keys under the program's names."""
    from nerrf_tpu.config import from_dict
    from nerrf_tpu.models.stream import StreamConfig

    a = {k: v["value"] for k, v in config["assumed"].items() if "value" in v}
    return from_dict(StreamConfig, {
        "dim": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "mlp_dim": config["intermediate_size"],
        "window": config["sliding_window"],
        "d_state": a["d_state"], "d_conv": a["d_conv"],
        "expand": a["expand"], "dt_rank": a["dt_rank"],
        "num_layers": config["num_hidden_layers"],
        "kinds": list(config["kinds"]),
        "published_layers": list(config["published_layers"]),
        "vocab_size": config["vocab_size"],
        "dropout": float(config["resid_pdrop"]),
        "dtype": config["dtype"]})


def train_config_of(config: dict, batch: int):
    import dataclasses

    from nerrf_tpu.config import from_dict
    from nerrf_tpu.train.loop import TrainConfig

    return dataclasses.replace(from_dict(TrainConfig, config["train"]),
                               batch_size=batch)


def make_sequences(config: dict, cell: dict):
    """-> ({"tokens", "segments"} [num_seqs, seq_len] int32, waste): the
    same arrays for every ``--seed``."""
    from nerrf_tpu.data import make_corpus
    from nerrf_tpu.data.stream import PackConfig, build_packed_streams

    c = config["corpus"]
    traces = make_corpus(
        int(cell["traces"]), attack_fraction=c["attack_fraction"],
        base_seed=datagen.corpus_base_seed(int(cell["corpus_seed"])),
        duration_sec=c["duration_sec"],
        num_target_files=c["num_target_files"],
        benign_rate_hz=c["benign_rate_hz"])
    pack = PackConfig(
        seq_len=int(cell["seq_len"]), num_seqs=int(cell["num_seqs"]),
        doc_median=float(cell["doc_median"]),
        doc_sigma=float(cell["doc_sigma"]), doc_min=int(cell["doc_min"]),
        seed=int(cell["corpus_seed"]))
    return build_packed_streams(traces, int(config["vocab_size"]), pack)


def make_order_table(seed: int, rows: int, cost) -> np.ndarray:
    """[rows, 1] int32, in epochs: every ``len(cost)`` consecutive rows hold
    every resident sequence once.  A sequence's required work follows its
    documents (5.7 to 33.6 M attending pairs), and so does its step: 746 ms
    to 800 ms alone on the chip, the five one-document sequences the most.
    A window of 10 s holds 13 of the 32, so under a free permutation
    (`train_resident.make_idx_table`) ``train_windows_per_s`` is a draw of
    its sequences: 1.16 % between 8 seeds measured in one process, 0.64 %
    over 2000 seeds simulated from the 32 times, where the admission of a
    cell asks for under 0.5 % (PERF.md section 6, PR 28's review round).  So
    the sequences are sorted by ``cost`` (their attending pairs: a property
    of the traffic, not of the program) and paired with their neighbours;
    each half of an epoch holds one member of every pair, the pairs in a
    fixed order that alternates cheap and costly, and ``--seed`` draws which
    member comes in which half.  Every seed's window then trains one
    sequence of each pair, in another choice: 0.086 % between 6 seeds in
    that same process, 0.053 % between 6 runs."""
    n = len(cost)
    if n % 4 or rows % n:
        raise RuntimeError(f"not whole epochs of cost pairs: {n} sequences, "
                           f"{rows} table rows")
    cost = np.asarray(cost)
    pairs = np.argsort(cost, kind="stable").reshape(-1, 2)
    k = len(pairs)
    # one coin for two pairs: the pairs whose members differ most are set
    # against each other, so that a half's total hardly depends on the coins
    by_gap = np.argsort(-(cost[pairs[:, 1]] - cost[pairs[:, 0]]),
                        kind="stable")
    # cheapest, costliest, second cheapest, ...
    position = np.stack([np.arange(k), k - 1 - np.arange(k)], 1).ravel()[:k]
    rng = np.random.default_rng([int(seed), 0x0bde4])
    out = []
    for _ in range(rows // n):
        first = np.empty(k, np.int64)
        coins = rng.integers(0, 2, size=k // 2)
        first[by_gap[0::2]], first[by_gap[1::2]] = coins, 1 - coins
        for member in (first, 1 - first):
            out.append(pairs[np.arange(k), member][position])
    return np.concatenate(out).astype(np.int32)[:, None]


def sequence_costs(config: dict, segments) -> list:
    """Attending pairs (full, causal, within a document) of each resident
    sequence: what its attention layers' time follows."""
    work = importlib.import_module(f"chipbench.work.{config['model']}")
    return [work.packing_of(row[None], config["sliding_window"])["pairs_full"]
            for row in np.asarray(segments)]


def make_weights(config: dict, seed: int):
    import jax

    ref = importlib.import_module(f"chipbench.reference.{config['model']}")
    return ref.make_params(
        config, jax.random.PRNGKey(int(tr.seed_words(seed)[2]) & 0x7FFFFFFF))


def build_step(config: dict, batch: int, arrays: dict, idx_table, params,
               cache_root=None, log=None):
    """-> (state, train_step, infos): the program's stream step behind its
    AOT cache, and a fresh state around ``params``."""
    from flax.training import train_state
    from nerrf_tpu.compilecache import CompileCache
    from nerrf_tpu.models.stream import StreamNet
    from nerrf_tpu.train.loop import make_tx
    from nerrf_tpu.train.stream import make_stream_step

    cfg = train_config_of(config, batch)
    model = StreamNet(stream_config_of(config))
    state = train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=make_tx(cfg))
    cached = make_stream_step(model, cfg, arrays, idx_table,
                              CompileCache(root=cache_root, log=log))
    return state, cached, cached.infos


def host_norms(tree) -> np.ndarray:
    return compare.leaf_norms(tree)[1]


def diff_numbers(prog: dict, ref: dict, worst: dict | None = None) -> dict:
    """Beside `compare.compare_training`'s gaps between norms: the norm of
    the element-by-element difference, leaf by leaf, of the first clipped
    gradient (``grad``) and of the parameters' change after the three steps
    (``delta``), measured like the gaps against the reference's norm of that
    leaf or of the median leaf.  ``*_diff`` is the worst leaf's, ``*_diff_
    mean`` the mean over leaves (for the update: the leaves that move).
    With random weights a fault such as a scan that ignores document
    boundaries changes the gradient's direction and hardly its norms."""
    import jax

    out = {}
    for name, key, keep in (
            ("grad", "grad", None),
            ("update", "delta", compare.moving_leaves(ref["grad_norms"]))):
        diff = host_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, prog[key], ref[key]))
        norms = ref[f"{name}_norms"]
        out[f"{name}_diff"], leaf, out[f"{name}_diff_mean"] = \
            compare.norm_gap(norms + diff, norms, keep)
        if worst is not None and "leaves" in ref:
            worst[f"{name}_diff"] = ref["leaves"][leaf]
    return out


def compare_all(prog: dict, ref: dict, worst: dict | None = None) -> dict:
    return {**compare.compare_training(prog, ref, worst),
            **diff_numbers(prog, ref, worst)}


def follow_reference(config: dict, arrays: dict, idx_table, seed: int,
                     precision: str = "f32", fault=None):
    """The plain reference through the first steps -> {"losses", "grad"
    (the first clipped gradient), "delta" (the parameters' change), both
    on the host, their "grad_norms" and "update_norms", "leaves"}.
    ``precision`` and ``fault`` are the control and the planted fault of
    `chipbench/reference/phi4flash.py`."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"chipbench.reference.{config['model']}")
    fn = ref.make_loss_and_grad(config, precision, fault)
    params = make_weights(config, seed)
    params0 = jax.device_get(params)
    opt = ref.init_opt(params)
    losses, first_grad = [], None
    for k in range(WARMUP_STEPS):
        idx = np.asarray(idx_table[k % len(idx_table)])
        loss, grads = fn(params, jnp.asarray(arrays["tokens"][idx]),
                         jnp.asarray(arrays["segments"][idx]))
        params, opt, clipped = ref.clip_and_update(
            params, grads, opt, config["train"], fetch=first_grad is None)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = clipped
    delta = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - b, params,
                                   params0)
    names, update_norms = compare.leaf_norms(delta)
    return {"losses": losses, "grad": first_grad, "delta": delta,
            "grad_norms": host_norms(first_grad),
            "update_norms": update_norms, "leaves": names}


def program_phase(ctx, arrays, idx_table, compile_log):
    """Everything that holds the program's device state; returns host data
    only, so that the state is gone when the reference starts."""
    import jax

    from chipbench.reference import adamw

    config, cell, seed = ctx.config, ctx.cell, ctx.seed
    batch = int(cell["batch"])
    t0 = time.perf_counter()
    params = make_weights(config, seed)
    params0 = jax.device_get(params)
    state, train_step, infos = build_step(
        config, batch, arrays, idx_table, params,
        cache_root=ctx.cache_root, log=ctx.log)
    del params
    rng, _ = tr.step_keys(seed, 0)

    # the first steps, through the window's own call: the object compiled
    # here is the one the window drives
    losses, grad1 = [], None
    for k in range(WARMUP_STEPS):
        state, loss, _aux, rng = train_step(state, rng)
        losses.append(float(loss))
        if k == 0:
            # the clipped first gradient, from Adam's first moment after
            # one step: mu = (1 - b1) g
            grad1 = jax.tree_util.tree_map(
                lambda m: m / np.float32(1.0 - adamw.B1),
                tr._adam_mu(state.opt_state))
        ctx.log(f"warm-up step {k + 1}: loss {losses[-1]:.6f} "
                f"({time.perf_counter() - t0:.1f}s since weights)")
    delta = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - b,
                                   state.params, params0)
    prog = {"losses": losses, "grad": grad1, "delta": delta,
            "grad_norms": host_norms(grad1),
            "update_norms": host_norms(delta)}
    del params0

    seconds = ctx.seconds
    trace_dir = None
    if ctx.trace:
        seconds = min(seconds, float(cell["trace_seconds"]))
        trace_dir = ctx.make_trace_dir()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    window_start = time.perf_counter()
    try:
        state, rng, steps, elapsed, win_losses, dispatch = tr.timed_window(
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
    plan = tr.executable_temp_bytes(train_step)
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
            f"{steps * batch / elapsed:.4f} sequences/s; peak "
            f"{out['memory_peak_bytes'] / 1e9:.3f} GB (allocator "
            f"{allocator_peak / 1e9:.3f} + reserved {reserved / 1e9:.3f}; "
            f"the compiler's plan had {plan / 1e9:.3f} of scratch); aot "
            f"{out['aot']}; compiles in window {out['compiles_in_window']}")
    del state, train_step
    return out


def program_counters() -> dict:
    """What the program counted about its own packing (its metrics
    registry)."""
    from nerrf_tpu.observability import DEFAULT_REGISTRY

    return {name: DEFAULT_REGISTRY.value(name)
            for name in ("stream_pack_waste_fraction", "stream_tokens_total")}


def run(ctx) -> dict:
    """One run of a `stream_resident` cell -> the harness's run record."""
    # a program that lacks the stream trainer fails here, at once
    importlib.import_module("nerrf_tpu.train.stream")
    config, cell = ctx.config, ctx.cell
    batch, num_seqs = int(cell["batch"]), int(cell["num_seqs"])
    compile_log = tr.CompileLog()
    compile_log.listen()

    t = time.perf_counter()
    arrays, waste = make_sequences(config, cell)
    ctx.log(f"data: {num_seqs} packed sequences of {cell['seq_len']} from "
            f"{cell['traces']} traces in {time.perf_counter() - t:.1f}s; "
            f"packing waste {waste:.4f}")
    if batch != 1:
        raise RuntimeError("the cost-paired order is drawn for a batch of 1")
    idx_table = make_order_table(ctx.seed, int(cell["table_rows"]),
                                 sequence_costs(config, arrays["segments"]))

    rec = program_phase(ctx, arrays, idx_table, compile_log)
    gc.collect()

    t = time.perf_counter()
    ref = follow_reference(config, arrays, idx_table, ctx.seed)
    reference_s = time.perf_counter() - t
    ctx.log(f"reference: {len(ref['losses'])} steps in {reference_s:.1f}s")
    worst: dict = {}
    numbers = compare_all(rec["prog"], ref, worst)
    correct, table, not_compared = compare.verdict(numbers, cell["limits"])
    if rec["failed"] or rec["steps"] == 0:
        correct = False

    work = importlib.import_module(f"chipbench.work.{config['model']}")
    packing = work.packing_of(arrays["segments"], config["sliding_window"])
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
            "train_flops_per_window": work.train_flops(config, packing),
            "train_work_per_window": work.train_work(config, packing),
            "scope_groups": work.SCOPE_GROUPS,
            **program_counters(),
        },
        "extras": {
            "aot": rec["aot"], "reference_s": reference_s,
            "last_loss": rec["last_loss"],
            "tokens_per_s": rate * int(cell["seq_len"]),
            "pack_waste": waste, "packing": packing,
            "memory_stats": rec["memory_stats"],
            "executable_plan_temp_bytes": rec["executable_plan_temp_bytes"],
            "program_losses": rec["prog"]["losses"],
            "reference_losses": ref["losses"],
            "worst_leaf": worst, "not_compared": not_compared,
            "bytes_per_window": (rec["memory_peak_bytes"] / batch
                                 if batch else None),
        },
    }
