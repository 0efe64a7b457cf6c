"""Traffic kind `stream_kda_resident`: the stream pretrainer's scheduled
resident step over packed event-token sequences, for a stack of channel-gated
delta-rule layers beside position-free latent attention (a leading dense
layer, then sigmoid-routed experts beside a shared one, one chip's share).

`stream_gqa_resident`'s twin, and like it made of `stream_resident`'s and
`train_resident`'s parts: the same sequences (`make_sequences`), the same
cost-paired order from ``--seed`` (`make_order_table`; a sequence's cost is
the mix's measured ``seq_cost`` where it has one, else its modelled training
FLOPs, which its tokens and the latent core's attending pairs set:
`sequence_costs`), weights under the program's names (`make_weights` ->
`chipbench.reference.kimilinear.make_params`) from the mix's
``weights_seed`` where it fixes one (`stream_sparse_resident.
weights_seed_of`), the same state and step as `python -m
nerrf_tpu.train.run` builds for a stream experiment
(`nerrf_tpu.train.stream.make_stream_step` behind its AOT cache, the
optimizer `make_stream_tx`'s), the same three warm-up steps through the
window's own object, the same window (`train_resident.timed_window`), the
same reference walk and comparison (`follow_reference`, `compare_all`,
`compare.verdict`).  What differs, and why this is a file of its own
(nothing the benchmark has may be edited):

* `stream_config_of` reads this family's keys (``linear_attn_config``'s
  layer lists, heads, head size and convolution width, the
  ``published_layers`` the cut keeps, ``first_k_dense_replace``,
  ``mla_use_nope``, ``q_lora_rank`` null, ``num_experts`` beside
  ``router_experts``, ``routed_scaling_factor``, ``num_shared_experts``);
* the warm-up steps hand their ``aux`` to the program's own `count_sparse`,
  so the run's counters hold ``moe_assignments_total{held}``,
  ``moe_expert_load_max_over_mean``, ``kda_tokens_total`` and
  ``kda_resets_total``; the counted assignments enter the required work,
  and the counted tokens and resets are held equal to what
  `chipbench/work/kimilinear.py::packing_of` counts on the same sequences
  (`check_counts`; the required work's are the resident sequences'
  average, which every seed trains equally often).

Parameters of a mix and of a cell: as `stream_resident`'s.
"""

from __future__ import annotations

import gc
import importlib
import math
import time

import numpy as np

from chipbench import compare
from chipbench.traffic import stream_resident as sr
from chipbench.traffic import stream_sparse_resident as ssr
from chipbench.traffic import train_resident as tr

WARMUP_STEPS = tr.WARMUP_STEPS
COUNTERS = ("stream_pack_waste_fraction", "stream_tokens_total",
            "moe_expert_load_max_over_mean", "kda_tokens_total",
            "kda_resets_total")


def kinds_of(config: dict) -> list:
    """The program's layer kinds of the published layers the cut keeps."""
    lin = config["linear_attn_config"]
    out = []
    for n in config["published_layers"]:
        if n in lin["kda_layers"]:
            mixer = "kda"
        elif n in lin["full_attn_layers"]:
            mixer = "mla"
        else:
            raise RuntimeError(f"layer {n} is in neither of "
                               "linear_attn_config's lists")
        dense = n <= config["first_k_dense_replace"]
        out.append(f"{mixer}_{'dense' if dense else 'moe'}")
    return out


def stream_config_of(config: dict):
    """The benchmark's configuration file -> the program's `StreamConfig`:
    the published keys under the program's names.  The program's
    delta-rule layers read the latent layers' head count and the gates'
    rank from the head size: a configuration where they differ is refused."""
    from nerrf_tpu.config import from_dict
    from nerrf_tpu.models.stream import StreamConfig

    lin = config["linear_attn_config"]
    if (lin["num_heads"] != config["num_attention_heads"]
            or config["assumed"]["gate_rank"]["value"] != lin["head_dim"]):
        raise RuntimeError("the delta-rule layers' heads must be the latent "
                           "layers' and the gates' rank their head size")
    return from_dict(StreamConfig, {
        "dim": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "head_dim": lin["head_dim"],
        "num_layers": config["num_hidden_layers"], "kinds": kinds_of(config),
        "vocab_size": config["vocab_size"], "dropout": 0.0,
        "dtype": config["dtype"],
        "mlp_dim": config["intermediate_size"],
        "rope_theta": float(config["rope_theta"]),
        "q_lora_rank": config["q_lora_rank"] or 0,
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_dim": config["qk_nope_head_dim"],
        "qk_rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "mla_rope": not config["mla_use_nope"],
        "d_conv": lin["short_conv_kernel_size"],
        "num_experts": config["router_experts"],
        "experts_per_token": config["num_experts_per_token"],
        "expert_dim": config["moe_intermediate_size"],
        "first_expert": config["first_expert"],
        "held_experts": config["num_experts"],
        "router_scale": config["routed_scaling_factor"],
        "shared_dim": (config["moe_intermediate_size"]
                       * config["num_shared_experts"]),
        "rms_eps": config["rms_norm_eps"],
        "tie_head": config["tie_word_embeddings"]})


def sequence_costs(config: dict, cell: dict, segments) -> list:
    """What orders the resident sequences into cost pairs: the mix's
    ``seq_cost`` where it has one, else each sequence's modelled training
    FLOPs (`chipbench/work/kimilinear.py`: its real tokens and the latent
    core's attending pairs)."""
    if cell.get("seq_cost"):
        if len(cell["seq_cost"]) != len(segments):
            raise RuntimeError("the mix's seq_cost does not name every "
                               "resident sequence")
        return [float(c) for c in cell["seq_cost"]]
    work = importlib.import_module(f"chipbench.work.{config['model']}")
    return [work.train_flops(config, work.packing_of(row[None]))["total"]
            for row in np.asarray(segments)]


def build_step(config: dict, batch: int, arrays: dict, idx_table, params,
               cache_root=None, log=None):
    """-> (state, train_step, infos, the model's `StreamConfig`)."""
    from flax.training import train_state
    from nerrf_tpu.compilecache import CompileCache
    from nerrf_tpu.models.stream import StreamNet
    from nerrf_tpu.train.stream import make_stream_step, make_stream_tx

    cfg = sr.train_config_of(config, batch)
    scfg = stream_config_of(config)
    model = StreamNet(scfg)
    state = train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=make_stream_tx(cfg, scfg))
    cached = make_stream_step(model, cfg, arrays, idx_table,
                              CompileCache(root=cache_root, log=log))
    return state, cached, cached.infos, scfg


def program_phase(ctx, arrays, idx_table, compile_log):
    """Everything that holds the program's device state; returns host data
    only, so that the state is gone when the reference starts."""
    import jax

    from chipbench.reference import adamw
    from nerrf_tpu.train.stream import count_sparse

    config, cell, seed = ctx.config, ctx.cell, ctx.seed
    batch = int(cell["batch"])
    t0 = time.perf_counter()
    params = sr.make_weights(config, ssr.weights_seed_of(cell, seed))
    params0 = jax.device_get(params)
    state, train_step, infos, scfg = build_step(
        config, batch, arrays, idx_table, params,
        cache_root=ctx.cache_root, log=ctx.log)
    del params
    rng, _ = tr.step_keys(seed, 0)

    losses, grad1, routed = [], None, []
    for k in range(WARMUP_STEPS):
        state, loss, aux, rng = train_step(state, rng)
        losses.append(float(loss))
        # the step's own counts, where this loop syncs anyway
        count_sparse(aux, scfg)
        routed.append({name: float(v) for name, v in aux.items()})
        if k == 0:
            grad1 = jax.tree_util.tree_map(
                lambda m: m / np.float32(1.0 - adamw.B1),
                tr._adam_mu(state.opt_state))
        ctx.log(f"warm-up step {k + 1}: loss {losses[-1]:.6f}; the delta "
                f"rule ran {routed[-1]['delta_tokens']:.0f} tokens and "
                f"zeroed its state at {routed[-1]['delta_resets']:.0f} "
                f"starts ({time.perf_counter() - t0:.1f}s since weights)")
    delta = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - b,
                                   state.params, params0)
    prog = {"losses": losses, "grad": grad1, "delta": delta,
            "grad_norms": sr.host_norms(grad1),
            "update_norms": sr.host_norms(delta)}
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
        "prog": prog, "steps": steps, "elapsed": elapsed, "routed": routed,
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
    """What the program counted about its packing, its routing and its
    delta-rule layers (its metrics registry)."""
    from nerrf_tpu.observability import DEFAULT_REGISTRY as reg

    out = {name: reg.value(name) for name in COUNTERS}
    for held in ("true", "false"):
        out[f"moe_assignments_total.held_{held}"] = reg.value(
            "moe_assignments_total", labels={"held": held})
    return out


def check_counts(config: dict, arrays: dict, idx_table, routed) -> None:
    """The tokens the program's delta-rule layers ran and the starts they
    zeroed their state at in the warm-up steps (its ``aux``) against
    `packing_of`'s count on the same sequences: the required work's are that
    count's, so it has to describe what the program does."""
    work = importlib.import_module(f"chipbench.work.{config['model']}")
    layers = sum(k.startswith("kda") for k in kinds_of(config))
    for k, counts in enumerate(routed):
        rows = np.asarray(idx_table[k % len(idx_table)]).ravel()
        got = work.packing_of(arrays["segments"][rows])
        for name, key in (("delta_tokens", "tokens"),
                          ("delta_resets", "docs")):
            want = got[key] * len(rows) * layers
            if abs(counts[name] - want) > 1e-6 * want:
                raise RuntimeError(
                    f"warm-up step {k + 1}: the program counted "
                    f"{counts[name]:.0f} {name}, the required work counts "
                    f"{want:.0f}")


def run(ctx) -> dict:
    """One run of a `stream_kda_resident` cell -> the harness's run
    record."""
    from nerrf_tpu.models import stream

    # a program that lacks the delta-rule kinds fails here, at once
    if "kda_moe" not in getattr(stream, "DELTA_KINDS", ()):
        raise RuntimeError("the program has no delta-rule layer kinds")
    config, cell = ctx.config, ctx.cell
    batch, num_seqs = int(cell["batch"]), int(cell["num_seqs"])
    compile_log = tr.CompileLog()
    compile_log.listen()

    t = time.perf_counter()
    arrays, waste = sr.make_sequences(config, cell)
    ctx.log(f"data: {num_seqs} packed sequences of {cell['seq_len']} from "
            f"{cell['traces']} traces in {time.perf_counter() - t:.1f}s; "
            f"packing waste {waste:.4f}")
    if batch != 1:
        raise RuntimeError("the cost-paired order is drawn for a batch of 1")
    idx_table = sr.make_order_table(
        ctx.seed, int(cell["table_rows"]),
        sequence_costs(config, cell, arrays["segments"]))
    weights_seed = ssr.weights_seed_of(cell, ctx.seed)

    rec = program_phase(ctx, arrays, idx_table, compile_log)
    gc.collect()
    check_counts(config, arrays, idx_table, rec["routed"])

    t = time.perf_counter()
    ref = sr.follow_reference(config, arrays, idx_table, weights_seed)
    reference_s = time.perf_counter() - t
    ctx.log(f"reference: {len(ref['losses'])} steps in {reference_s:.1f}s")
    worst: dict = {}
    numbers = sr.compare_all(rec["prog"], ref, worst)
    correct, table, not_compared = compare.verdict(numbers, cell["limits"])
    if rec["failed"] or rec["steps"] == 0:
        correct = False
    reference_losses = list(ref["losses"])
    del ref
    gc.collect()

    work = importlib.import_module(f"chipbench.work.{config['model']}")
    scfg = stream_config_of(config)
    packing = work.packing_of(arrays["segments"])
    # the experts' required work follows what the router sent them: the
    # warm-up steps' own count, per routed layer and sequence
    packing["assignments"] = float(np.mean(
        [r["held_assignments"] for r in rec["routed"]])) / (
            scfg.routed_layers * batch)
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
            "routed": rec["routed"],
            "memory_stats": rec["memory_stats"],
            "executable_plan_temp_bytes": rec["executable_plan_temp_bytes"],
            "program_losses": rec["prog"]["losses"],
            "reference_losses": reference_losses,
            "worst_leaf": worst, "not_compared": not_compared,
            "bytes_per_window": (rec["memory_peak_bytes"] / batch
                                 if batch else None),
        },
    }
