"""Training the stream encoder (`models/stream.py`) through the trainer's
own step: `make_tx`, `TrainState`, the resident scheduled step behind the
AOT cache (`cache_train_step`), the `train_step_call` span.

Two objectives, chosen by the encoder's configuration and nothing else:
``vocab_size`` > 0 trains the next event token over packed documents (the
pretrainer: `python -m nerrf_tpu.train.run --experiment
configs/stream-phi4-mini-flash.json`); ``vocab_size`` 0 trains the per-event
attack logit on feature streams (`benchmarks/run_stream_eval.py`,
`bench.py`'s stream leg and the dp x sp step of `parallel/train.py` use the
same loss and the same optimizer recipe).
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import numpy as np
from flax.training import train_state

import optax

from nerrf_tpu.models.stream import (DELTA_KINDS, GQA_KINDS, LATENT_KINDS,
                                     SIGMOID_ROUTED_KINDS, WINDOW_KINDS,
                                     StreamConfig, StreamNet, mtp_loss,
                                     next_token_loss, stream_loss)
from nerrf_tpu.tracing import DEFAULT_TRACER
from nerrf_tpu.train import loop


def _input_keys(scfg: StreamConfig):
    """The batch's two model inputs: tokens and segment ids (next token) or
    features and mask (per-event BCE)."""
    return ("tokens", "segments") if scfg.vocab_size else ("feat", "mask")


def _seq_len(scfg: StreamConfig, arrays: dict) -> int:
    return np.shape(arrays[_input_keys(scfg)[0]])[1]


def make_stream_loss_fn(model: StreamNet):
    """``loss_fn(params, batch, dropout_rng) -> (loss, aux)`` in the shape
    `loop._step_body` takes.  ``batch``: ``tokens``, ``segments`` (next
    token) or ``feat``, ``mask``, ``label`` (per-event BCE)."""
    scfg = model.cfg
    first, second = _input_keys(scfg)

    def loss_fn(params, batch, dropout_rng):
        out = model.apply({"params": params}, batch[first], batch[second],
                          deterministic=False, rngs={"dropout": dropout_rng})
        if not scfg.vocab_size:
            return stream_loss(out, batch["label"], batch["mask"]), {}
        loss = next_token_loss(scfg, params, out["hidden"],
                               batch["tokens"], batch["segments"])
        aux = out.get("aux", {})
        if aux:
            aux = dict(aux, token_loss=loss)
        if "index_loss" in aux:
            # the indexer's own term (`ops/dsa.py`): without it the
            # indexer's parameters would never receive a gradient
            loss = loss + scfg.index_loss_weight * aux["index_loss"]
        if "mtp_hidden" in out:
            # the token two ahead, through the multi-token-prediction
            # module and the main model's head
            extra, count = mtp_loss(scfg, params, out["mtp_hidden"],
                                    batch["tokens"], batch["segments"])
            aux.update(mtp_loss=extra, mtp_targets=count)
            loss = loss + scfg.mtp_loss_weight * extra
        return loss, aux

    return loss_fn


def count_sparse(aux: dict, scfg: StreamConfig, steps: int = 1) -> None:
    """The ``aux`` of a step with routed layers -> the program's registry:
    the routing of every such stack, the selection where the stack has an
    indexer, the multi-token-prediction term where it has that module, the
    pairs its grouped-query layers attended where it has those, the tokens
    its delta-rule layers ran and the document starts their state was
    zeroed at where it has those.  It
    floats device scalars, so the loop calls it only where it already
    syncs; ``steps`` is how many steps that sync stands for (the counters
    then assume they routed alike)."""
    from nerrf_tpu.observability import DEFAULT_REGISTRY as reg

    if "held_assignments" not in aux:
        return
    held = float(aux["held_assignments"])
    total = (float(aux["routed_tokens"]) * scfg.experts_per_token
             * scfg.routed_layers)
    help_ = "token-to-expert assignments of the routed layers, by whether " \
            "this chip holds the expert"
    reg.counter_inc("moe_assignments_total", held * steps,
                    labels={"held": "true"}, help=help_)
    reg.counter_inc("moe_assignments_total", (total - held) * steps,
                    labels={"held": "false"}, help=help_)
    reg.gauge_set("moe_expert_load_max_over_mean",
                  float(aux["load_max_over_mean"]),
                  help="largest held expert's assignments over the mean, "
                       "averaged over layers, last synced step")
    if "selected_pairs" in aux:
        chosen, allowed = (float(aux[k]) for k in ("selected_pairs",
                                                   "allowed_pairs"))
        reg.counter_inc("dsa_selected_pairs_total", chosen * steps,
                        help="query-key pairs the indexer's selection kept")
        reg.gauge_set("dsa_selected_share", chosen / max(allowed, 1.0),
                      help="selected over causal same-document pairs, last "
                           "synced step")
    if "mtp_targets" in aux:
        term = scfg.mtp_loss_weight * float(aux["mtp_loss"])
        reg.counter_inc("mtp_targets_total",
                        float(aux["mtp_targets"]) * steps,
                        help="positions that carried a target two tokens "
                             "ahead")
        reg.gauge_set("stream_mtp_loss_share",
                      term / max(float(aux["token_loss"]) + term, 1e-30),
                      help="the weighted multi-token-prediction term over "
                           "the whole loss, last synced step")
    for kind in ("window", "full"):
        if f"{kind}_pairs" in aux:
            reg.counter_inc(
                "attention_pairs_total", float(aux[f"{kind}_pairs"]) * steps,
                labels={"kind": kind},
                help="query-key pairs the grouped-query attention layers "
                     "attended (real queries), by kind: inside the window, "
                     "or the whole document")
    if "delta_tokens" in aux:
        reg.counter_inc("kda_tokens_total",
                        float(aux["delta_tokens"]) * steps,
                        help="real tokens the delta-rule layers ran, summed "
                             "over those layers")
        reg.counter_inc("kda_resets_total",
                        float(aux["delta_resets"]) * steps,
                        help="document starts at which the delta-rule "
                             "layers zeroed their state, summed over those "
                             "layers")


def make_stream_tx(cfg: loop.TrainConfig, scfg: StreamConfig):
    """The trainer's optimizer (`loop.make_tx`); for a stack with sigmoid
    routers the update of every correction bias (``router_bias``) is zeroed
    behind it, weight decay included: the bias is a buffer, held fixed (the
    rule that would move it between steps is not here)."""
    # nerrflint: ok[recompile-hazard] scfg is the model's STATIC configuration (a frozen dataclass that rides the AOT key, `stream_key_extra`), never a traced value
    if (not set(scfg.stack) & set(SIGMOID_ROUTED_KINDS)
            and not scfg.mtp_layers):
        return loop.make_tx(cfg)
    frozen = lambda tree: jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) == "router_bias", tree)
    return optax.chain(loop.make_tx(cfg),
                       optax.masked(optax.set_to_zero(), frozen))


def stream_kernel_path(scfg: StreamConfig,
                       seq_len: Optional[int] = None) -> dict:
    """Which of an op's routes a step over ``seq_len``-token sequences
    traces on this backend, for the ops of ``scfg``'s stack that have two:
    the chosen-set attention (`ops/dsa.py::attention_route`) where the stack
    has a ``dsa_moe`` layer, the latent attention core
    (`ops/mla.py::attention_route`) where the stack or its
    multi-token-prediction module has a latent layer, the same core under
    each grouped-query kind's scope where the stack has that kind, the
    selective scan (`ops/ssm.py::scan_route`) where it has a ``mamba``
    layer, and the delta rule (`ops/kda.py::delta_route`) where it has a
    ``kda_*`` layer."""
    sparse = "dsa_moe" in scfg.stack
    latent = bool(set(scfg.stack) & set(LATENT_KINDS) or scfg.mtp_layers)
    # whether each grouped-query kind of the stack attends inside a window
    grouped = {k in WINDOW_KINDS for k in scfg.stack if k in GQA_KINDS}
    mamba = "mamba" in scfg.stack
    delta = bool(set(scfg.stack) & set(DELTA_KINDS))
    if not (sparse or latent or grouped or mamba or delta):
        return {}
    if seq_len is None:
        raise ValueError("an attention core's or the scan's route depends on "
                         "the sequence length: pass seq_len")
    from nerrf_tpu.ops import dsa, kda, mla, ssm

    path = {}
    if mamba:
        path["ssm_scan"] = ssm.scan_route(seq_len, scfg.expand * scfg.dim,
                                          scfg.d_state)
    if delta:
        path["kda_core"] = kda.delta_route(seq_len, scfg.head_dim,
                                           scfg.head_dim)
    if sparse:
        path["dsa_attention"] = dsa.attention_route(
            seq_len, scfg.num_heads, scfg.num_kv_heads, scfg.head_dim)
    if latent:
        path["mla_attention"] = mla.attention_route(
            seq_len, scfg.qk_nope_dim + scfg.qk_rope_dim, scfg.v_head_dim)
    for window in sorted(grouped):
        path["gqa_window_attention" if window else "gqa_full_attention"] = \
            mla.attention_route(seq_len, scfg.head_dim, scfg.head_dim)
    return path


def stream_key_extra(scfg: StreamConfig,
                     seq_len: Optional[int] = None) -> dict:
    """AOT key material of a stream step beside the training config's: the
    encoder's configuration and the routes its ops take (an executable
    traced with one route is never served where the other would be)."""
    return {"stream_cfg": repr(scfg), **stream_kernel_path(scfg, seq_len)}


def init_stream_state(model: StreamNet, cfg: loop.TrainConfig, sample: dict,
                      rng) -> train_state.TrainState:
    """A fresh `TrainState` around ``model.init`` (one jitted call: at the
    published widths the parameters are 2.8 GB) with the trainer's
    optimizer.  ``sample``: one batch's arrays."""
    first, second = _input_keys(model.cfg)
    params = jax.jit(lambda r: model.init(
        r, sample[first], sample[second], deterministic=True)["params"])(rng)
    return train_state.TrainState.create(
        apply_fn=model.apply, params=params,
        tx=make_stream_tx(cfg, model.cfg))


def make_stream_step(model: StreamNet, cfg: loop.TrainConfig, arrays: dict,
                     idx_table: np.ndarray, compile_cache=None):
    """The resident scheduled step over ``arrays`` (uploaded here, once) ->
    ``step(state, rng) -> (state, loss, aux, rng)``, behind the AOT cache
    where one is given."""
    step = loop.make_train_step_scheduled(
        model, cfg, arrays, idx_table, loss_fn=make_stream_loss_fn(model),
        tx=make_stream_tx(cfg, model.cfg))
    if compile_cache is None:
        return loop.traced_step(step)
    return loop.cache_train_step(
        compile_cache, step, model, cfg, "stream_step_scheduled",
        extra=stream_key_extra(model.cfg, _seq_len(model.cfg, arrays)))


def train_stream(arrays: dict, scfg: StreamConfig, cfg: loop.TrainConfig,
                 log=print, compile_cache=None,
                 tokens_per_row: Optional[int] = None) -> loop.TrainResult:
    """Train the stream encoder on device-resident ``arrays`` for
    ``cfg.num_steps`` steps of ``cfg.batch_size`` rows; the schedule of rows
    is `make_idx_schedule`'s.  The loss is floated every ``cfg.eval_every``
    steps (the loop's only sync) and at the end."""
    n = len(next(iter(arrays.values())))
    model = StreamNet(scfg)
    tracer = DEFAULT_TRACER
    with tracer.span("train_setup", device=True):
        # the keys too: a process's first PRNG call compiles
        rng = jax.random.PRNGKey(cfg.seed)
        rng, init_rng = jax.random.split(rng)
        sample = {k: v[:min(cfg.batch_size, n)] for k, v in arrays.items()}
        state = init_stream_state(model, cfg, sample, init_rng)
    for op, route in stream_kernel_path(scfg, _seq_len(scfg, arrays)).items():
        log(f"kernel_path: {op}: {route}")
    with tracer.span("train_setup", device=True, phase="step_fns"):
        step = make_stream_step(model, cfg, arrays,
                                loop.make_idx_schedule(n, cfg), compile_cache)
    history = []
    t_first = None
    synced = -1
    blocked_s = 0.0
    with tracer.span("train_loop", steps=cfg.num_steps, resident=True,
                     seq_len=_seq_len(scfg, arrays)):
        for i in range(cfg.num_steps):
            state, loss, aux, rng = step(state, rng)
            if (i == 0 or (i + 1) % cfg.eval_every == 0
                    or i == cfg.num_steps - 1):
                # the loop's only sync: logged steps (eval_every)
                with tracer.span("train_step_wait", step=i) as wait:
                    history.append({"step": i, "loss": float(loss)})
                log(f"step {i}: loss {history[-1]['loss']:.4f}")
                count_sparse(aux, scfg, steps=i - synced)
                synced = i
                if t_first is None:      # step 0 holds the compile
                    t_first = time.perf_counter()
                else:
                    blocked_s += wait.dur
    elapsed = time.perf_counter() - (t_first or 0.0)
    steps_per_sec = max(cfg.num_steps - 1, 1) / max(elapsed, 1e-9)
    if cfg.num_steps > 1:
        # the steady state after step 0, as `train_nerrfnet` counts it
        loop.gauge_host_blocked(blocked_s, max(elapsed, 1e-9))
    metrics = {"final_loss": history[-1]["loss"]}
    if tokens_per_row:
        metrics["tokens_per_sec"] = (steps_per_sec * min(cfg.batch_size, n)
                                     * tokens_per_row)
    return loop.TrainResult(state=state, metrics=metrics,
                            steps_per_sec=steps_per_sec, history=history)
