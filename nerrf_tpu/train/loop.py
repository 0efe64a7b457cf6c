"""Training loop for NerrfNet (the reference's planned `ai/train.py`).

Pure-JAX training: one jitted `train_step` (donated state, bfloat16 compute,
adamw + cosine schedule), vmapped model over the window batch.  The same step
function is reused by `nerrf_tpu.parallel` under a device mesh — there the
batch axis is sharded and XLA inserts the gradient all-reduce over ICI,
replacing the reference north star's DDP/NCCL design.

Objective = masked, class-rebalanced BCE on edge logits (the GNN's
edge-anomaly task, `architecture.mdx:49-53`) + node BCE (aux) + sequence BCE
(the LSTM task, `architecture.mdx:55-59`) — the "joint loss" of
`ROADMAP.md:68`.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from functools import partial
from pathlib import Path
from typing import Any, Dict, Optional

_T_IMPORT = time.perf_counter()   # for the `module_import` span below

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from nerrf_tpu.utils import sync_result  # noqa: E402
import optax  # noqa: E402
from flax.training import train_state  # noqa: E402

from nerrf_tpu.models.joint import JointConfig, NerrfNet  # noqa: E402
from nerrf_tpu.observability import DEFAULT_REGISTRY  # noqa: E402
from nerrf_tpu.tracing import DEFAULT_TRACER  # noqa: E402
from nerrf_tpu.train.data import (  # noqa: E402
    WindowDataset, padding_waste_fractions)
from nerrf_tpu.train.metrics import best_f1, roc_auc  # noqa: E402

# what this module brought in after the tracer's epoch (jax, flax and optax
# where nothing had imported them yet: seconds of `train.run`'s start)
DEFAULT_TRACER.record("module_import", time.perf_counter() - _T_IMPORT,
                      module=__name__)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    model: JointConfig = JointConfig()
    batch_size: int = 8
    num_steps: int = 500
    learning_rate: float = 2e-3
    warmup_steps: int = 50
    weight_decay: float = 1e-4
    edge_loss_weight: float = 1.0
    node_loss_weight: float = 0.3
    seq_loss_weight: float = 1.0
    pos_weight: float = 8.0  # attack classes are rare
    seed: int = 0
    eval_every: int = 100
    # in-step health telemetry (trainwatch): grad/param/update norms +
    # per-component nonfinite flags computed INSIDE the jitted step and
    # returned alongside the loss.  Changes the lowered program and its
    # output treedef, so it rides the compile-cache key (step_key_extra
    # carries repr(cfg) AND an explicit "telemetry" axis)
    telemetry: bool = False


@dataclasses.dataclass
class TrainResult:
    state: Any
    metrics: Dict[str, float]
    steps_per_sec: float
    history: list


_MODEL_INPUTS = (
    "node_feat", "node_type", "node_aux", "node_mask", "edge_src", "edge_dst",
    "edge_feat", "edge_mask", "seq_feat", "seq_mask", "seq_node_idx",
)


def model_inputs(batch: Dict[str, jnp.ndarray]) -> tuple:
    return tuple(batch[k] for k in _MODEL_INPUTS)


def _weighted_bce(logit, label, mask, pos_weight):
    """Masked BCE-with-logits, positives upweighted."""
    log_p = jax.nn.log_sigmoid(logit)
    log_np = jax.nn.log_sigmoid(-logit)
    loss = -(pos_weight * label * log_p + (1.0 - label) * log_np)
    return (loss * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def make_loss_fn(model: NerrfNet, cfg: TrainConfig):
    def loss_fn(params, batch, dropout_rng):
        out = jax.vmap(
            lambda *args: model.apply(
                {"params": params}, *args, deterministic=False,
                rngs={"dropout": dropout_rng},
            )
        )(*model_inputs(batch))
        with jax.named_scope("loss"):
            e_mask = batch["edge_mask"].astype(jnp.float32)
            n_mask = batch["node_mask"].astype(jnp.float32)
            s_mask = batch["seq_valid"].astype(jnp.float32)
            edge_loss = _weighted_bce(out["edge_logit"], batch["edge_label"], e_mask, cfg.pos_weight)
            node_loss = _weighted_bce(out["node_logit"], batch["node_label"], n_mask, cfg.pos_weight)
            seq_loss = _weighted_bce(out["seq_logit"], batch["seq_label"], s_mask, cfg.pos_weight)
            total = (
                cfg.edge_loss_weight * edge_loss
                + cfg.node_loss_weight * node_loss
                + cfg.seq_loss_weight * seq_loss
            )
        return total, {"edge_loss": edge_loss, "node_loss": node_loss, "seq_loss": seq_loss}

    return loss_fn


def _step_body(loss_fn, state: train_state.TrainState, batch, rng,
               telemetry: bool = False):
    """The one grad/update body shared by every batching strategy.

    ``telemetry`` (static at trace time — `TrainConfig.telemetry`) adds
    the in-step health scalars (trainwatch/telemetry.py) to ``aux`` under
    the reserved ``"telemetry"`` key: same program outputs carry the
    grad/param/update norms and nonfinite flags, so the host reads them
    at the sync points it already pays — zero extra device round trips."""
    rng, dropout_rng = jax.random.split(rng)
    (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        state.params, batch, dropout_rng
    )
    with jax.named_scope("optimizer_update"):
        new_state = state.apply_gradients(grads=grads)
    # nerrflint: ok[recompile-hazard] telemetry is STATIC configuration (a Python bool bound by partial/closure from TrainConfig.telemetry, never a traced value) and the axis rides the compile-cache key (step_key_extra)
    if telemetry:
        from nerrf_tpu.trainwatch.telemetry import step_telemetry

        aux = dict(aux, telemetry=step_telemetry(
            state.params, new_state.params, grads, loss, aux))
    return new_state, loss, aux, rng


def make_train_step(model: NerrfNet, cfg: TrainConfig):
    loss_fn = make_loss_fn(model, cfg)

    @partial(jax.jit, donate_argnums=(0,))
    def train_step(state: train_state.TrainState, batch, rng):
        return _step_body(loss_fn, state, batch, rng,
                          telemetry=cfg.telemetry)

    return train_step


def make_flat_step(model: NerrfNet, cfg: TrainConfig, body, tx=None,
                   **jit_kwargs):
    """Jit ``body(state, *rest) -> (state, loss, aux, rng)`` behind a
    SERIALIZABLE pytree boundary: (params, opt_state, step, *rest) in,
    ((params, opt_state, step), loss, aux, rng) out.

    The persistent compile cache (nerrf_tpu/compilecache) serializes an
    executable's in/out treedefs next to the XLA payload, and a reloaded
    executable only accepts calls whose arg treedef compares EQUAL to the
    stored one.  `TrainState`'s treedef carries ``apply_fn``/``tx`` as
    static aux data — closures that neither pickle nor compare equal
    across processes — so a TrainState-shaped program can never be AOT-
    cached.  Flattening the boundary to plain dicts/namedtuples of arrays
    (optax states are module-level NamedTuples) makes the treedefs both
    picklable and process-stable; the TrainState wrapper is rebuilt
    INSIDE the traced function, where it costs nothing.

    ``jit_kwargs`` extend the jit decoration (the sharded twin in
    parallel/train.py passes in/out_shardings over the FLAT slots) so the
    boundary contract lives in exactly one body.  ``tx`` is the optimizer of
    the state the step will be called with, where that is not `make_tx`'s
    (`train/stream.py::make_stream_tx`)."""
    tx = tx or make_tx(cfg)

    @partial(jax.jit, donate_argnums=(0, 1), **jit_kwargs)
    def flat_step(params, opt_state, step_no, *rest):
        state = train_state.TrainState(
            step=step_no, apply_fn=model.apply, params=params, tx=tx,
            opt_state=opt_state)
        state, loss, aux, rng = body(state, *rest)
        return (state.params, state.opt_state, state.step), loss, aux, rng

    return flat_step


def traced_step(step_fn):
    """``step_fn`` behind the ``train_step_call`` span: the one place every
    train step's host call is timed, cached (`CachedTrainStep`) or not.
    The span holds the program's own Python and the runtime's call, never a
    wait for the device; ``call`` counts this object's calls from 0 and
    travels into a profiler session with the annotation (``device=True``),
    where it names the execution the call started."""
    calls = itertools.count()

    def call(*args):
        with DEFAULT_TRACER.span("train_step_call", device=True,
                                 call=next(calls)):
            return step_fn(*args)

    return call


class CachedTrainStep:
    """A TrainState-in/TrainState-out train step resolved through the
    persistent compile cache.

    Wraps a `make_flat_step` program in a `compilecache.StepCache` (one
    resolution per argument-shape signature: deserialize on a cache hit,
    compile+persist on a miss, live jit on total failure) and converts
    state↔flat at the boundary — callers keep the exact signature of the
    jit step they replaced.  The returned state is ``state.replace(...)``
    of the caller's own TrainState, so the live ``apply_fn``/``tx``
    objects flow through untouched (nothing reconstructed from a cache
    entry ever leaks into caller state)."""

    def __init__(self, cache, flat_fn, program: str, extra=None,
                 tail: tuple = ()) -> None:
        from nerrf_tpu.compilecache import StepCache

        self._sc = StepCache(cache, flat_fn, program=program, extra=extra,
                             tail=tail)
        self._call = traced_step(self._step)

    @property
    def infos(self):
        """Every resolution's CompileInfo (provenance for benches/tests)."""
        return self._sc.infos

    def __call__(self, state, *rest):
        return self._call(state, *rest)

    def _step(self, state, *rest):
        # a fresh TrainState carries step as a Python int; the program's
        # output carries it as an int32 array — pin the boundary dtype so
        # step 0 and step N resolve to the SAME executable signature
        step_no = jnp.asarray(state.step, jnp.int32)
        (params, opt_state, step_no), loss, aux, rng = self._sc(
            state.params, state.opt_state, step_no, *rest)
        return (state.replace(params=params, opt_state=opt_state,
                              step=step_no), loss, aux, rng)


def make_flat_train_step(model: NerrfNet, cfg: TrainConfig):
    """The cacheable twin of `make_train_step`: same grad/update body, flat
    (params, opt_state, step, batch, rng) boundary — see `make_flat_step`."""
    loss_fn = make_loss_fn(model, cfg)
    return make_flat_step(
        model, cfg, partial(_step_body, loss_fn, telemetry=cfg.telemetry))


def cache_train_step(compile_cache, train_step, model: NerrfNet,
                     cfg: TrainConfig, resident_flavor: str,
                     extra: Optional[dict] = None):
    """Route a (batch, rng)-shaped train step through the persistent
    compile cache — the ONE wiring point for every loop that swaps its
    jitted step for a `CachedTrainStep` (a key-material change here
    changes every flavor at once, instead of silently missing one).
    Resident steps expose their cacheable twin as ``flat_jit_fn`` with the
    device-resident arrays as the bound ``tail``; plain steps get a fresh
    `make_flat_train_step`.  ``resident_flavor`` names the resident
    program in the cache key (scheduled vs by-idx lower different HLO);
    ``extra`` is key material the training config does not hold (the stream
    encoder's own configuration)."""
    flat = getattr(train_step, "flat_jit_fn", None)
    if flat is not None:
        return CachedTrainStep(
            compile_cache, flat, program="train_step",
            extra={**step_key_extra(cfg, resident_flavor), **(extra or {})},
            tail=train_step.tail)
    return CachedTrainStep(
        compile_cache, make_flat_train_step(model, cfg),
        program="train_step", extra=step_key_extra(cfg, "train_step"))


def make_train_step_resident(model: NerrfNet, cfg: TrainConfig, arrays):
    """Train step over an HBM-resident dataset: the full window arrays are
    device_put once and passed as jit *parameters* (closure capture would
    fold them into the HLO as constants and blow up compile time); each step
    gathers its batch on device, so per-step host→device traffic is just the
    [batch] index vector — on TPU this removes the transfer of ~MBs of
    padded windows from the critical path."""
    step, _, _ = _make_resident_steps(model, cfg, arrays)
    return step


def device_put_chunked(arrays, max_bytes: int = 64 << 20, block: bool = False,
                       log=None):
    """device_put a dict of host arrays in bounded-size pieces.

    Every dataset-sized upload goes through this helper: arrays larger
    than ``max_bytes`` are sliced along axis 0 and reassembled on device,
    so no single transfer is dataset-sized.  Since transfers and the
    concatenates that free the pieces dispatch async, the worst-case
    transient is one extra copy of the input until the queued concatenates
    execute.  ``block=True`` waits and (with ``log``) reports throughput;
    leave it False where the upload should overlap other work.
    """
    out = {}
    t0 = time.perf_counter()
    total = 0
    # the span is the host's part of the upload (the transfers are async
    # unless block=True, whose barrier lies outside it)
    with DEFAULT_TRACER.span("dataset_upload", device=True) as sp:
        for k, v in arrays.items():
            v = np.asarray(v)
            nbytes = v.nbytes
            total += nbytes
            if nbytes <= max_bytes or v.shape[0] < 2:
                out[k] = jax.device_put(v)
            else:
                rows = max(1, int(v.shape[0] * max_bytes // nbytes))
                if log and rows == 1 and nbytes > max_bytes * v.shape[0]:
                    log(f"upload warning: single rows of '{k}' exceed the "
                        f"{max_bytes >> 20} MB chunk bound "
                        f"({nbytes // v.shape[0] >> 20} MB/row) — transfers "
                        "stay monolithic per row")
                pieces = [jax.device_put(v[i:i + rows])
                          for i in range(0, v.shape[0], rows)]
                out[k] = jnp.concatenate(pieces, axis=0)
        sp.args["bytes"] = total
    if block:
        # per-array barrier: the uploads are independent transfers, so
        # syncing one leaf would not prove the others landed — fetch a
        # scalar carved from each (one cheap round trip per array)
        for v in out.values():
            # nerrflint: ok[sync-in-hot-loop] upload barrier (block=True):
            np.asarray(jax.device_get(v[(0,) * v.ndim]))  # prove each landed
        if log:
            dt = time.perf_counter() - t0
            log(f"upload: {total / 1e9:.2f} GB in {dt:.1f}s "
                f"({total / 1e9 / max(dt, 1e-9):.2f} GB/s)")
    return out


def make_train_step_scheduled(model: NerrfNet, cfg: TrainConfig, arrays,
                              idx_table: np.ndarray, loss_fn=None, tx=None):
    """Fully device-driven training: the HBM-resident dataset *and* the whole
    batch-index schedule live on device, and each step picks its row with
    ``state.step`` — so a step issues zero host→device transfers and back-to-
    back steps pipeline instead of syncing on per-step input uploads.
    ``idx_table`` is [num_steps, batch] int32.  ``loss_fn(params, batch,
    dropout_rng) -> (loss, aux)`` replaces NerrfNet's joint loss (the stream
    encoder trains through this same step: `train/stream.py`, with ``tx``
    where its optimizer is not `make_tx`'s).  One ``step_build`` span: the
    `dataset_upload` it causes (its child) and the jit wrappers; nothing is
    traced or compiled here."""
    with DEFAULT_TRACER.span("step_build", device=True,
                             flavor="scheduled"):
        _, make_scheduled, _ = _make_resident_steps(model, cfg, arrays,
                                                    loss_fn, tx)
        return make_scheduled(idx_table)


def make_train_superstep(model: NerrfNet, cfg: TrainConfig, arrays,
                         idx_table: np.ndarray, steps_per_call: int):
    """K scheduled steps per XLA program — see ``make_super`` in
    ``_make_resident_steps``.  The benchmark of record times this flavor:
    no host dispatch sits between the K steps, so the timed quantity is the
    chip and not the per-call overhead of the host loop."""
    _, _, make_super = _make_resident_steps(model, cfg, arrays)
    return make_super(idx_table, steps_per_call)


def _make_resident_steps(model: NerrfNet, cfg: TrainConfig, arrays,
                         loss_fn=None, tx=None):
    """One factory for both resident flavors, sharing placement, the gather,
    and the step body (so fixes to any of them apply to both)."""
    loss_fn = loss_fn or make_loss_fn(model, cfg)
    # async: the chunked upload overlaps the caller's jit tracing/compile
    dev = device_put_chunked(arrays)

    def gathered_step(state, idx, rng, data):
        with jax.named_scope("batch_gather"):
            batch = {k: jnp.take(v, idx, axis=0) for k, v in data.items()}
        return _step_body(loss_fn, state, batch, rng,
                          telemetry=cfg.telemetry)

    @partial(jax.jit, donate_argnums=(0,))
    def step_by_idx(state: train_state.TrainState, idx, rng, data):
        return gathered_step(state, idx, rng, data)

    def scheduled_body(state, rng, data, sched):
        idx = jnp.take(sched, state.step % sched.shape[0], axis=0)
        return gathered_step(state, idx, rng, data)

    step_by_schedule = jax.jit(scheduled_body, donate_argnums=(0,))

    def resident(state, idx, rng):
        return step_by_idx(state, idx, rng, dev)

    # the cacheable twin (see make_flat_step): dev stays a jit *parameter*
    # there too, bound as the StepCache tail
    resident.flat_jit_fn = make_flat_step(model, cfg, gathered_step, tx)
    resident.tail = (dev,)
    flat_by_schedule = make_flat_step(model, cfg, scheduled_body, tx)

    def make_scheduled(idx_table):
        table = jax.device_put(np.asarray(idx_table, np.int32))
        fn = lambda state, rng: step_by_schedule(state, rng, dev, table)
        # expose AOT lowering so the bench can cost-analyze the real HLO
        fn.lower = lambda state, rng: step_by_schedule.lower(state, rng, dev, table)
        # ... and the flat cacheable twin + bound tail so train_nerrfnet
        # can route the step through the persistent compile cache
        # (CachedTrainStep — dev/table stay jit *parameters* there too)
        fn.flat_jit_fn = flat_by_schedule
        fn.tail = (dev, table)
        return fn

    def make_super(idx_table, steps_per_call):
        """K schedule-driven steps per XLA program (``lax.scan`` over the
        step body): one host call, K steps, no per-call dispatch between
        them — returns (state, losses[K], rng)."""
        table = jax.device_put(np.asarray(idx_table, np.int32))

        @partial(jax.jit, donate_argnums=(0,), static_argnames=("k",))
        def superstep(state, rng, data, sched, k):
            def body(carry, _):
                st, r = carry
                idx = jnp.take(sched, st.step % sched.shape[0], axis=0)
                st, loss, _aux, r = gathered_step(st, idx, r, data)
                return (st, r), loss

            (state, rng), losses = jax.lax.scan(
                body, (state, rng), None, length=k)
            return state, losses, rng

        fn = lambda state, rng: superstep(state, rng, dev, table,
                                          k=steps_per_call)
        fn.lower = lambda state, rng: superstep.lower(state, rng, dev, table,
                                                      k=steps_per_call)
        return fn

    return resident, make_scheduled, make_super


def step_key_extra(cfg: TrainConfig, flavor: str) -> dict:
    """Caller-side compile-cache key material for a train-step program: the
    full training config (model architecture AND optimizer/loss
    hyperparameters — learning-rate schedule, loss weights, pos_weight all
    constant-fold into the HLO), the routes the backend gives the ops
    (`ops.segment.active_impls`), and the donation spec — every axis beyond
    the argument avals that changes the lowered program.  Conservative by construction: a config change that
    would NOT change the HLO still misses (one extra compile), but a stale
    executable can never be reused."""
    from nerrf_tpu.ops.segment import active_impls

    return {
        "kind": flavor,
        "train_cfg": repr(cfg),
        "ops": repr(sorted(active_impls().items())),
        "donate": "(params,opt_state)",
        # explicit (already inside repr(cfg), but this axis changes the
        # program's OUTPUT TREEDEF too — a deserialized executable only
        # accepts equal treedefs, so the key must never collapse it)
        "telemetry": "on" if cfg.telemetry else "off",
    }


def make_idx_schedule(n: int, cfg: TrainConfig) -> np.ndarray:
    """The deterministic batch schedule train_nerrfnet follows: row `step` is
    the same draw the streaming loop would make at that step."""
    order = np.random.default_rng(cfg.seed)
    size = min(cfg.batch_size, n)
    return np.stack([
        order.choice(n, size=size, replace=False)
        for _ in range(cfg.num_steps)
    ])


# Datasets larger than this stream batches from host instead of living in
# device memory (override: NERRF_RESIDENT_MAX_BYTES).
RESIDENT_MAX_BYTES = 2 << 30

# Bounded in-memory loss history: a long soak logging every eval_every
# steps must not grow a list for the life of the run.  Callers that need
# the complete trajectory (tests, offline analysis) pass
# ``full_history=True``; everyone else gets the newest HISTORY_LIMIT
# entries (TrainResult.history stays a plain list either way).
HISTORY_LIMIT = 512


def gauge_host_blocked(blocked_s: float, elapsed: float) -> None:
    """``train_host_blocked_fraction``: the `train_step_wait` seconds of a
    loop's steady state (after step 0) over that steady state's wall."""
    DEFAULT_REGISTRY.gauge_set(
        "train_host_blocked_fraction", blocked_s / elapsed,
        help="fraction of steady-state train wall spent blocked on "
             "device results (train_step_wait spans: the syncs the "
             "loop has anyway)")


def _history(full_history: bool) -> deque:
    return deque(maxlen=None if full_history else HISTORY_LIMIT)


def _history_entry(step: int, loss, aux) -> dict:
    """One logged-step history entry.  Floats the loss (the loop's one
    existing host sync point) and, when the step carries in-step
    telemetry, the headline health scalars with it — same sync, no extra
    device round trip."""
    entry = {"step": step, "loss": float(loss)}
    tel = aux.get("telemetry") if isinstance(aux, dict) else None
    if tel is not None:
        entry["grad_norm"] = float(tel["grad_norm"])
        entry["update_ratio"] = float(tel["update_ratio"])
    return entry


def _loss_components(aux) -> Dict[str, float]:
    return {k: float(v) for k, v in aux.items() if k != "telemetry"}


def _telemetry_floats(aux) -> Optional[dict]:
    tel = aux.get("telemetry") if isinstance(aux, dict) else None
    if tel is None:
        return None
    return {
        "grad_norm": float(tel["grad_norm"]),
        "param_norm": float(tel["param_norm"]),
        "update_norm": float(tel["update_norm"]),
        "update_ratio": float(tel["update_ratio"]),
        "nonfinite": {k: float(v) for k, v in tel["nonfinite"].items()},
    }


def _dataset_bytes(arrays) -> int:
    return sum(int(v.nbytes) for v in arrays.values())


def _fits_resident(arrays) -> bool:
    import os

    limit = int(os.environ.get("NERRF_RESIDENT_MAX_BYTES", RESIDENT_MAX_BYTES))
    return _dataset_bytes(arrays) <= limit


def make_eval_fn(model: NerrfNet):
    @jax.jit
    def eval_fn(params, batch):
        return jax.vmap(
            lambda *args: model.apply({"params": params}, *args, deterministic=True)
        )(*model_inputs(batch))

    # indexed variant for device-resident evaluation; an attribute (not a
    # global cache) so the compiled executable's lifetime is the eval_fn's
    @jax.jit
    def indexed(params, idx, data):
        batch = {k: jnp.take(v, idx, axis=0) for k, v in data.items()}
        return eval_fn(params, batch)

    eval_fn.indexed = indexed
    return eval_fn


def make_tx(cfg: TrainConfig) -> optax.GradientTransformation:
    """The one optimizer recipe, shared by single-device and sharded paths."""
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, cfg.learning_rate, cfg.warmup_steps, max(cfg.num_steps, cfg.warmup_steps + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(schedule, weight_decay=cfg.weight_decay),
    )


def init_state(
    model: NerrfNet, cfg: TrainConfig, sample: Dict[str, np.ndarray], rng
) -> train_state.TrainState:
    one = {k: jnp.asarray(v[0]) for k, v in sample.items()}
    params = model.init(rng, *model_inputs(one), deterministic=True)["params"]
    return train_state.TrainState.create(
        apply_fn=model.apply, params=params, tx=make_tx(cfg)
    )


def evaluate(eval_fn, params, ds: WindowDataset, batch_size: int = 8,
             resident: Optional[bool] = None) -> Dict[str, float]:
    """Masked metrics over a dataset.

    ``resident`` uploads the model-input arrays to the device once
    (chunked) and drives batches by index — one compile, no per-batch
    host→device transfer (the 100 h run's held-out split is ~300
    batches), so this defaults on for accelerator backends; the
    host-slicing path remains for CPU and tiny sets.
    """
    with DEFAULT_TRACER.span("eval", device=True, samples=len(ds)):
        return _evaluate(eval_fn, params, ds, batch_size, resident)


def _evaluate(eval_fn, params, ds: WindowDataset, batch_size: int = 8,
              resident: Optional[bool] = None) -> Dict[str, float]:
    n = len(ds)
    if resident is None:
        resident = (jax.default_backend() not in ("cpu",)
                    and n > 4 * batch_size
                    and _fits_resident(ds.arrays))
    dev_data = None
    eval_idx = None
    if resident:
        # cache the device copy on the dataset object: periodic mid-training
        # eval would otherwise repeat a multi-GB chunked upload per call
        # (r2 advisor finding); invalidate if the arrays dict is replaced
        cached = getattr(ds, "_resident_cache", None)
        if cached is not None and cached[0] is ds.arrays:
            dev_data = cached[1]
        else:
            dev_data = device_put_chunked(
                {k: v for k, v in ds.arrays.items() if k in _MODEL_INPUTS})
            ds._resident_cache = (ds.arrays, dev_data)
        eval_idx = getattr(eval_fn, "indexed", None)
        if eval_idx is None:  # bare callable: build (uncached) locally

            @jax.jit
            def eval_idx(p, idx, data):
                batch = {k: jnp.take(v, idx, axis=0) for k, v in data.items()}
                return eval_fn(p, batch)

    edge_scores, edge_labels = [], []
    node_scores, node_labels = [], []
    seq_scores, seq_labels = [], []
    for i in range(0, n, batch_size):
        idx = np.arange(i, min(i + batch_size, n))
        if resident:
            # fixed-size index vector (clamped tail) → single compile
            full = np.minimum(np.arange(i, i + batch_size), n - 1)
            # nerrflint: ok[sync-in-hot-loop] eval: per-batch fetch is the product
            out = jax.device_get(eval_idx(params, jnp.asarray(full), dev_data))
            out = {k: v[: len(idx)] for k, v in out.items()}
        else:
            batch = {k: jnp.asarray(v[idx]) for k, v in ds.arrays.items()}
            # nerrflint: ok[sync-in-hot-loop] eval: per-batch fetch is the product
            out = jax.device_get(eval_fn(params, batch))
        for j in range(len(idx)):
            em = ds.arrays["edge_mask"][idx[j]]
            nm = ds.arrays["node_mask"][idx[j]]
            sm = ds.arrays["seq_valid"][idx[j]]
            edge_scores.append(out["edge_logit"][j][em])
            edge_labels.append(ds.arrays["edge_label"][idx[j]][em])
            node_scores.append(out["node_logit"][j][nm])
            node_labels.append(ds.arrays["node_label"][idx[j]][nm])
            seq_scores.append(out["seq_logit"][j][sm])
            seq_labels.append(ds.arrays["seq_label"][idx[j]][sm])
    e_s, e_l = np.concatenate(edge_scores), np.concatenate(edge_labels)
    n_s, n_l = np.concatenate(node_scores), np.concatenate(node_labels)
    s_s, s_l = np.concatenate(seq_scores), np.concatenate(seq_labels)
    seq_f1, seq_t = best_f1(s_l, s_s)
    node_f1, _node_t = best_f1(n_l, n_s)
    # NOTE: no node-level operating threshold is derived here — the file
    # detector's threshold is calibrated at FILE granularity through the
    # deployed decision function (pipeline.calibrate_file_threshold):
    # node-level precision is dominated by the abundant easy positives and
    # calibrates to a uselessly low cut (measured p≈0.04), while the KPI
    # failure mode lives in per-file max-aggregation over few hard
    # negatives.
    return {
        "edge_auc": roc_auc(e_l, e_s),
        "node_auc": roc_auc(n_l, n_s),
        "seq_auc": roc_auc(s_l, s_s),
        "seq_f1": seq_f1,
        "seq_f1_threshold": seq_t,
        "node_f1": node_f1,
        "num_edges_eval": float(len(e_l)),
        "num_seqs_eval": float(len(s_l)),
    }


def train_nerrfnet(
    train_ds: WindowDataset,
    eval_ds: Optional[WindowDataset] = None,
    cfg: Optional[TrainConfig] = None,
    log=None,
    compile_cache=None,
    monitor=None,
    full_history: bool = False,
) -> TrainResult:
    """``compile_cache`` (a `compilecache.CompileCache`) routes the jitted
    train step through the persistent AOT cache: a repeat run on an
    unchanged config deserializes the step executable instead of paying
    the flagship compile before step 0.
    Fail-open — any cache problem falls back to the live jit path.

    ``monitor`` (a `trainwatch.TrainHealthMonitor`) observes every logged
    step — loss, in-step telemetry floats, accumulated data-wait — at the
    loop's existing host sync point, and can halt the loop once a
    divergence latches (NaN weights cannot recover; see
    docs/training-health.md).  A halted run skips the final eval and
    returns empty metrics."""
    cfg = cfg or TrainConfig()
    model = NerrfNet(cfg.model)
    # config+model fingerprints into the flight journal: a run's identity
    # survives into any later incident bundle (which retrained config
    # produced the weights a serve pod is about to swap in)
    from nerrf_tpu.flight.journal import DEFAULT_JOURNAL, fingerprint

    DEFAULT_JOURNAL.record(
        "train_start", config_fingerprint=fingerprint(cfg),
        model_fingerprint=fingerprint(cfg.model),
        steps=cfg.num_steps, batch_size=cfg.batch_size,
        windows=len(train_ds), seed=cfg.seed)
    if monitor is not None:
        # run identity into the monitor: every train trigger's bundle
        # carries the same fingerprints the journal already stamps
        monitor.set_run(config_fingerprint=fingerprint(cfg),
                        model_fingerprint=fingerprint(cfg.model),
                        steps=cfg.num_steps, seed=cfg.seed)
    rng = jax.random.PRNGKey(cfg.seed)
    rng, init_rng = jax.random.split(rng)
    with DEFAULT_TRACER.span("train_setup", device=True):
        state = init_state(model, cfg, train_ds.arrays, init_rng)
    n = len(train_ds)
    if log:
        # the same attribution the bench artifacts carry, stamped into the
        # training log: a steps/s claim from this run is only
        # interpretable against the aggregation mode and op routes that
        # served it (both are rules over node bucket and backend)
        from nerrf_tpu.ops.segment import active_impls

        log(f"gnn aggregation="
            f"{cfg.model.gnn.resolved_aggregation(train_ds.arrays['node_feat'].shape[1])} "
            f"kernel_path={active_impls()}")
    # HBM-resident + device-scheduled fast path when the dataset fits;
    # stream batches from host otherwise
    resident = _fits_resident(train_ds.arrays)
    with DEFAULT_TRACER.span("train_setup", device=True, phase="step_fns"):
        if resident:
            train_step = make_train_step_scheduled(
                model, cfg, train_ds.arrays, make_idx_schedule(n, cfg))
        else:
            train_step = make_train_step(model, cfg)
        eval_fn = make_eval_fn(model)
    if compile_cache is not None:
        train_step = cache_train_step(compile_cache, train_step, model, cfg,
                                      "train_step_scheduled")
    else:
        train_step = traced_step(train_step)

    order_rng = np.random.default_rng(cfg.seed)
    history = _history(full_history)
    # step-time attribution: padding waste is knowable before the first
    # step (static shapes make padded slots cost real compute); the
    # host-blocked / data-wait split comes from spans around waits the loop
    # has anyway — no span adds a sync
    tracer = DEFAULT_TRACER
    bucket_tag = (f"{train_ds.arrays['node_feat'].shape[1]}n/"
                  f"{train_ds.arrays['edge_src'].shape[1]}e")
    for kind, frac in padding_waste_fractions(train_ds.arrays).items():
        DEFAULT_REGISTRY.gauge_set(
            "train_padding_waste_fraction", frac,
            labels={"kind": kind, "bucket": bucket_tag},
            help="fraction of padded capacity carrying no real data")
    blocked_s = 0.0
    data_wait_s = 0.0
    dw_accum = 0.0  # data wait since the monitor's last observation
    steps_done = 0
    halted = None
    # warmup/compile step excluded from timing
    t_start = None
    with tracer.span("train_loop", steps=cfg.num_steps, resident=resident,
                     bucket=bucket_tag):
        for step in range(cfg.num_steps):
            if not resident:
                with tracer.span("data_wait", step=step) as dw:
                    idx = order_rng.choice(
                        n, size=min(cfg.batch_size, n), replace=False)
                    batch = {k: jnp.asarray(v[idx])
                             for k, v in train_ds.arrays.items()}
                # step 0 excluded: the attribution fractions share the
                # steps/s convention of measuring steady state only
                if step > 0:
                    data_wait_s += dw.dur
                    dw_accum += dw.dur
                # chaos fault point (disarmed = one global None read):
                # poison this step's input with NaN — the non-finite
                # value propagates through loss and gradients, so the
                # in-step nonfinite telemetry must fire and the monitor
                # must dump exactly one train_divergence bundle.  Same
                # shapes, same program: the zero-recompile contract holds
                from nerrf_tpu import chaos

                if chaos.check("train.nonfinite_grad", key=str(step),
                               step=step) is not None:
                    batch = dict(
                        batch,
                        node_feat=batch["node_feat"] * jnp.float32(np.nan))
            step_args = (state, rng) if resident else (state, batch, rng)
            state, loss, aux, rng = train_step(*step_args)
            if step == 0:
                with tracer.span("train_step_wait", step=step):
                    # nerrflint: ok[sync-in-hot-loop] step-0 compile barrier
                    sync_result(loss)
                t_start = time.perf_counter()
            steps_done = step + 1
            if step % cfg.eval_every == 0 or step == cfg.num_steps - 1:
                # the logged step's fetch: the loop's one steady-state sync
                with tracer.span("train_step_wait", step=step) as wait:
                    entry = _history_entry(step, loss, aux)
                if step > 0:
                    blocked_s += wait.dur
                history.append(entry)
                DEFAULT_REGISTRY.gauge_set("train_step", step,
                                           help="last completed train step")
                DEFAULT_REGISTRY.gauge_set(
                    "train_loss", entry["loss"],
                    help="joint loss at last logged step")
                if log:
                    log(f"step {step}: loss={entry['loss']:.4f} "
                        + " ".join(f"{k}={v:.4f}"
                                   for k, v in
                                   _loss_components(aux).items()))
                if monitor is not None:
                    monitor.observe_step(
                        step, entry["loss"],
                        telemetry=_telemetry_floats(aux),
                        data_wait_s=dw_accum,
                        components=_loss_components(aux))
                    dw_accum = 0.0
                    if monitor.should_halt:
                        halted = monitor.diverged
                        if log:
                            log(f"trainwatch: halting at step {step} — "
                                f"{halted[1]} (bundle dumped; resume from "
                                f"the last good checkpoint)")
                        break
        with tracer.span("train_step_wait", step=steps_done) as wait:
            sync_result(state.params)
        if t_start is not None:
            blocked_s += wait.dur
    if monitor is not None:
        # stepping is over: post-training eval/calibration can run for
        # minutes and must not read as a train_stall
        monitor.finish()
    elapsed = time.perf_counter() - (t_start or time.perf_counter())
    steps_per_sec = ((steps_done - 1) / elapsed
                     if elapsed > 0 and steps_done > 1 else 0.0)
    if elapsed > 0 and cfg.num_steps > 1:
        # same denominator as steps_per_sec (post-step-0 steady state), so
        # the fractions attribute the time the headline number measures —
        # dividing by the whole loop would dilute them with compile time
        gauge_host_blocked(blocked_s, elapsed)
        DEFAULT_REGISTRY.gauge_set(
            "train_data_wait_fraction", data_wait_s / elapsed,
            help="fraction of steady-state train wall spent assembling or "
                 "waiting for input batches")
    # device-efficiency plane: analytic step FLOPs x measured steps/s →
    # nerrf_device_mfu{program="train_step"} + roofline intensity.
    # Shape-level trace only (no compile), best-effort by contract, and
    # the MFU gauge stays absent off-chip (null-not-fake).  Spanned: the
    # cost trace takes ~a second and the trace-coverage acceptance
    # (test_tracing) rightly refuses unattributed wall time
    with tracer.span("devtime_cost", program="train_step"):
        from nerrf_tpu.devtime import train_efficiency_gauges

        eff = train_efficiency_gauges(model, cfg, train_ds.arrays,
                                      steps_per_sec)
    if eff and log:
        log(f"device efficiency: {eff}")

    if halted is not None:
        # diverged weights: evaluating NaN params would only fabricate
        # metrics — return empty ones and let the journal say why
        metrics = {}
    else:
        metrics = evaluate(
            eval_fn, state.params,
            eval_ds if eval_ds is not None else train_ds,
            cfg.batch_size,
            # evaluating the train set: its arrays are already
            # device-resident in the train-step closure — a second
            # resident upload would double HBM, so stream per batch in
            # that (diagnostic) case
            resident=None if eval_ds is not None else False,
        )
    DEFAULT_JOURNAL.record(
        "train_done", config_fingerprint=fingerprint(cfg),
        steps_per_sec=round(steps_per_sec, 3),
        steps_done=steps_done,
        **({"halted": halted[1]} if halted is not None else {}),
        metrics={k: round(float(v), 4) for k, v in metrics.items()})
    return TrainResult(state=state, metrics=metrics, steps_per_sec=steps_per_sec,
                       history=list(history))


def train_sharded_stream(
    corpus,
    cfg: Optional[TrainConfig] = None,
    eval_ds: Optional[WindowDataset] = None,
    log=None,
    passes_per_shard: int = 2,
    ckpt_dir=None,
    save_every: int = 0,
    upload_chunk_bytes: int = 64 << 20,
    compile_cache=None,
    monitor=None,
    full_history: bool = False,
) -> TrainResult:
    """100 h-scale training: rotate disk shards through HBM, double-buffered.

    The full corpus (~16 GB of window tensors at 100 h — train/corpus.py)
    exceeds HBM, and per-batch host→device streaming is throttled by the
    ~0.5 GB/s transfer link, so neither resident nor per-step streaming
    works.  Instead: a disk-reader thread stages shard i+1 in host RAM
    while the chip trains on shard i; the consumer issues the (async)
    device_put for i+1 as soon as it starts computing on i, so the upload
    hides behind `passes_per_shard` epochs of scheduled batches and HBM
    holds two resident shards plus, transiently, up to one extra copy of
    the incoming shard while chunked-upload reassembly drains
    (``upload_chunk_bytes``).  Shard order reshuffles every corpus epoch
    (block-shuffled SGD).

    ``ckpt_dir``/``save_every`` enable periodic full-state checkpoints and
    resume-from-latest (elastic.py machinery).  Resume restores params/
    opt-state/step exactly; the *batch schedule* restarts from the restored
    step's derived rng, which is deterministic per step but means the
    shard rotation is not replayed bit-identically across restarts —
    acceptable for the 100 h run (pure data-order perturbation).
    """
    import queue as queue_mod
    import threading

    def put_chunked(arrays, block=False):
        # device_put_chunked, bound to this run's chunk size and logger;
        # the first shard blocks (it gates init anyway) and logs
        # throughput, prefetch uploads stay async so they overlap the
        # current shard's steps.
        return device_put_chunked(arrays, max_bytes=upload_chunk_bytes,
                                  block=block, log=log)

    cfg = cfg or TrainConfig()
    model = NerrfNet(cfg.model)
    loss_fn = make_loss_fn(model, cfg)

    def stream_body(state, idx, rng, data):
        batch = {k: jnp.take(v, idx, axis=0) for k, v in data.items()}
        # f16 is a storage/transfer format only — compute sees f32
        batch = {
            k: v.astype(jnp.float32) if v.dtype == jnp.float16 else v
            for k, v in batch.items()
        }
        return _step_body(loss_fn, state, batch, rng,
                          telemetry=cfg.telemetry)

    if compile_cache is not None:
        # persistent AOT cache: each distinct shard shape resolves once
        # (deserialize on a repeat run — the stream_step compile drops to a
        # disk read), later steps dispatch directly
        step_by_idx = CachedTrainStep(
            compile_cache, make_flat_step(model, cfg, stream_body),
            program="stream_step",
            extra=step_key_extra(cfg, "stream_step"))
    else:
        step_by_idx = traced_step(
            jax.jit(stream_body, donate_argnums=(0,)))

    # -- shard pipeline: disk → host queue → async device upload -------------
    host_q: "queue_mod.Queue" = queue_mod.Queue(maxsize=1)
    stop = threading.Event()

    def reader():
        try:
            epoch = 0
            while not stop.is_set():
                for arrays in corpus.iter_train_shards(
                        epoch_seed=cfg.seed + epoch):
                    while not stop.is_set():
                        try:
                            host_q.put(arrays, timeout=0.5)
                            break
                        except queue_mod.Full:
                            continue
                    if stop.is_set():
                        return
                epoch += 1
        except BaseException as e:  # propagate instead of hanging the train
            host_q.put(e)

    # named so journal records and faulthandler dumps attribute shard-read
    # stalls to this subsystem; daemon is safe here — the reader touches
    # only numpy/disk (never jax), and the finally below joins it anyway
    thread = threading.Thread(target=reader, daemon=True,
                              name="nerrf-train-reader")
    thread.start()

    dw_accum = [0.0]  # shard-queue wait since the monitor's last look

    def next_host_shard():
        # data_wait: host blocked on the disk-reader thread — when this
        # span dominates the trace the reader, not the chip, is the
        # bottleneck (the same accumulated seconds feed the monitor's
        # train_starvation trigger)
        t_dw = time.perf_counter()
        try:
            with DEFAULT_TRACER.span("data_wait", source="shard_queue"):
                while True:
                    try:
                        item = host_q.get(timeout=5.0)
                    except queue_mod.Empty:
                        if not thread.is_alive():
                            raise RuntimeError(
                                "corpus reader thread died without "
                                "reporting")
                        continue
                    if isinstance(item, BaseException):
                        raise RuntimeError(
                            "corpus shard read failed") from item
                    return item
        finally:
            dw_accum[0] += time.perf_counter() - t_dw

    rng = jax.random.PRNGKey(cfg.seed)
    rng, init_rng = jax.random.split(rng)
    shard = put_chunked(next_host_shard(), block=True)
    state = init_state(model, cfg, shard, init_rng)
    if monitor is not None:
        from nerrf_tpu.flight.journal import fingerprint as _fp

        monitor.set_run(config_fingerprint=_fp(cfg),
                        model_fingerprint=_fp(cfg.model),
                        steps=cfg.num_steps, seed=cfg.seed)

    steps_done = 0
    if ckpt_dir is not None and save_every > 0:
        from nerrf_tpu.train.elastic import _restore_full, _save_full, latest_step

        resumed = latest_step(ckpt_dir)
        if resumed is not None:
            state = _restore_full(Path(ckpt_dir), resumed, state)
            steps_done = resumed
            if log:
                log(f"resumed from step {resumed}")

    order = np.random.default_rng((cfg.seed, steps_done))
    history = _history(full_history)
    t_start = None
    timed_from = steps_done
    loss = None
    halted = None
    try:
        while steps_done < cfg.num_steps and halted is None:
            # stage the next shard: async upload overlaps this shard's steps
            nxt = put_chunked(next_host_shard()) \
                if steps_done + _shard_steps(shard, cfg, passes_per_shard) \
                < cfg.num_steps else None
            n = int(shard["node_feat"].shape[0])
            local = min(_shard_steps(shard, cfg, passes_per_shard),
                        cfg.num_steps - steps_done)
            for _ in range(local):
                idx = jnp.asarray(
                    order.choice(n, size=min(cfg.batch_size, n),
                                 replace=False))
                state, loss, aux, rng = step_by_idx(state, idx, rng, shard)
                if t_start is None:
                    # nerrflint: ok[sync-in-hot-loop] step-0 compile barrier
                    sync_result(loss)
                    t_start = time.perf_counter()
                    timed_from = steps_done
                if cfg.eval_every and steps_done % cfg.eval_every == 0:
                    entry = _history_entry(steps_done, loss, aux)
                    history.append(entry)
                    if log:
                        log(f"step {steps_done}: loss={entry['loss']:.4f} "
                            + " ".join(f"{k}={v:.4f}"
                                       for k, v in
                                       _loss_components(aux).items()))
                    if monitor is not None:
                        monitor.observe_step(
                            steps_done, entry["loss"],
                            telemetry=_telemetry_floats(aux),
                            data_wait_s=dw_accum[0],
                            components=_loss_components(aux))
                        dw_accum[0] = 0.0
                        if monitor.should_halt:
                            halted = monitor.diverged
                            if log:
                                log(f"trainwatch: halting at step "
                                    f"{steps_done} — {halted[1]}")
                            break
                steps_done += 1
                if (ckpt_dir is not None and save_every > 0
                        and steps_done % save_every == 0):
                    _save_full(Path(ckpt_dir), steps_done, state)
                    if monitor is not None:
                        monitor.note_checkpoint(
                            Path(ckpt_dir) / f"step_{steps_done:08d}",
                            steps_done)
            if nxt is not None:
                shard = nxt
    finally:
        stop.set()
        try:  # release a blocked put so the reader can exit
            while True:
                host_q.get_nowait()
        except queue_mod.Empty:
            pass
        thread.join(timeout=10)

    sync_result(state.params)
    if monitor is not None:
        monitor.finish()  # post-training eval must not read as a stall
    if ckpt_dir is not None and save_every > 0 and halted is None:
        # a diverged run must not overwrite the last GOOD checkpoint with
        # NaN weights — the bundle's pointer is the restart point
        _save_full(Path(ckpt_dir), steps_done, state)
    elapsed = time.perf_counter() - (t_start or time.perf_counter())
    timed = max(steps_done - timed_from - 1, 1)
    steps_per_sec = timed / elapsed if elapsed > 0 else 0.0
    metrics = (
        evaluate(make_eval_fn(model), state.params, eval_ds, cfg.batch_size)
        if eval_ds is not None and halted is None else {}
    )
    return TrainResult(state=state, metrics=metrics,
                       steps_per_sec=steps_per_sec, history=list(history))


def _shard_steps(shard, cfg: TrainConfig, passes: int) -> int:
    n = int(shard["node_feat"].shape[0])
    return max(1, passes * n // cfg.batch_size)
