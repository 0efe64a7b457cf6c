"""Sharded training step: the multi-chip version of `train.loop`.

Same model, same loss — the only difference is sharding annotations.  The
window batch is sharded over ``dp``; parameters are laid out by
`parallel.mesh.param_sharding` (large kernels tensor-parallel over ``tp``,
the rest replicated).  Under `jax.jit` with these shardings, GSPMD emits the
gradient all-reduce over dp and the activation collectives for tp — there is
no hand-written communication anywhere, per the TPU-first design stance
(SURVEY.md §7).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax.training import train_state
from jax.sharding import Mesh

from typing import TYPE_CHECKING

from nerrf_tpu.models.joint import NerrfNet
from nerrf_tpu.parallel.mesh import batch_sharding, param_sharding, replicated

if TYPE_CHECKING:  # runtime import is deferred: models → parallel → train.loop
    from nerrf_tpu.train.loop import TrainConfig                    # noqa: F401


def _loop():
    """nerrf_tpu.train.loop, imported lazily to break the package cycle
    (train.__init__ → loop → models → stream → parallel → here)."""
    from nerrf_tpu.train import loop

    return loop


def shard_batch(mesh: Mesh, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
    """Place a host batch onto the mesh, window axis split over dp.

    Works in both deployment shapes:
      * single process (one host, N local devices): plain sharded device_put;
      * multi-process (one controller per host, global mesh): every process
        must call this with the IDENTICAL global batch (derive it from a
        shared seed — run.py does); `make_array_from_callback` then uploads
        only the rows owned by this process's addressable devices, and the
        result is one global jax.Array spanning hosts.
    """
    sh = batch_sharding(mesh)
    if jax.process_count() == 1:
        return {k: jax.device_put(jnp.asarray(v), sh) for k, v in batch.items()}
    return {
        k: jax.make_array_from_callback(
            np.asarray(v).shape, sh, lambda idx, v=np.asarray(v): v[idx])
        for k, v in batch.items()
    }


def init_sharded_state(
    model: NerrfNet,
    cfg: "TrainConfig",
    sample: Dict[str, np.ndarray],
    mesh: Mesh,
    rng: Optional[jax.Array] = None,
) -> train_state.TrainState:
    """Initialize params directly into their sharded layout (jitted init with
    output shardings, so no host-side full copy materializes first)."""
    loop = _loop()
    rng = rng if rng is not None else jax.random.PRNGKey(cfg.seed)
    one = {k: jnp.asarray(v[0]) for k, v in sample.items()}

    def init_fn(rng):
        return model.init(
            rng, *loop.model_inputs(one), deterministic=True)["params"]

    shapes = jax.eval_shape(init_fn, rng)
    p_shard = param_sharding(mesh, shapes)
    params = jax.jit(init_fn, out_shardings=p_shard)(rng)

    with mesh:
        state = train_state.TrainState.create(
            apply_fn=model.apply, params=params, tx=loop.make_tx(cfg)
        )
    return state


def make_sharded_train_step(model: NerrfNet, cfg: "TrainConfig", mesh: Mesh,
                            compile_cache=None):
    """Jitted train step with explicit in/out shardings over the mesh.

    ``compile_cache`` routes the step through the persistent AOT cache
    (`compilecache.StepCache`): the first call on each batch signature
    resolves — deserializing a prior run's executable when the config,
    mesh shape, and jax/device identity are unchanged — and later calls
    dispatch straight to the compiled program.  The mesh axis sizes ride
    the cache key (sharding changes the emitted collectives, so a (2,1)
    executable must never serve a (1,2) mesh even at equal device count).
    """
    loop = _loop()
    loss_fn = loop.make_loss_fn(model, cfg)
    b_shard = batch_sharding(mesh)
    r_shard = replicated(mesh)

    def step_body(state, batch, rng):
        # the ONE grad/update body (loop._step_body) so the in-step
        # telemetry axis (cfg.telemetry) can never drift per flavor —
        # under the mesh the norm reductions become collectives, which is
        # exactly what a sharded health reading should be
        state, loss, aux, rng = loop._step_body(
            loss_fn, state, batch, rng, telemetry=cfg.telemetry)
        # hand the state back in the layout it came in (param_sharding is
        # a rule over leaf names and shapes, so it covers the optimizer's
        # moments too).  Left to GSPMD, the outputs come back in a layout
        # of its own choosing — biases split over tp — and step 1 no
        # longer matches the program step 0 compiled: a silent second
        # compile under jit, a refused call on the AOT executable
        pin = lambda tree: jax.lax.with_sharding_constraint(
            tree, param_sharding(mesh, tree))
        state = state.replace(params=pin(state.params),
                              opt_state=pin(state.opt_state))
        return state, loss, aux, rng

    train_step = jax.jit(
        step_body,
        donate_argnums=(0,),
        in_shardings=(None, b_shard, r_shard),
        out_shardings=None,
    )

    if compile_cache is None:
        return train_step
    # the cacheable twin: the same flat (params, opt_state, step, batch,
    # rng) boundary as every other flavor (loop.make_flat_step — the
    # TrainState treedef can't serialize), with this mesh's shardings
    # over the flat slots
    flat_step = loop.make_flat_step(
        model, cfg, step_body,
        in_shardings=(None, None, None, b_shard, r_shard),
        out_shardings=None)

    extra = loop.step_key_extra(cfg, "train_step_sharded")
    extra["mesh"] = repr(sorted(mesh.shape.items()))
    return loop.CachedTrainStep(compile_cache, flat_step,
                                program="train_step_sharded", extra=extra)


def sharding_contract(mesh: Mesh) -> list:
    """Declared sharding layout of the pjit shims in this module, as
    ``(program, array, PartitionSpec, ndim)`` tuples — built from the SAME
    `batch_sharding`/`replicated`/`stream_shardings` calls the real steps
    use, so the contract can never drift from the code.

    The deep static pass (`nerrf lint --deep`, collective-consistency)
    validates every spec's axis names against the mesh and its rank
    against the array it annotates: the pre-flight the pod-scale serving
    work needs, run abstractly on CPU instead of at GSPMD partitioning
    time on a pod."""
    from nerrf_tpu.train.data import DatasetConfig, sample_spec

    contract = []
    b_spec = batch_sharding(mesh).spec
    r_spec = replicated(mesh).spec
    for k, (shape, _dtype) in sample_spec(DatasetConfig()).items():
        contract.append(
            ("train_step_sharded", f"batch[{k}]", b_spec, len(shape) + 1))
    contract.append(("train_step_sharded", "rng", r_spec, 1))
    # the stream batch layout the ring path consumes (train_sharded_stream
    # builds exactly these three [B,T,...] arrays); a key stream_shardings
    # grows beyond this map still gets its axis names validated — ndim
    # falls back to the spec's own rank rather than crashing the rule
    stream_ndim = {"feat": 3, "mask": 2, "label": 2}
    for k, sh in stream_shardings(mesh).items():
        contract.append(("stream_train_step", k, sh.spec,
                         stream_ndim.get(k, len(tuple(sh.spec)))))
    return contract


# --- long-context stream training (dp × sp) ----------------------------------


def stream_shardings(mesh: Mesh) -> Dict[str, "jax.sharding.NamedSharding"]:
    """Stream batches shard batch over dp and *time* over sp — the layout ring
    attention expects (parallel/ring.py)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return {
        "feat": NamedSharding(mesh, P("dp", "sp", None)),
        "mask": NamedSharding(mesh, P("dp", "sp")),
        "label": NamedSharding(mesh, P("dp", "sp")),
    }


def make_stream_train_step(model, mesh: Mesh,
                           cfg: Optional["TrainConfig"] = None):
    """(init_fn, step_fn, place) for StreamNet over a dp×sp mesh: the
    sharded twin of `train.stream`'s step, with the same loss
    (`make_stream_loss_fn`), the same grad/update body and the trainer's
    optimizer (`make_tx(cfg)`; ``cfg`` None is `TrainConfig()`).

    ``model`` must be a StreamNet constructed with this mesh so its attention
    layers run the sp ring.  Gradients all-reduce over dp×sp automatically
    (GSPMD); the only hand-written collective in the whole step is the
    ppermute inside ring attention.
    """
    from nerrf_tpu.train.stream import init_stream_state, make_stream_loss_fn

    loop = _loop()
    cfg = cfg if cfg is not None else loop.TrainConfig()
    sh = stream_shardings(mesh)
    loss_fn = make_stream_loss_fn(model)

    def place(batch):
        # On a 1-device mesh, inputs committed to a NamedSharding push every
        # call of the step down a path two orders of magnitude slower
        # (v5e, 12 x 4096 stream step: 2,442 vs 23.9 ms/step — PERF.md,
        # PR 21); plain device_put is semantically identical there.
        if mesh.size == 1:
            return {k: jax.device_put(jnp.asarray(v)) for k, v in batch.items()}
        return {k: jax.device_put(jnp.asarray(v), sh[k]) for k, v in batch.items()}

    def init_fn(rng, placed_batch):
        """``placed_batch`` must come from ``place`` — init reuses it, so the
        host→device transfer happens once per batch, not once per caller."""
        return init_stream_state(model, cfg, placed_batch, rng)

    @partial(jax.jit, donate_argnums=(0,))
    def step_fn(state, batch, rng):
        state, loss, _aux, rng = loop._step_body(loss_fn, state, batch, rng)
        return state, loss, rng

    return init_fn, step_fn, place
