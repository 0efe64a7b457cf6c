"""Unified per-program cost model: FLOPs / bytes / HBM floor per program.

`program_costs()` resolves an analytic cost for every serve bucket
program (at the exact shapes `serve/service.warmup_batches` compiles —
the admission-reachable set) and for the flat train step.  The FLOP
numerator of record is the analytic jaxpr count
(`nerrf_tpu.bench.flops.analytic_flops`): XLA's
``lower().compile().cost_analysis()`` costs matmuls at their MXU-padded
shapes and double-counts fused producers (~3x high at flagship shapes —
the 195%-MFU lesson documented in `bench/mfu.py`), so it is recorded
here strictly as a cross-check, never the authority.

Bytes are an analytic floor, not a measurement: params + inputs read
once, outputs written once.  Intermediates and re-reads are invisible to
a shape-level trace, so the derived arithmetic intensity is an UPPER
bound — honest for "is this program near the roofline ridge" reading
(a program whose ceiling intensity is below the ridge is definitely
bandwidth-bound).

Everything here traces shapes only (``jax.make_jaxpr``/``eval_shape``):
no device execution, no compile — safe to run at service boot without
touching the zero-recompile contract.  The one exception is the opt-in
``cross_check=True``, which pays one real compile per program.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from nerrf_tpu.bench.flops import analytic_flops


@dataclasses.dataclass(frozen=True)
class ProgramCost:
    """One program's analytic cost at one call signature."""

    program: str                 # "serve_eval[<bucket>]" / "train_step"
    flops: float                 # analytic matmul/conv FLOPs per call
    bytes_accessed: float        # analytic floor: params+inputs+outputs
    peak_hbm_bytes: float        # residency floor: params+inputs+outputs
    batch_slots: Optional[int] = None   # padded windows per call (serve)
    # the XLA cost_analysis cross-check (None unless cross_check=True
    # succeeded) — recorded, never the MFU numerator
    xla_flops: Optional[float] = None
    xla_bytes: Optional[float] = None

    @property
    def intensity_flops_per_byte(self) -> Optional[float]:
        """Ceiling arithmetic intensity (analytic flops over the byte
        floor) — compare against `ChipPeaks.ridge_flops_per_byte`."""
        if self.bytes_accessed <= 0:
            return None
        return self.flops / self.bytes_accessed

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        i = self.intensity_flops_per_byte
        d["intensity_flops_per_byte"] = round(i, 2) if i else None
        return d


def _tree_bytes(tree) -> float:
    import jax

    total = 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += float(np.prod(shape, dtype=np.float64)
                       * np.dtype(dtype).itemsize)
    return total


def xla_cost(fn, *args) -> tuple:
    """``(flops, bytes accessed)`` from one real compile's cost analysis —
    the recorded cross-check.  ``(None, None)`` when the backend/jit
    cannot produce it (plain callables, failed lowering): the cross-check
    is optional evidence, never a reason to fail the cost model."""
    try:
        compiled = fn.lower(*args).compile()
        cost = compiled.cost_analysis()
        flops = float(cost.get("flops", 0.0)) or None
        byts = float(cost.get("bytes accessed", 0.0)) or None
        return flops, byts
    except Exception:  # noqa: BLE001 — cross-check is best-effort
        return None, None


def program_cost(fn, *args, program: str, batch_slots: Optional[int] = None,
                 cross_check: bool = False) -> Optional[ProgramCost]:
    """Cost one call of ``fn`` at these arg shapes (shape-level trace).
    Returns None when the analytic counter cannot see the program (trace
    failure, zero matmuls) — null, never a fabricated number."""
    import jax

    flops = analytic_flops(fn, *args)
    if not flops:
        return None
    in_bytes = _tree_bytes(args)
    try:
        out_bytes = _tree_bytes(jax.eval_shape(fn, *args))
    except Exception:  # noqa: BLE001 — outputs are part of the floor only
        out_bytes = 0.0
    xf, xb = xla_cost(fn, *args) if cross_check else (None, None)
    return ProgramCost(
        program=program, flops=float(flops),
        bytes_accessed=in_bytes + out_bytes,
        peak_hbm_bytes=in_bytes + out_bytes,
        batch_slots=batch_slots, xla_flops=xf, xla_bytes=xb)


def serve_program_costs(eval_fn, params, cfg,
                        cross_check: bool = False) -> Dict[str, ProgramCost]:
    """``bucket tag → ProgramCost`` for every warmup-compiled serve
    program, at the exact shape-donor batches `warmup_batches` yields —
    the same shapes admission can ever produce (the deep static pass
    proves that closure; tests/test_devtime.py pins this function to it
    and to `train/data.sample_spec`)."""
    from nerrf_tpu.serve.service import warmup_batches

    out: Dict[str, ProgramCost] = {}
    for _bucket, tag, batch in warmup_batches(cfg):
        cost = program_cost(
            eval_fn, params, batch, program=f"serve_eval[{tag}]",
            batch_slots=int(next(iter(batch.values())).shape[0]),
            cross_check=cross_check)
        if cost is not None:
            out[tag] = cost
    return out


def train_step_cost(model, train_cfg, arrays,
                    cross_check: bool = False) -> Optional[ProgramCost]:
    """Analytic cost of ONE flat train step at these dataset shapes.

    Costs a fresh `make_train_step` program (the canonical grad/update
    body every flavor shares) with shape-only state/batch/rng — the live
    loop's step may be a cached executable or a resident closure, neither
    of which re-traces; the cost is identical because the body is."""
    import jax

    from nerrf_tpu.train.loop import init_state, make_train_step

    try:
        n = int(next(iter(arrays.values())).shape[0])
        b = min(train_cfg.batch_size, n)
        batch = {k: jax.ShapeDtypeStruct((b,) + tuple(v.shape[1:]),
                                         np.asarray(v).dtype)
                 for k, v in arrays.items()}
        rng = jax.eval_shape(lambda s: jax.random.PRNGKey(s),
                             jax.ShapeDtypeStruct((), np.uint32))
        # init under eval_shape: param/opt-state SHAPES only — no real
        # initialization runs, so costing a step is boot-cheap
        state = jax.eval_shape(
            lambda r: init_state(model, train_cfg, arrays, r), rng)
        step = make_train_step(model, train_cfg)
        return program_cost(step, state, batch, rng, program="train_step",
                            batch_slots=b, cross_check=cross_check)
    except Exception:  # noqa: BLE001 — a cost model must degrade to null
        return None


def program_costs(eval_fn, params, serve_cfg, model=None, train_cfg=None,
                  arrays=None, cross_check: bool = False
                  ) -> Dict[str, ProgramCost]:
    """The unified cost surface: ``program name → ProgramCost`` for every
    serve bucket program plus (when the training pieces are given) the
    flat train step.  This is the measured cost table a future
    ``nerrf tune`` fits its routing/ladder model over."""
    out = {c.program: c for c in serve_program_costs(
        eval_fn, params, serve_cfg, cross_check=cross_check).values()}
    if model is not None and train_cfg is not None and arrays is not None:
        tc = train_step_cost(model, train_cfg, arrays,
                             cross_check=cross_check)
        if tc is not None:
            out[tc.program] = tc
    return out
