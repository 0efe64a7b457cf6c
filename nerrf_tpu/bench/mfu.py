"""Chip peaks + the XLA cost-analysis cross-check for the bench.

The reference has no chip-side perf baseline (its AI subsystem was never
built, SURVEY.md §6), and a torch-on-CPU ratio is a strawman — the honest
single-chip metric is MFU.  The MFU *numerator* of record is the analytic
jaxpr count (`nerrf_tpu.bench.flops.analytic_flops`): r5 measured
`compiled.cost_analysis()["flops"]` on the TPU backend costing matmuls at
their MXU-padded shapes AND ignoring scan trip counts — wrong in both
directions, enough to put "MFU" at an impossible 195%.  `flops_per_step`
here remains only as the recorded cross-check
(`xla_cost_analysis_flops_per_step` in the bench line), and
`chip_peak_tflops`/`mfu` supply the per-chip peaks for the ratio.
"""

from __future__ import annotations

from typing import Optional

# The peak table lives in nerrf_tpu/devtime/peaks.py now (exact-match-
# first resolution + HBM bandwidth for the roofline gauges); this module
# keeps its historical API as a thin delegate so every bench caller and
# artifact script keeps working unchanged.
from nerrf_tpu.devtime.peaks import chip_peak_tflops  # noqa: F401  (re-export)


def flops_per_step(jit_fn, *args, **kwargs) -> Optional[float]:
    """FLOPs of one call of a jitted function, from XLA cost analysis."""
    try:
        compiled = jit_fn.lower(*args, **kwargs).compile()
        cost = compiled.cost_analysis()
        f = float(cost.get("flops", 0.0))
        return f if f > 0 else None
    except Exception:
        return None


def mfu(flops: Optional[float], steps_per_sec: float,
        device) -> tuple[Optional[float], Optional[float]]:
    """(achieved_tflops, mfu_pct) — None when flops or peak are unknown."""
    if not flops:
        return None, None
    achieved = flops * steps_per_sec / 1e12
    peak = chip_peak_tflops(device)
    return achieved, (100.0 * achieved / peak if peak else None)
