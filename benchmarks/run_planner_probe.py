#!/usr/bin/env python3
"""Planner throughput probe: rollouts/s at the bench configuration (M1-scale
incident, 800 simulations) for frontier batch sizes 64 and 128.  The metric
of record lands in bench.py's `mcts_rollouts_per_sec`; this standalone probe
exists for tuning runs."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np


def main() -> int:
    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    from nerrf_tpu.planner import MCTSConfig, MCTSPlanner, UndoDomain
    from nerrf_tpu.planner.value_net import ValueNet

    prng = np.random.default_rng(7)
    F, P = 45, 4
    domain = UndoDomain(
        file_paths=[f"/app/uploads/doc_{i}.lockbit3" for i in range(F)],
        file_scores=prng.beta(0.4, 0.4, F).astype(np.float32),
        file_loss_mb=prng.uniform(2.0, 5.0, F).astype(np.float32),
        proc_names=[f"{4000 + p}:python3" for p in range(P)],
        proc_scores=np.array([0.95] + [0.1] * (P - 1), np.float32),
        max_steps=64,
    )
    vnet = ValueNet.create()
    vnet.fit_to_domain(domain, num_rollouts=256, steps=150)
    for bs in (64, 128):
        plan = MCTSPlanner(domain, vnet, MCTSConfig(
            num_simulations=800, batch_size=bs)).plan()
        print(f"host batch {bs}: {plan.rollouts} rollouts @ "
              f"{plan.rollouts_per_sec:.0f}/s, {len(plan.actions)} actions")

    # single-program planner: tree + search on device, no per-batch round
    # trips
    from nerrf_tpu.planner import DeviceMCTS

    dm = DeviceMCTS(domain, cfg=MCTSConfig(num_simulations=800),
                    value_apply=vnet.apply_fn, value_params=vnet.params)
    dm.plan()  # compile
    plan = dm.plan()
    print(f"device single-program: {plan.rollouts} rollouts @ "
          f"{plan.rollouts_per_sec:.0f}/s, {len(plan.actions)} actions")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
