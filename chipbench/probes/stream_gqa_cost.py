"""Probe: what each resident sequence of a `stream_gqa_resident` cell costs
the program's step, one step at a time, and what the step routed
(`chipbench/probes/stream_mla_cost.py`'s twin on this generator's step).

The step of a stack with routed experts walks the tiles its router filled,
so its time follows what the router sends the held experts: with the
weights' draw and with the sequence's tokens (PERF.md section 6, PRs 33 and
35).  The step is built once (as the generator builds it, over an order
table that holds every resident sequence ``--repeats`` times in a row, WITH
THE LEARNING RATE AT ZERO, so that every sequence meets the same weights);
for each weights seed a fresh state takes it through the table, every step
waited for and timed alone.  One JSON line per weights seed: per sequence
the median seconds a step (a mix's ``seq_cost``) and the held assignments,
the fit of the one on the other, and the mean step: how far the weights'
draw alone moves the cell.

    python3 chipbench/probes/stream_gqa_cost.py --workload stream-lm-8k-swa-packed --weights-seeds 1,2
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--weights-seeds", required=True)
    ap.add_argument("--repeats", type=int, default=2)
    args = ap.parse_args()
    import numpy as np
    from flax.training import train_state

    from chipbench import run
    from chipbench.traffic import stream_gqa_resident as sgr
    from chipbench.traffic import stream_resident as sr
    from chipbench.traffic import train_resident as tr

    _, _, cell, config = run.load_cell(args.workload)
    config = dict(config, train=dict(config["train"], learning_rate=0.0))
    run.find_device(1, False)
    run.enable_caches()
    arrays, _ = sr.make_sequences(config, cell)
    n = int(cell["num_seqs"])
    table = np.repeat(np.arange(n, dtype=np.int32), args.repeats)[:, None]
    seeds = [int(s) for s in args.weights_seeds.split(",")]
    state, step, _, _ = sgr.build_step(config, int(cell["batch"]), arrays,
                                       table, sr.make_weights(config, seeds[0]),
                                       log=run.say)
    for seed in seeds:
        params = sr.make_weights(config, seed)
        state = train_state.TrainState.create(
            apply_fn=state.apply_fn, params=params, tx=state.tx)
        del params
        rng, _ = tr.step_keys(seed, 0)
        for _ in range(2):      # compile, then one warm call
            state, loss, aux, rng = step(state, rng)
            float(loss)
        # the two calls above moved the schedule on by two rows
        seconds = [[] for _ in range(n)]
        held = [[] for _ in range(n)]
        for k in range(len(table)):
            row = int(table[(k + 2) % len(table), 0])
            t0 = time.perf_counter()
            state, loss, aux, rng = step(state, rng)
            float(loss)
            seconds[row].append(time.perf_counter() - t0)
            held[row].append(float(aux["held_assignments"]))
        cost = [float(np.median(s)) for s in seconds]
        load = [float(np.mean(h)) for h in held]
        slope, intercept = np.polyfit(load, cost, 1)
        print(json.dumps({
            "workload": args.workload, "weights_seed": seed,
            "seconds": cost, "held_assignments": load,
            "mean_seconds": float(np.mean(cost)),
            "seconds_per_assignment": float(slope),
            "seconds_at_none": float(intercept),
            "spread_of_seconds": float(np.std(cost) / np.mean(cost))}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
