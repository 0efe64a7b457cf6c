"""Probe: does the compiled step's scratch occupy HBM that the allocator's
``peak_bytes_in_use`` does not show?  Loads a cell's step, reads the
executable's temp size, then holds a filler array beside it: a filler that
leaves room for the scratch lets the step run, one that does not makes it
fail.  Prints one JSON line per trial.

    python3 chipbench/probes/hbm_witness.py --workload train-1024
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=13)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from chipbench import datagen, run
    from chipbench.traffic import train_resident as tr

    _, _, cell, config = run.load_cell(args.workload)
    dev, _ = run.find_device(1, False)
    run.enable_caches()
    b = int(cell["batch"])
    arrays = datagen.make_windows(config, args.seed, int(cell["traces"]),
                                  int(cell["windows"]))
    table = tr.make_idx_table(args.seed, int(cell["table_rows"]),
                              int(cell["windows"]), b)
    state, step, _ = tr.build_step(config, b, arrays, table,
                                   tr.make_weights(config, args.seed),
                                   log=run.say)
    rng, _ = tr.step_keys(args.seed, 0)
    state, loss, _aux, rng = step(state, rng)
    float(loss)
    scratch = tr.executable_temp_bytes(step)
    stats = dev.memory_stats()
    limit, in_use = stats["bytes_limit"], stats["bytes_in_use"]
    print(json.dumps({"scratch_bytes": scratch, "bytes_limit": limit,
                      "bytes_in_use": in_use,
                      "peak_bytes_in_use": stats["peak_bytes_in_use"]}),
          flush=True)
    # climb in half-GiB steps from well under the plan to the allocator's
    # limit: the first filler beside which the step fails brackets what the
    # step really needs
    step_bytes = 1 << 29
    filler = max(limit - in_use - scratch - (4 << 30), step_bytes)
    last_ok = None
    while filler < limit - in_use:
        n = int(filler) // 4
        try:
            hold = jnp.ones((n,), jnp.float32)
            hold.block_until_ready()
            state, loss, _aux, rng = step(state, rng)
            ok, err = bool(jnp.isfinite(loss)), None
            intact = float(hold[:: 1 << 22].sum()) == float(len(hold[:: 1 << 22]))
        except Exception as e:  # noqa: BLE001 - the failure IS the reading
            ok, err, intact = False, repr(e)[:300], None
        stats = dev.memory_stats()
        print(json.dumps({"filler_bytes": n * 4, "step_ran": ok,
                          "filler_intact": intact, "error": err,
                          "bytes_in_use": stats["bytes_in_use"],
                          "peak_bytes_in_use": stats["peak_bytes_in_use"]}),
              flush=True)
        hold = None
        if not ok:
            break
        last_ok = n * 4
        filler += step_bytes
    print(json.dumps({"workload": args.workload, "scratch_plan_bytes": scratch,
                      "largest_filler_beside_which_the_step_ran": last_ok,
                      "so_the_step_needs_at_most":
                          None if last_ok is None else limit - in_use - last_ok}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
