"""Probe: the program's timed step at several batch sizes, ascending, in one
process: compile seconds, ``peak_bytes_in_use`` (a process's peak never
falls, hence ascending), ``bytes_limit``, windows/s over a short window.
No reference, no verdict: this sizes a cell, it proves nothing.

    python3 chipbench/probes/batch_sweep.py --workload train-1024 --batches 8,16 --seconds 5
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
    ap.add_argument("--batches", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    import jax

    from chipbench import datagen, run
    from chipbench.traffic import train_resident as tr

    _, entry, cell, config = run.load_cell(args.workload)
    dev, _ = run.find_device(1, False)
    run.enable_caches()
    batches = sorted(int(b) for b in args.batches.split(","))
    arrays = datagen.make_windows(config, args.seed, int(cell["traces"]),
                                  max(batches))
    for b in batches:
        sub = {k: v[:b] for k, v in arrays.items()}
        table = tr.make_idx_table(args.seed, int(cell["table_rows"]), b, b)
        t0 = time.perf_counter()
        try:
            state, step, infos = tr.build_step(
                config, b, sub, table, tr.make_weights(config, args.seed),
                log=run.say)
            rng, _ = tr.step_keys(args.seed, 0)
            for _ in range(2):
                state, loss, _aux, rng = step(state, rng)
            loss = float(loss)
            warm = time.perf_counter() - t0
            state, rng, steps, elapsed, _l, dispatch = tr.timed_window(
                step, state, rng, args.seconds, spans=False)
        except Exception as e:  # noqa: BLE001 - a probe reports and goes on
            print(json.dumps({"batch": b, "error": repr(e)[:400]}), flush=True)
            continue
        stats = dev.memory_stats()
        print(json.dumps({
            "workload": args.workload, "batch": b, "warm_s": warm,
            "loss": loss, "steps": steps, "elapsed": elapsed,
            "windows_per_s": steps * b / elapsed,
            "step_s": elapsed / max(steps, 1),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_in_use": stats.get("bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
            "largest_alloc_size": stats.get("largest_alloc_size"),
            "aot": [i.source for i in infos]}), flush=True)
        del state, step
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
