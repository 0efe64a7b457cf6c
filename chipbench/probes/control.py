"""Probe: the control and the planted faults of the comparison that decides
`correct`, at the cell's own size, with no program in the process.

For each seed: the plain reference (f32) through the cell's first steps,
then the same reference computed in a lower precision (``fp8``: the step
below the configurations' bf16; ``bf16``: what a faithful program may
differ by) and with half of the batch left out (the mean taken over the
rest), each held against the f32 reference by `chipbench.compare`.  One
JSON line per seed.

    python3 chipbench/probes/control.py --workload train-1024 --seeds 1,2,3
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precisions", default="fp8,bf16")
    args = ap.parse_args()
    from chipbench import compare, datagen, run
    from chipbench.traffic import train_resident as tr

    _, _, cell, config = run.load_cell(args.workload)
    run.find_device(1, False)
    run.enable_caches()
    b = int(cell["batch"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        arrays = datagen.make_windows(config, seed, int(cell["traces"]),
                                      int(cell["windows"]))
        table = tr.make_idx_table(seed, int(cell["table_rows"]),
                                  int(cell["windows"]), b)
        sound = tr.follow_reference(config, cell, arrays, table, seed)
        out = {"workload": args.workload, "seed": seed,
               "losses": sound["losses"]}
        for precision in args.precisions.split(","):
            other = tr.follow_reference(config, cell, arrays, table, seed,
                                        precision=precision)
            worst: dict = {}
            out[precision] = compare.compare_training(other, sound, worst)
            out[precision + "_worst"] = worst
        half = tr.follow_reference(config, cell, arrays, table, seed,
                                   rows=lambda idx: idx[: len(idx) // 2])
        out["half_batch"] = compare.compare_training(half, sound)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
