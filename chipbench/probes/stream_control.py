"""Probe: the control and the planted fault of the comparison that decides
`correct` in a `stream_resident` cell, at the cell's own size, with no
program in the process (`chipbench/probes/control.py`'s twin for the stream
pretrainer).

For each seed: the plain reference (f32) through the cell's first steps,
then the same reference with every matmul operand in a lower precision
(``fp8``: the step below the configuration's bf16; ``bf16``: what a faithful
program may differ by) and with the planted fault (the scan's state carried
across document boundaries), each held against the f32 reference by
`chipbench.compare` and the generator's `diff_numbers` and put through
`compare.verdict` with the cell's own limits: ``correct`` has to read false
for ``fp8`` and for the fault, true for ``bf16``.  One JSON line per seed.
``--precisions ""`` reads the fault alone.

    python3 chipbench/probes/stream_control.py --workload stream-lm-8k-packed --seeds 1,2,3
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
    from chipbench import compare, run
    from chipbench.traffic import stream_resident as sr

    _, _, cell, config = run.load_cell(args.workload)
    run.find_device(1, False)
    run.enable_caches()
    arrays, _ = sr.make_sequences(config, cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        table = sr.make_order_table(
            seed, int(cell["table_rows"]),
            sr.sequence_costs(config, arrays["segments"]))
        sound = sr.follow_reference(config, arrays, table, seed)
        out = {"workload": args.workload, "seed": seed,
               "losses": sound["losses"],
               "reference_s": time.perf_counter() - t0}
        others = [(p, {"precision": p}) for p in args.precisions.split(",")
                  if p] + [("scan_ignores_documents",
                            {"fault": "scan_ignores_documents"})]
        for name, kwargs in others:
            other = sr.follow_reference(config, arrays, table, seed,
                                        **kwargs)
            worst: dict = {}
            numbers = sr.compare_all(other, sound, worst)
            del other
            correct, compared, _ = compare.verdict(numbers, cell["limits"])
            out[name] = {"correct": correct, "compared": compared,
                         "numbers": numbers, "worst": worst}
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
