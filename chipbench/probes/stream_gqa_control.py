"""Probe: the control and the planted faults of the comparison that decides
`correct` in a `stream_gqa_resident` cell, at the cell's own size, with no
program in the process (`chipbench/probes/stream_mla_control.py`'s twin).

For each seed: the plain reference (f32) through the cell's first steps,
then the same reference with every matmul operand in a lower precision
(``fp8``: the step below the configuration's bf16) and with each planted
fault (``half_batch``: the second half of the sequence's targets left out;
``no_window``: the window layers attending their whole document;
``rope_unscaled``: YaRN left out of the full layers' rotary;
``router_unscaled``: the routed part without its 2.5), each held against the
f32 reference by `chipbench.compare` and the generator's `diff_numbers` and
put through `compare.verdict` with the cell's own limits: ``correct`` has to
read false for every one.  One JSON line per seed.  ``--precisions ""``
reads the faults alone, ``--faults ""`` the control alone.

    python3 chipbench/probes/stream_gqa_control.py --workload stream-lm-8k-swa-packed --seeds 1,2
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
    ap.add_argument("--precisions", default="fp8")
    ap.add_argument("--faults", default="half_batch,no_window,rope_unscaled,"
                                        "router_unscaled")
    args = ap.parse_args()
    from chipbench import compare, run
    from chipbench.traffic import stream_gqa_resident as sgr
    from chipbench.traffic import stream_resident as sr
    from chipbench.traffic import stream_sparse_resident as ssr

    _, _, cell, config = run.load_cell(args.workload)
    run.find_device(1, False)
    run.enable_caches()
    arrays, _ = sr.make_sequences(config, cell)
    costs = sgr.sequence_costs(config, cell, arrays["segments"])
    others = [(p, {"precision": p}) for p in args.precisions.split(",") if p]
    others += [(f, {"fault": f}) for f in args.faults.split(",") if f]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        table = sr.make_order_table(seed, int(cell["table_rows"]), costs)
        weights = ssr.weights_seed_of(cell, seed)
        sound = sr.follow_reference(config, arrays, table, weights)
        out = {"workload": args.workload, "seed": seed,
               "losses": sound["losses"],
               "reference_s": time.perf_counter() - t0}
        for name, kwargs in others:
            other = sr.follow_reference(config, arrays, table, weights,
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
