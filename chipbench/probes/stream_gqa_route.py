"""Probe: which route serves the window layers' attention core faster in the
whole step of a `stream_gqa_resident` cell.  The cell's own step (its
weights, its sequences and its order, as ``--seed`` makes them), built once with every
grouped core on the route `ops/mla.py::attention_route` gives and once with
the window layers' core held to the blocked XLA form (``xla_blocked``); each
is driven through three steps to compile and warm it, then ``--steps`` steps
are timed to `block_until_ready`.  One JSON line: the routes, milliseconds a
step for each, the peak memory each left.  `docs/kernel-paths.md` records
what it said.

    python3 chipbench/probes/stream_gqa_route.py --workload stream-lm-8k-swa-packed --seed 1 --steps 10
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()
    import jax

    from chipbench import run
    from chipbench.traffic import stream_gqa_resident as sgr
    from chipbench.traffic import stream_resident as sr
    from chipbench.traffic import stream_sparse_resident as ssr
    from chipbench.traffic import train_resident as tr
    from nerrf_tpu.ops import mla
    from nerrf_tpu.train.stream import stream_kernel_path

    _, _, cell, config = run.load_cell(args.workload)
    dev, _ = run.find_device(1, False)
    run.enable_caches()
    arrays, _ = sr.make_sequences(config, cell)
    table = sr.make_order_table(
        args.seed, int(cell["table_rows"]),
        sgr.sequence_costs(config, cell, arrays["segments"]))
    weights = ssr.weights_seed_of(cell, args.seed)
    given, chosen = mla.attention, mla.attention_route

    def window_xla(q, k, v, seg, *, window=None, **kw):
        # the window layers' core traced on the blocked XLA form
        if window is not None:
            mla.attention_route = lambda *a: "xla_blocked"
        try:
            return given(q, k, v, seg, window=window, **kw)
        finally:
            mla.attention_route = chosen

    out = {"workload": args.workload, "seed": args.seed}
    for label, attention in (("given", given), ("window_xla", window_xla)):
        mla.attention = attention
        # an AOT cache of its own: the key does not tell the two forms apart
        with tempfile.TemporaryDirectory() as root:
            state, step, _, scfg = sgr.build_step(
                config, 1, arrays, table, sr.make_weights(config, weights),
                cache_root=root, log=lambda _: None)
            rng, _ = tr.step_keys(args.seed, 0)
            for _ in range(3):
                state, loss, _, rng = step(state, rng)
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            state, loss, _, rng = step(state, rng)
        jax.block_until_ready(state)
        ms = 1e3 * (time.perf_counter() - t0) / args.steps
        stats = dev.memory_stats() or {}
        routes = stream_kernel_path(scfg, int(cell["seq_len"]))
        if attention is window_xla:
            routes["gqa_window_attention"] = "xla_blocked"
        out[label] = {"routes": routes, "ms_per_step": ms, "loss": float(loss),
                      "peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
        print(f"{label}: {out[label]}", file=sys.stderr, flush=True)
        del state, step
        gc.collect()
    mla.attention = given
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
