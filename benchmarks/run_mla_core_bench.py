"""Time `ops/mla.py::attention` alone on the chip: forward, and forward +
backward, of one packed sequence at a latent layer's widths, for the blocked
XLA route and for the fused route at a list of tile sizes; each fused form is
also held to the XLA route's output and gradients on the same inputs.

    chiprun -- python3 benchmarks/run_mla_core_bench.py \
        --tiles 512x512,1024x512,1024x1024 --out chiprun_out/mla_core_bench.json

The table of record is `benchmarks/results/mla_core_bench_v5e.json`
(docs/kernel-paths.md); a kernel alone is not the step (PR 30): the whole
step is timed by `chipbench/probes/stream_mla_cost.py`.  Off a TPU the
script exits 1: the fused route is the TPU's.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _seconds(fn, args, calls: int) -> float:
    """Least seconds a call of ``fn(*args)``, each call waited for."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="512x512",
                    help="comma-separated QxK tile sizes of the fused route")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=20)
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--documents", default="one,packed",
                    help="one: a single document; packed: documents of "
                         "1000-3000 tokens and a padded tail")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/mla_core_bench.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from nerrf_tpu.ops import mla

    if jax.default_backend() != "tpu":
        print("the fused route is the TPU's: no TPU here", file=sys.stderr)
        return 1
    t, h, d = args.tokens, args.heads, args.width
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, cot = (jax.random.normal(key, (t, h, d), jnp.bfloat16)
                    for key in keys)
    rng = np.random.default_rng(0)
    segs = {"one": np.ones(t, np.int32)}
    lengths = rng.integers(1000, 3000, size=t // 1000)
    packed = np.repeat(np.arange(1, len(lengths) + 1), lengths)[:t - 300]
    segs["packed"] = np.concatenate(
        [packed, np.zeros(t - len(packed))]).astype(np.int32)

    def forms(seg):
        """-> (forward, gradients) of the route the module's tile sizes
        give, jitted afresh (the sizes are read when it is traced)."""
        fwd = jax.jit(lambda q, k, v: mla.attention(q, k, v, seg))
        loss = lambda q, k, v: jnp.sum(
            (mla.attention(q, k, v, seg) * cot).astype(jnp.float32))
        return fwd, jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    rows = []
    for name in args.documents.split(","):
        seg = jnp.asarray(segs[name])
        # the XLA route: what a TPU traced before the fused one
        real, mla.attention_route = mla.attention_route, (
            lambda *_: "xla_blocked")
        fwd, grad = forms(seg)
        want = jax.device_get((fwd(q, k, v),) + grad(q, k, v))
        rows.append({"documents": name, "route": "xla_blocked",
                     "query_block": mla.QUERY_BLOCK, "key_span": mla.KEY_SPAN,
                     "fwd_ms": 1e3 * _seconds(fwd, (q, k, v), args.calls),
                     "fwd_bwd_ms": 1e3 * _seconds(grad, (q, k, v),
                                                  args.calls)})
        mla.attention_route = real
        print(json.dumps(rows[-1]), flush=True)
        for tile in args.tiles.split(","):
            bq, bk = (int(x) for x in tile.split("x"))
            mla.FLASH_BLOCK_Q, mla.FLASH_BLOCK_K = bq, bk
            row = {"documents": name, "route": mla.attention_route(t, d, d),
                   "tile_q": bq, "tile_k": bk}
            try:
                fwd, grad = forms(seg)
                got = jax.device_get((fwd(q, k, v),) + grad(q, k, v))
                for what, a, b in zip(("o", "dq", "dk", "dv"), got, want):
                    a, b = (np.asarray(x, np.float32) for x in (a, b))
                    row[f"{what}_diff_over_mean"] = float(
                        np.abs(a - b).mean() / np.abs(b).mean())
                row["fwd_ms"] = 1e3 * _seconds(fwd, (q, k, v), args.calls)
                row["fwd_bwd_ms"] = 1e3 * _seconds(grad, (q, k, v),
                                                   args.calls)
            except Exception as e:  # noqa: BLE001 - a refused tile size is a row
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            rows.append(row)
            print(json.dumps(row), flush=True)
    dev = jax.devices()[0]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({
        "device": {"platform": dev.platform, "device_kind": dev.device_kind},
        "jax": jax.__version__, "tokens": t, "heads": h, "width": d,
        "rows": rows}, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
