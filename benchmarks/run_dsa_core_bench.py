"""Time `ops/dsa.py::sparse_attention` alone on the chip: forward, and forward
+ backward, of one packed sequence at a `dsa_moe` layer's widths (indexer,
selection, attention over the chosen keys, the indexer's loss), for the
blocked XLA route and for the fused route at a list of tile sizes; each fused
form is also held to the XLA route's outputs and gradients on the same
inputs, and to its chosen set.

    chiprun -- python3 benchmarks/run_dsa_core_bench.py \
        --tiles 512x512,256x512,512x1024 --out chiprun_out/dsa_core_bench.json

A tile is ``QxK``: the backward kernel's queries x both kernels' keys (the
forward kernel's queries are the scan's block, `dsa.QUERY_BLOCK`).  The table
of record is `benchmarks/results/dsa_core_bench_v5e.json`
(docs/kernel-paths.md); a kernel alone is not the step (PR 30): the whole
step is the cell's (`chipbench/run.py --workload stream-lm-8k-longdoc`).
Off a TPU the script exits 1: the fused route is the TPU's.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.run_mla_core_bench import _seconds  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="512x512",
                    help="comma-separated QxK tile sizes of the fused route")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--index-heads", type=int, default=16)
    ap.add_argument("--index-width", type=int, default=64)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--documents", default="one,packed",
                    help="one: a single document; packed: documents of "
                         "1000-3000 tokens and a padded tail")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/dsa_core_bench.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from nerrf_tpu.ops import dsa

    if jax.default_backend() != "tpu":
        print("the fused route is the TPU's: no TPU here", file=sys.stderr)
        return 1
    t, hq, hk, d = args.tokens, args.heads, args.kv_heads, args.width
    j, e = args.index_heads, args.index_width
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    draw = lambda key, shape, dt: jax.random.normal(key, shape, dt)
    q, cot = (draw(key, (t, hq, d), jnp.bfloat16) for key in keys[:2])
    k, v = (draw(key, (t, hk, d), jnp.bfloat16) for key in keys[2:4])
    qi = draw(keys[4], (t, j, e), jnp.float32)
    ki = draw(keys[5], (t, e), jnp.float32)
    wi = draw(keys[6], (t, j), jnp.float32) * (j * e) ** -0.5
    operands = (q, k, v, qi, ki, wi)
    rng = np.random.default_rng(0)
    segs = {"one": np.ones(t, np.int32)}
    lengths = rng.integers(1000, 3000, size=t // 1000)
    packed = np.repeat(np.arange(1, len(lengths) + 1), lengths)[:t - 300]
    segs["packed"] = np.concatenate(
        [packed, np.zeros(t - len(packed))]).astype(np.int32)

    def forms(seg):
        """-> (forward, gradients) of the route the module's tile sizes
        give, jitted afresh (the sizes are read when it is traced)."""
        attend = lambda *a: dsa.sparse_attention(*a, seg, topk=args.topk)
        fwd = jax.jit(attend)

        def loss(*a):
            o, kl, _ = attend(*a)
            return jnp.sum((o * cot).astype(jnp.float32)) + kl
        return fwd, jax.jit(jax.grad(loss, argnums=tuple(range(6))))

    names = ("o", "kl", "pairs", "dq", "dk", "dv", "dqi", "dki", "dwi")
    rows = []
    for name in args.documents.split(","):
        seg = jnp.asarray(segs[name])
        # the XLA route: what a TPU traced before the fused one
        real, dsa.attention_route = dsa.attention_route, (
            lambda *_: "xla_blocked")
        fwd, grad = forms(seg)
        want = jax.device_get(fwd(*operands) + grad(*operands))
        rows.append({"documents": name, "route": "xla_blocked",
                     "query_block": dsa.QUERY_BLOCK, "key_span": dsa.KEY_SPAN,
                     "fwd_ms": 1e3 * _seconds(fwd, operands, args.calls),
                     "fwd_bwd_ms": 1e3 * _seconds(grad, operands,
                                                  args.calls)})
        dsa.attention_route = real
        print(json.dumps(rows[-1]), flush=True)
        for tile in args.tiles.split(","):
            bq, bk = (int(x) for x in tile.split("x"))
            dsa.FLASH_BLOCK_Q, dsa.FLASH_BLOCK_K = bq, bk
            row = {"documents": name,
                   "route": dsa.attention_route(t, hq, hk, d),
                   "query_block": dsa.QUERY_BLOCK, "tile_q": bq, "tile_k": bk}
            try:
                fwd, grad = forms(seg)
                got = jax.device_get(fwd(*operands) + grad(*operands))
                for what, a, b in zip(names, got, want):
                    a, b = (np.asarray(x, np.float32) for x in (a, b))
                    row[f"{what}_diff_over_mean"] = float(
                        np.abs(a - b).mean() / max(np.abs(b).mean(), 1e-30))
                row["fwd_ms"] = 1e3 * _seconds(fwd, operands, args.calls)
                row["fwd_bwd_ms"] = 1e3 * _seconds(grad, operands,
                                                   args.calls)
            except Exception as err:  # noqa: BLE001 - a refused tile size is a row
                row["error"] = f"{type(err).__name__}: {str(err)[:300]}"
            rows.append(row)
            print(json.dumps(row), flush=True)
    dev = jax.devices()[0]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({
        "device": {"platform": dev.platform, "device_kind": dev.device_kind},
        "jax": jax.__version__, "tokens": t, "heads": hq, "kv_heads": hk,
        "width": d, "index_heads": j, "index_width": e, "topk": args.topk,
        "rows": rows}, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
