"""Time `ops/kda.py::chunk_delta` alone on the chip: forward, and forward +
backward, of one packed sequence at Kimi-Linear's delta-rule widths (32
heads of 128 x 128, 8192 tokens, documents starting inside chunks), for
the chunked XLA route and for the fused route at a list of (kernel chunk,
head block) sizes; each fused form is also held to the XLA route's output
and five gradients on the same inputs; each form, the XLA route too, is
held to the XLA route at ``highest`` (float32 products: the truth the bf16
products round away from).

    python3 benchmarks/run_kda_bench.py \
        --blocks 64x4,32x4,64x8 --out chiprun_out/kda_core_bench.json  # one chip

The table of record is `benchmarks/results/kda_core_bench_v5e.json`
(docs/kernel-paths.md); a kernel alone is not the step: the whole step is
the cell `stream-lm-8k-kda-packed`.  Off a TPU the script exits 1: the
fused route is the TPU's.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


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
    ap.add_argument("--blocks", default="64x4",
                    help="comma-separated CHUNKxHEADS sizes of the fused "
                         "route")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/kda_core_bench.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from nerrf_tpu.ops import kda

    if jax.default_backend() != "tpu":
        print("the fused route is the TPU's: no TPU here", file=sys.stderr)
        return 1
    t, h, d = args.tokens, args.heads, args.width
    rng = np.random.default_rng(0)
    # about 5.4 documents a sequence, as the cell's packed traffic
    lengths = rng.integers(200, 2800, size=64)
    seg = np.repeat(np.arange(1, 65), lengths)[:t].astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    ins = ((unit(jax.random.normal(keys[0], (1, t, h, d))) * d ** -0.5
            ).astype(jnp.bfloat16),
           unit(jax.random.normal(keys[1], (1, t, h, d))).astype(
               jnp.bfloat16),
           jax.random.normal(keys[2], (1, t, h, d), jnp.bfloat16),
           # the layer's decay: -exp(A_log) softplus(.), A_log = log(1..16)
           -jnp.exp(jnp.log(jnp.linspace(1.0, 16.0, h)))[:, None]
           * jax.nn.softplus(jax.random.normal(keys[3], (1, t, h, d)) - 5.0),
           jax.nn.sigmoid(jax.random.normal(keys[4], (1, t, h))),
           jnp.asarray(seg[None]))
    # the cotangent rides as an argument: a jitted closure would embed it
    # in the program as a constant
    ins += (jax.random.normal(keys[5], (1, t, h, d)),)
    rule = jax.vmap(kda.chunk_delta)

    def forms():
        """-> (forward, value and gradients), jitted afresh (the route and
        the block sizes are read when it is traced)."""
        fwd = jax.jit(lambda *a: rule(*a[:6]))
        both = jax.jit(lambda *a: jax.value_and_grad(
            lambda *v: jnp.sum(rule(*v, a[5]).astype(jnp.float32) * a[6]),
            argnums=range(5))(*a[:5]))
        return fwd, both

    def outputs(fwd, both):
        return jax.device_get((fwd(*ins),) + tuple(both(*ins)[1]))

    def against(row, got, ref, label):
        for what, a, b in zip(NAMES, got, ref):
            a, b = (np.asarray(v, np.float32) for v in (a, b))
            row[f"{what}_{label}_over_mean"] = float(
                np.abs(a - b).mean() / np.abs(b).mean())
            row[f"{what}_max{label}_over_max"] = float(
                np.abs(a - b).max() / np.abs(b).max())

    rows = []
    real = kda.delta_route
    kda.delta_route = lambda *_: "xla_chunked"
    with jax.default_matmul_precision("highest"):
        truth = outputs(*forms())
    fwd, both = forms()
    want = outputs(fwd, both)
    rows.append({"route": "xla_chunked", "chunk": kda.CHUNK})
    against(rows[-1], want, truth, "err")
    rows[-1]["fwd_ms"] = 1e3 * _seconds(fwd, ins, args.calls)
    rows[-1]["fwd_bwd_ms"] = 1e3 * _seconds(both, ins, args.calls)
    kda.delta_route = real
    print(json.dumps(rows[-1]), flush=True)
    for block in args.blocks.split(","):
        kda.KERNEL_CHUNK, kda.HEAD_BLOCK = (int(v) for v in block.split("x"))
        row = {"route": kda.delta_route(t, d, d),
               "chunk": kda.KERNEL_CHUNK, "heads": kda.HEAD_BLOCK}
        try:
            fwd, both = forms()
            got = outputs(fwd, both)
            against(row, got, want, "diff")
            against(row, got, truth, "err")
            row["fwd_ms"] = 1e3 * _seconds(fwd, ins, args.calls)
            row["fwd_bwd_ms"] = 1e3 * _seconds(both, ins, args.calls)
        except Exception as e:  # noqa: BLE001 - a refused size is a row
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        rows.append(row)
        print(json.dumps(row), flush=True)
    dev = jax.devices()[0]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({
        "device": {"platform": dev.platform, "device_kind": dev.device_kind},
        "jax": jax.__version__, "tokens": t, "heads": h, "width": d,
        "documents": int(len(np.unique(seg))), "rows": rows}, indent=2)
        + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
