#!/usr/bin/env python3
"""Bisect the flagship train step's wall time by component, on the chip.

Times each piece as a K-iteration ``lax.scan`` inside ONE XLA program with
fetch-based sync, so per-call dispatch overhead is out of every number.  Prints a JSON report:
fwd/bwd wall per component (LSTM, GNN, fuse, full), at the flagship
1024n/2048e bucket and the deployed 4096n/8192e bucket.

Usage: python benchmarks/profile_step.py [--platform cpu] [--k 16]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--buckets", default="1024,4096")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write the run's host spans as Chrome-trace JSON")
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    import jax.numpy as jnp
    import numpy as np

    from nerrf_tpu.bench.flops import analytic_flops
    from nerrf_tpu.data import make_corpus
    from nerrf_tpu.graph import GraphConfig
    from nerrf_tpu.models import JointConfig, NerrfNet
    from nerrf_tpu.models.graphsage import GraphSAGET
    from nerrf_tpu.models.lstm import ImpactLSTM
    from nerrf_tpu.tracing import DEFAULT_TRACER
    from nerrf_tpu.train import TrainConfig, build_dataset
    from nerrf_tpu.train.data import (DatasetConfig, padding_waste_fractions)
    from nerrf_tpu.train.loop import make_loss_fn, model_inputs

    log = lambda *a: print(*a, file=sys.stderr, flush=True)

    from nerrf_tpu.utils import fetch_value as fetch

    # constant per-call overhead (runtime dispatch + result fetch), measured
    # on a warm tiny program and subtracted from every timed leg below
    _tf = jax.jit(lambda x: x + 1.0)
    _tx = _tf(jnp.zeros((8,), jnp.float32))
    fetch(_tx)
    _t0 = time.perf_counter()
    for _ in range(4):
        fetch(_tf(_tx))
    rtt = (time.perf_counter() - _t0) / 4
    log(f"[profile] per-call overhead (warm RTT): {rtt * 1e3:.0f} ms")

    def timed(fn, *fargs, k=args.k, tag=""):
        """Wall seconds per iteration of fn, scanned k times in one program.

        fn must map its args to a pytree; we thread a float carry through a
        cheap dependency (sum of first output leaf) so XLA cannot hoist the
        body out of the scan, then fetch the carry.
        """

        @jax.jit
        def run(*xs):
            def body(c, _):
                # feed the carry back into an INPUT so the body is not
                # loop-invariant (else XLA's LICM could hoist fn out of the
                # scan and the timing would measure k float-adds): perturb
                # the first float leaf by c * 1e-30 — numerically nothing,
                # but data-dependent on the previous iteration
                def bump(leaf, done):
                    if not done[0] and hasattr(leaf, "dtype") and \
                            jnp.issubdtype(leaf.dtype, jnp.floating):
                        done[0] = True
                        return leaf + (c * 1e-30).astype(leaf.dtype)
                    return leaf

                flag = [False]
                xs_p = jax.tree_util.tree_map(lambda l: bump(l, flag), xs)
                out = fn(*xs_p)
                # consume EVERY output leaf: grad legs return a params-sized
                # pytree, and feeding only one leaf into the carry lets XLA
                # dead-code-eliminate the other parameters' backward matmuls
                # (r5 review catch — it underreported bwd by ~10x once)
                tot = sum(jnp.sum(l).astype(jnp.float32)
                          for l in jax.tree_util.tree_leaves(out)
                          if hasattr(l, "dtype"))
                return c + tot * 1e-9, ()

            c, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=k)
            return c

        # spans around both legs so --trace-out shows the timeline behind
        # every reported number (compile vs steady-state, per leg)
        slug = tag.replace(" ", "_").replace("+", "")
        t0 = time.perf_counter()
        with DEFAULT_TRACER.span(f"profile_compile_{slug}", k=k):
            fetch(run(*fargs))
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        with DEFAULT_TRACER.span(f"profile_{slug}", device=True, k=k):
            fetch(run(*fargs))
        per = max(time.perf_counter() - t0 - rtt, 1e-9) / k
        log(f"  {tag}: {per * 1e3:8.2f} ms/iter (compile {compile_s:.0f}s)")
        return per

    corpus = make_corpus(8, attack_fraction=0.5, base_seed=42,
                         duration_sec=180.0, num_target_files=24,
                         benign_rate_hz=40.0)
    report = {"backend": jax.default_backend(), "k": args.k,
              "per_call_overhead_ms": round(rtt * 1e3, 2), "buckets": {}}
    cfg = TrainConfig(model=JointConfig(), batch_size=8, num_steps=8, seed=0)
    model = NerrfNet(cfg.model)
    loss_fn = make_loss_fn(model, cfg)

    for bucket in (int(b) for b in args.buckets.split(",")):
        mn, me = bucket, bucket * 2
        log(f"[profile] bucket {mn}n/{me}e")
        ds = build_dataset(corpus, DatasetConfig(
            graph=GraphConfig(window_sec=45.0, stride_sec=15.0,
                              max_nodes=mn, max_edges=me),
            seq_len=100, max_seqs=128))
        arrs = ds.arrays
        batch = {k: jax.device_put(v[:8]) for k, v in arrs.items()}
        rng = jax.random.PRNGKey(0)
        params = model.init(
            rng, *(np.asarray(v[0]) for v in model_inputs(batch)),
            deterministic=True)["params"]
        params = jax.device_put(params)

        r = {}

        # full forward (loss)
        r["fwd_full_ms"] = timed(
            lambda p, b: loss_fn(p, b, rng)[0], params, batch,
            tag="fwd full") * 1e3
        # full fwd+bwd
        grad_fn = jax.grad(lambda p, b: loss_fn(p, b, rng)[0])
        r["step_fwdbwd_ms"] = timed(grad_fn, params, batch,
                                    tag="fwd+bwd full") * 1e3

        # LSTM alone (batched like the joint model: vmap over windows)
        lstm = ImpactLSTM(cfg.model.lstm)
        lp = jax.device_put(lstm.init(
            rng, np.asarray(batch["seq_feat"][0]),
            np.asarray(batch["seq_mask"][0]))["params"])

        def lstm_fwd(p, sf, sm):
            return jax.vmap(
                lambda f, m: lstm.apply({"params": p}, f, m)["seq_logit"]
            )(sf, sm).sum()

        r["fwd_lstm_ms"] = timed(lstm_fwd, lp, batch["seq_feat"],
                                 batch["seq_mask"], tag="fwd lstm") * 1e3
        r["bwd_lstm_ms"] = timed(jax.grad(lstm_fwd), lp, batch["seq_feat"],
                                 batch["seq_mask"], tag="fwd+bwd lstm") * 1e3

        # GNN alone
        gnn = GraphSAGET(cfg.model.gnn)
        gin = ("node_feat", "node_type", "node_aux", "node_mask", "edge_src",
               "edge_dst", "edge_feat", "edge_mask")
        gp = jax.device_put(gnn.init(
            rng, *(np.asarray(batch[k][0]) for k in gin))["params"])

        def gnn_fwd(p, *xs):
            return jax.vmap(
                lambda *a: gnn.apply({"params": p}, *a)["edge_logit"]
            )(*xs).sum()

        gxs = tuple(batch[k] for k in gin)
        r["fwd_gnn_ms"] = timed(gnn_fwd, gp, *gxs, tag="fwd gnn") * 1e3
        r["bwd_gnn_ms"] = timed(jax.grad(gnn_fwd), gp, *gxs,
                                tag="fwd+bwd gnn") * 1e3

        f = analytic_flops(grad_fn, params, batch)
        r["analytic_step_gflops"] = round(f / 1e9, 1) if f else None
        cell = {k: (round(v, 2) if isinstance(v, float) else v)
                for k, v in r.items()}
        # padded capacity IS compute cost at static shapes — the waste
        # fraction travels with every per-bucket time it explains
        cell["padding_waste"] = padding_waste_fractions(arrs)
        report["buckets"][f"{mn}n/{me}e"] = cell

    if args.trace_out:
        path = DEFAULT_TRACER.write(args.trace_out)
        log(f"[profile] host spans written to {path}")
    print(json.dumps(report, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
