#!/usr/bin/env python3
"""Per-bucket aggregation microbenchmark: {segment, dense_adj, fused}.

The 28-layer GraphSAGE-T's neighbor aggregation is the hot op of every
forward the system runs, and `GraphSAGEConfig.aggregation="auto"` must route
each node bucket to the shape that actually wins there — a threshold that
should come from measured numbers, not the r5 anecdote.  This bench sweeps
the three parity-tested aggregation shapes (every one an XLA composition;
benchmarks/results/kernel_bench_v5e.json is the record of the sweeps that
also ran the hand-written kernels PR 31 deleted) across the deployment
buckets and records, per (mode, bucket):

  * per-layer aggregation time (one aggregation call == one layer's work),
  * the one-off per-forward precompute cost the mode amortizes over the
    28 layers (adjacency build / sorted-view normalization),
  * sequential device ops per layer — the quantity the r5 profile showed
    dominating at ~0.27 ms fixed cost per launch: segment ≈ 6 (2 gathers +
    2×2 segment-mean sums), dense_adj = 1 matmul, fused = 1
    `sage_aggregate` composition,
  * `kernel_path` (ops.active_impls()) so every number is attributed to the
    routes that actually served it (TpuGraphs' lesson, arXiv:
    2308.13490: a runtime number without its kernel config is unusable).

`--stack` runs the leg the `auto` crossover is read from, measured where
the rule is used, in place of the single call (which on a chip is over
before the host's round trip is: 1.2-3.3 ms at every bucket from 1024 to
16384, whatever the mode): per bucket and mode, one stack of `--layers`
aggregates (a GraphSAGE-T's worth), forward AND backward, under `vmap` over
a batch of `--batch` windows, the per-forward precompute (for dense_adj the
[N,N] scatter, transpose and cast) inside the timed program, with the
compiled program's planned bytes beside the times and a mode that does not
compile (out of memory) recorded as not fitting.

`--head-gather` is the sweep `ops.gather_rows`' route on a TPU was chosen
by (`ops.segment.SELECTION_MATMUL_MAX_ROWS`): the heads' two gathers of E
rows from an [N, 160] table and their adjoints, batch 8, on the compiler's
gather and on one selection matmul each way; its rows go under the
artifact's `head_gather` key.

Off-TPU the wall-clock columns are degraded (XLA-CPU serves all modes; the
artifact says so) but the op-count attribution and the O(N²)-vs-O(E)
work ratio still hold.  The `auto` routing threshold (`DENSE_ADJ_MAX_NODES`, nerrf_tpu/models/graphsage.py)
cites the artifact this script writes.

Usage:
  python benchmarks/run_kernel_bench.py --platform cpu \
      --out benchmarks/results/kernel_bench_cpu.json
  python benchmarks/run_kernel_bench.py --stack \
      --buckets 1024,2048,4096,8192,16384 \
      --out benchmarks/results/kernel_bench_v5e.json     # on the chip
  python benchmarks/run_kernel_bench.py --head-gather \
      --buckets 256,1024,2048,4096,8192,16384 \
      --out benchmarks/results/kernel_bench_v5e.json     # on the chip
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np

# sequential device ops per layer per mode — the launch-overhead
# attribution (segment: fwd gather + fwd sum + fwd denom + rev gather +
# rev sum + rev denom)
KERNELS_PER_LAYER = {"segment": 6, "dense_adj": 1, "fused": 1}


def _log(m):
    print(f"[kernel-bench] {m}", file=sys.stderr, flush=True)


def _graph(n, e, seed):
    """Synthetic window graph in the builder's layout: dst-sorted edges,
    causality-style weights with a masked tail (like padded edge slots)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = np.sort(rng.integers(0, n, e)).astype(np.int32)
    w = rng.uniform(0.1, 1.1, e).astype(np.float32)
    w[int(e * 0.9):] = 0.0  # ~10% padded slots
    return src, dst, w


def _time_fn(fn, arg, iters, fetch):
    t0 = time.perf_counter()
    fetch(fn(arg))
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fetch(fn(arg))
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3), round(compile_s, 3)


def bench_bucket(n, e, hidden, iters, dtype, fetch, report_rows):
    import jax
    import jax.numpy as jnp

    from nerrf_tpu.models.graphsage import (GraphSAGEConfig, dense_adjacency,
                                            fused_edge_views)
    from nerrf_tpu.ops import gather_rows, sage_aggregate, segment_mean

    src_np, dst_np, w_np = _graph(n, e, seed=n)
    order_np = np.argsort(src_np)
    src = jnp.asarray(src_np)
    dst = jnp.asarray(dst_np)
    w32 = jnp.asarray(w_np)
    msg = jnp.asarray(
        np.random.default_rng(n + 1).normal(size=(n, hidden)), dtype)
    w_dt = w32.astype(dtype)

    # --- segment: the 6-op per-layer path (SageBlock's shape) -----------
    src_sorted = jnp.asarray(src_np[order_np])
    dst_srcorder = jnp.asarray(dst_np[order_np])
    w_s = jnp.asarray(w_np[order_np]).astype(dtype)

    @jax.jit
    def agg_segment(m):
        a_f = segment_mean(gather_rows(m, src), dst, n, weights=w_dt,
                           sorted_ids=True)
        a_r = segment_mean(gather_rows(m, dst_srcorder), src_sorted, n,
                           weights=w_s, sorted_ids=True)
        return a_f + a_r

    # --- shared per-forward precompute: THE model's view builder ------------
    # (nerrf_tpu/models/graphsage.py fused_edge_views — timing a replica
    # would let the routing artifact drift from the shape the model runs)
    _views = jax.jit(lambda w: fused_edge_views(src, dst, w, n))
    fused_build_ms, _ = _time_fn(lambda w: _views(w)[0][-1], w32, iters,
                                 fetch)
    edges, _d_f, _d_r, inv_f, inv_r = _views(w32)

    # --- dense_adj: one [N,N]@[N,H] matmul per layer ------------------------
    # (the model's own build too: graphsage.py dense_adjacency)
    _build_adj = jax.jit(
        lambda w: dense_adjacency(src, dst, w, inv_f, inv_r, n, dtype))
    dense_build_ms, _ = _time_fn(_build_adj, w32, iters, fetch)
    adj = _build_adj(w32)
    agg_dense = jax.jit(lambda m: adj @ m)

    # --- fused: one sage_aggregate call per layer ---------------------------
    agg_fused = jax.jit(lambda m: sage_aggregate(m, *edges, n))

    modes = {}
    for name, fn in (("segment", agg_segment), ("dense_adj", agg_dense),
                     ("fused", agg_fused)):
        ms, compile_s = _time_fn(fn, msg, iters, fetch)
        modes[name] = {
            "ms_per_layer": round(ms, 3),
            "compile_s": compile_s,
            "kernels_per_layer": KERNELS_PER_LAYER[name],
        }
        _log(f"  n={n} {name}: {ms:.3f} ms/layer "
             f"({KERNELS_PER_LAYER[name]} kernel(s)/layer)")
    modes["dense_adj"]["per_forward_build_ms"] = round(dense_build_ms, 3)
    modes["dense_adj"]["adj_bytes"] = n * n * np.dtype(
        np.float32 if dtype == jnp.float32 else np.float16).itemsize
    modes["fused"]["per_forward_build_ms"] = round(fused_build_ms, 3)

    report_rows.append({
        "nodes": n, "edges": e, "hidden": hidden,
        "auto_resolves_to": GraphSAGEConfig().resolved_aggregation(n),
        "modes": modes,
    })


def bench_stack(n, e, hidden, layers, batch, iters, dtype):
    """-> {mode: {...}}: one stack of ``layers`` aggregates, forward and
    backward, over ``batch`` windows under `vmap` -- the GNN's aggregate as
    a train step runs it, and nothing else of the step.  Each mode's program
    builds its own per-forward views from (src, dst, w) inside the timed
    call, once a forward, so the adjacency's scatter and its bytes count on
    dense_adj's side; ``build_ms`` is that part alone, and ``ms_per_layer``
    what is left, a layer.  Timed to `block_until_ready`: fetching an
    [8, N, N] adjacency to the host would time the transfer.  A mode whose
    program the compiler refuses is recorded with ``fits: false`` and the
    refusal's first line."""
    import jax
    import jax.numpy as jnp

    from nerrf_tpu.models.graphsage import dense_adjacency, fused_edge_views
    from nerrf_tpu.ops import gather_rows, sage_aggregate, segment_mean

    graphs = [_graph(n, e, seed=n + 7 * b) for b in range(batch)]
    src, dst, w32 = (jnp.asarray(np.stack(col)) for col in zip(*graphs))
    rng = np.random.default_rng(n + 1)
    msg = jnp.asarray(rng.normal(size=(batch, n, hidden)), dtype)
    cot = jnp.asarray(rng.normal(size=(batch, n, hidden)), dtype)

    def views_dense(s, d, w):
        _, _, _, inv_f, inv_r = fused_edge_views(s, d, w, n)
        return dense_adjacency(s, d, w, inv_f, inv_r, n, dtype)

    def views_fused(s, d, w):
        return fused_edge_views(s, d, w, n)[0]

    def views_segment(s, d, w):
        order = jnp.argsort(s)
        return (s, d, w.astype(dtype), jnp.take(s, order),
                jnp.take(d, order), jnp.take(w, order).astype(dtype))

    def layer_segment(view, m):
        s, d, w, s_sorted, d_srcorder, w_s = view
        return (segment_mean(gather_rows(m, s), d, n, weights=w,
                             sorted_ids=True)
                + segment_mean(gather_rows(m, d_srcorder), s_sorted, n,
                               weights=w_s, sorted_ids=True))

    routes = {
        "segment": (views_segment, layer_segment),
        "dense_adj": (views_dense, lambda adj, m: adj @ m),
        "fused": (views_fused, lambda edges, m: sage_aggregate(m, *edges, n)),
    }

    def stack_of(views, layer):
        def one_window(s, d, w, m, c):
            view = views(s, d, w)

            def loss(m0):
                out = m0
                for _ in range(layers):
                    out = layer(view, out)
                return jnp.sum(out.astype(jnp.float32)
                               * c.astype(jnp.float32))

            return jax.value_and_grad(loss)(m)

        return jax.jit(jax.vmap(one_window))

    out = {}
    for name, (views, layer) in routes.items():
        try:
            compiled = stack_of(views, layer).lower(
                src, dst, w32, msg, cot).compile()
            mem = compiled.memory_analysis()
            stack_ms, _ = _time_fn(
                lambda m: compiled(src, dst, w32, m, cot), msg, iters,
                jax.block_until_ready)
            build = jax.jit(jax.vmap(views))
            build_ms, _ = _time_fn(lambda w: build(src, dst, w), w32, iters,
                                   jax.block_until_ready)
        except Exception as err:  # noqa: BLE001 - a sweep records and goes on
            line = (str(err).strip().splitlines() or [repr(err)])[0]
            out[name] = {"fits": False, "error": line[:300]}
            _log(f"  stack n={n} {name}: does not run: {line[:160]}")
            continue
        out[name] = {
            "fits": True,
            "stack_ms": round(stack_ms, 3),
            "build_ms": round(build_ms, 3),
            "ms_per_layer": round((stack_ms - build_ms) / layers, 4),
            "peak_bytes": int(mem.temp_size_in_bytes
                              + mem.argument_size_in_bytes
                              + mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
        }
        _log(f"  stack n={n} {name}: {stack_ms:.3f} ms fwd+bwd "
             f"({build_ms:.3f} build), "
             f"{out[name]['peak_bytes'] / 1e9:.2f} GB")
    ran = {m: r["stack_ms"] for m, r in out.items() if r["fits"]}
    return {"modes": out, "wins": min(ran, key=ran.get) if ran else None}


# --- the heads' row gather: which route serves ops.gather_rows on a TPU -----


def _gather_candidates():
    """{route: (gather for edge_src, gather for edge_dst)}: plain functions
    of (table [N,H], idx [E]) -> [E,H].  `xla` and `xla_selection_matmul`
    are the two routes `ops.gather_rows` chooses between, themselves, not
    replicas; the others are built from the same pieces and ship nowhere.
    Every one sums its adjoint in float32 (`ops.segment._f32_adjoint`): a
    bf16 scatter-add into a hub node would be a lower precision, not a
    faster gather."""
    import jax
    import jax.numpy as jnp

    from nerrf_tpu.ops import segment as seg

    def take(table, idx):
        return jnp.take(table, idx, axis=0)

    def scatter_add_sorted(g, idx, n):
        return jax.ops.segment_sum(g.astype(jnp.float32), idx,
                                   num_segments=n, indices_are_sorted=True)

    return {
        # (a) the compiler's gather and a float32 scatter-add; the second
        # declares the builder's dst order, which `gather_rows` is not told
        "xla": (seg._gather_take,) * 2,
        "xla_sorted_dst": (seg._gather_take,
                           seg._f32_adjoint(take, scatter_add_sorted)),
        # (b) one selection matmul each way: one bf16 pass, f32 accumulation
        "xla_selection_matmul": (seg._gather_select,) * 2,
        "xla_take_fwd_selection_adjoint": (seg._f32_adjoint(
            take, lambda g, idx, n: seg._select(idx, n, g, 0)),) * 2,
    }


def bench_head_gather(n, e, hidden, batch, reps, iters, dtype):
    """-> {route: {...}}: what `GraphSAGET`'s heads ask of `gather_rows`,
    and nothing else of the step: ``h_src = table[src]``, ``h_dst =
    table[dst]`` from one ``[n, hidden]`` table over ``batch`` windows under
    `vmap`, forward alone and forward + backward (cotangents as arguments,
    as in training), ``reps`` times over different data inside one program
    so that the host's round trip (1-3 ms a call) is a small part of what
    is timed.  Times are a repetition's; ``fwd_err`` is against `jnp.take`
    (0.0 = bit for bit), ``adjoint_err`` against a float32 scatter-add of
    the same cotangents, relative to its largest entry."""
    import jax
    import jax.numpy as jnp

    from nerrf_tpu.ops.segment import gather_rows_route

    rng = np.random.default_rng(n + 3)
    src = rng.integers(0, n, (batch, e)).astype(np.int32)
    dst = np.sort(rng.integers(0, n, (batch, e)), axis=1).astype(np.int32)
    dst[:, : e // 16] = 0          # a hub: e/16 in-edges on node 0
    tables = jnp.asarray(rng.normal(size=(reps, batch, n, hidden)), dtype)
    cots = jnp.asarray(rng.normal(size=(reps, 2, batch, e, hidden)), dtype)
    src, dst = jnp.asarray(src), jnp.asarray(dst)

    def programs(g_src, g_dst):
        def fwd_window(t, s, d):
            return g_src(t, s), g_dst(t, d)

        def both_window(t, s, d, c_s, c_d):
            # the gathered rows are outputs too: a gradient alone would let
            # the compiler drop the forward, which the adjoint never reads
            rows, pull = jax.vjp(lambda t0: fwd_window(t0, s, d), t)
            return rows, pull((c_s, c_d))[0]

        fwd = jax.jit(lambda ts: jax.lax.map(
            lambda t: jax.vmap(fwd_window)(t, src, dst), ts))
        both = jax.jit(lambda ts, cs: jax.lax.map(
            lambda tc: jax.vmap(both_window)(tc[0], src, dst, tc[1][0],
                                             tc[1][1]), (ts, cs)))
        return fwd, both

    want_fwd = want_adj = None
    out = {}
    for name, (g_src, g_dst) in _gather_candidates().items():
        try:
            fwd, both = programs(g_src, g_dst)
            c_fwd = fwd.lower(tables).compile()
            c_both = both.lower(tables, cots).compile()
            mem = c_both.memory_analysis()
            fwd_ms, _ = _time_fn(c_fwd, tables, iters, jax.block_until_ready)
            both_ms, _ = _time_fn(lambda ts: c_both(ts, cots), tables, iters,
                                  jax.block_until_ready)
            got_fwd = [np.asarray(x[0], np.float32) for x in c_fwd(tables)]
            got_adj = np.asarray(c_both(tables, cots)[1][0], np.float32)
        except Exception as err:  # noqa: BLE001 - a sweep records and goes on
            line = (str(err).strip().splitlines() or [repr(err)])[0]
            out[name] = {"fits": False, "error": line[:300]}
            _log(f"  head_gather n={n} {name}: does not run: {line[:160]}")
            continue
        if want_fwd is None:       # the host's own, once
            t0 = np.asarray(tables[0], np.float32)
            s_np, d_np = np.asarray(src), np.asarray(dst)
            want_fwd = [np.take_along_axis(t0, i[..., None], 1)
                        for i in (s_np, d_np)]
            want_adj = np.zeros((batch, n, hidden), np.float64)
            c0 = np.asarray(cots[0], np.float64)
            for b in range(batch):
                np.add.at(want_adj[b], s_np[b], c0[0, b])
                np.add.at(want_adj[b], d_np[b], c0[1, b])
        out[name] = {
            "fits": True,
            "fwd_ms": round(fwd_ms / reps, 4),
            "fwd_bwd_ms": round(both_ms / reps, 4),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "fwd_err": float(max(np.max(np.abs(g - w))
                                 for g, w in zip(got_fwd, want_fwd))),
            "adjoint_err": float(np.max(np.abs(got_adj - want_adj))
                                 / np.max(np.abs(want_adj))),
        }
        _log(f"  head_gather n={n} {name}: fwd {fwd_ms / reps:.3f} ms, "
             f"fwd+bwd {both_ms / reps:.3f} ms, "
             f"{mem.temp_size_in_bytes / 1e9:.2f} GB temp, "
             f"fwd_err {out[name]['fwd_err']:.1e} "
             f"adjoint_err {out[name]['adjoint_err']:.1e}")
    ran = {m: r["fwd_bwd_ms"] for m, r in out.items() if r["fits"]}
    return {"routes": out, "wins": min(ran, key=ran.get) if ran else None,
            "gather_rows_takes": gather_rows_route(n)}


def _dense_minus_fused(row):
    """dense_adj's time less fused's at one bucket, in ms: of the batched
    forward + backward stack where the sweep ran it (`--stack`: the rule's
    own use), else of the single forward call; None where either mode did
    not run there."""
    stack = (row.get("stack") or {}).get("modes")
    if stack:
        if not (stack["dense_adj"]["fits"] and stack["fused"]["fits"]):
            return None
        return stack["dense_adj"]["stack_ms"] - stack["fused"]["stack_ms"]
    return (row["modes"]["dense_adj"]["ms_per_layer"]
            - row["modes"]["fused"]["ms_per_layer"])


def measured_crossover(rows):
    """The smallest node count where the fused mode's time matches
    dense_adj's, log-interpolated between swept buckets — the number the
    `nerrf tune` kernel-routing prior cites.  None when one mode dominates
    every bucket both ran at (no crossing to cite: the prior then falls
    back on the authored constant)."""
    import math

    pts = sorted((r["nodes"], d) for r in rows
                 if (d := _dense_minus_fused(r)) is not None)
    prev = None
    for n, diff in pts:
        if prev is None and diff >= 0:
            return n  # dense already loses at the smallest swept bucket
        if prev is not None:
            n0, diff0 = prev
            if diff0 < 0 <= diff:
                t = -diff0 / (diff - diff0)
                return int(round(math.exp(
                    math.log(n0) + t * (math.log(n) - math.log(n0)))))
        prev = (n, diff)
    return None


def _head_gather_main(args, backend, dtype, t0, provenance) -> int:
    """The `--head-gather` leg: its rows replace the `head_gather` key of
    the artifact at --out and nothing else there (the aggregation sweep's
    rows are another run's record)."""
    import jax.numpy as jnp

    rows = [{"nodes": n, "edges": 2 * n, "hidden": args.hidden,
             **bench_head_gather(n, 2 * n, args.hidden, args.batch,
                                 args.reps, args.iters, dtype)}
            for n in (int(b) for b in args.buckets.split(","))]
    out = Path(args.out)
    report = json.loads(out.read_text()) if out.exists() else {}
    report["head_gather"] = {
        "backend": backend,
        "dtype": jnp.dtype(dtype).name,
        "batch": args.batch, "reps": args.reps, "iters": args.iters,
        "buckets": rows,
        "provenance": provenance,
        "wall_seconds": round(time.time() - t0, 1),
    }
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=2) + "\n")
    _log(f"wrote {out}")
    print(json.dumps({r["nodes"]: {m: v.get("fwd_bwd_ms") for m, v in
                                   r["routes"].items()} for r in rows}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="benchmarks/results/kernel_bench_cpu.json")
    ap.add_argument("--platform", default=None,
                    help="force a JAX platform before backend init "
                         "(same effect as JAX_PLATFORMS in the environment)")
    ap.add_argument("--buckets", default="256,1024,4096",
                    help="comma-separated node buckets (edges = 2×nodes, "
                         "the builder's capacity ratio)")
    ap.add_argument("--hidden", type=int, default=160,
                    help="message width (flagship hidden=160)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--stack", action="store_true",
                    help="time a whole stack of aggregates, forward and "
                         "backward, batched (the leg the auto crossover is "
                         "read from) in place of the single forward call, "
                         "which on a chip times the host's round trip")
    ap.add_argument("--head-gather", action="store_true",
                    help="time the heads' two row gathers and their "
                         "adjoints on every candidate route and write the "
                         "rows under the artifact's `head_gather` key, "
                         "keeping whatever else --out already holds")
    ap.add_argument("--reps", type=int, default=8,
                    help="gathers over different data inside one timed "
                         "program of --head-gather")
    ap.add_argument("--layers", type=int, default=28,
                    help="aggregates in the stack (flagship depth 28)")
    ap.add_argument("--batch", type=int, default=8,
                    help="windows under vmap in the stack (train batch 8)")
    args = ap.parse_args(argv)

    from nerrf_tpu.utils import enable_compilation_cache, fetch_value

    enable_compilation_cache()
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp

    from nerrf_tpu.models.graphsage import (DENSE_ADJ_MAX_NODES,
                                            GraphSAGEConfig)
    from nerrf_tpu.ops.segment import active_impls

    t0 = time.time()
    backend = jax.default_backend()
    dtype = jnp.bfloat16 if backend == "tpu" else jnp.float32
    _log(f"backend={backend} dtype={jnp.dtype(dtype).name}")

    provenance = ("python benchmarks/run_kernel_bench.py "
                  + " ".join(sys.argv[1:] if argv is None else argv))
    if args.head_gather:
        return _head_gather_main(args, backend, dtype, t0, provenance)

    rows = []
    for n in [int(b) for b in args.buckets.split(",")]:
        if args.stack:
            rows.append({
                "nodes": n, "edges": 2 * n, "hidden": args.hidden,
                "auto_resolves_to": GraphSAGEConfig().resolved_aggregation(n),
                "stack": dict(layers=args.layers, batch=args.batch,
                              **bench_stack(n, 2 * n, args.hidden,
                                            args.layers, args.batch,
                                            args.iters, dtype))})
        else:
            bench_bucket(n, 2 * n, args.hidden, args.iters, dtype,
                         fetch_value, rows)

    report = {
        "backend": backend,
        # off-TPU every mode is served by XLA-CPU: wall-clock columns rank
        # shapes on the wrong machine, so the chip-routing evidence is the
        # kernels_per_layer × ~0.27 ms launch cost + the work-ratio scaling
        # across buckets; re-run on chip for times of record
        "degraded": backend != "tpu",
        "dtype": jnp.dtype(dtype).name,
        "iters": args.iters,
        "kernel_path": active_impls(),
        "buckets": rows,
        "routing": {
            "auto_rule": "tpu: dense_adj if nodes <= dense_adj_max_nodes "
                         "else segment; off-tpu: segment",
            "dense_adj_max_nodes": DENSE_ADJ_MAX_NODES,
            "dense_adj_max_nodes_consumer":
                "nerrf_tpu/models/graphsage.py DENSE_ADJ_MAX_NODES (cites "
                "the chip's sweep, kernel_bench_v5e.json)",
            # the stamped crossover `nerrf tune` calibrates its routing
            # prior from (tune.costmodel.load_kernel_bench_crossover);
            # off-TPU it ranks XLA-CPU lowerings — directionally right
            # (O(N²) vs O(E)), degraded as evidence, superseded by a
            # chip re-run
            "measured_crossover_nodes": measured_crossover(rows),
            "crossover_basis": (
                "dense_adj vs fused stack_ms (forward + backward, batched, "
                "per-forward build included)" if args.stack else
                "dense_adj vs fused ms_per_layer") + ", log-interpolated "
                "between swept buckets",
        },
        "provenance": provenance,
        "wall_seconds": round(time.time() - t0, 1),
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=2) + "\n")
    _log(f"wrote {out}")
    print(json.dumps({
        "buckets": {r["nodes"]: {m: v.get("ms_per_layer") for m, v in
                                 (r.get("stack") or r)["modes"].items()}
                    for r in rows},
        "degraded": report["degraded"],
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
