"""GraphSAGE-T: temporal GraphSAGE edge/node anomaly classifier.

Realizes the reference's specified (never-implemented) GNN
(`/root/reference/docs/content/docs/architecture.mdx:45-53`: "GraphSAGE-T
(28 layers, 2M params)", task = classify edges as normal/attack, target
ROC-AUC ≥ 0.90) as a pure-JAX flax module, built TPU-first:

* message passing is a dense matmul + sorted segment reduction (the layout
  the graph builder guarantees), so the MXU does the FLOPs and aggregation is
  one pass handled by `nerrf_tpu.ops`, or on a TPU one more matmul against
  a per-window adjacency (`GraphSAGEConfig.aggregation`);
* all shapes are static (padded graphs with masks), so the whole forward jits
  once regardless of window content;
* compute runs in bfloat16 with float32 params (`dtype`/`param_dtype` split),
  the MXU-native precision;
* depth-28 residual blocks with pre-LayerNorm keep the deep spec trainable;
  default hidden width 160 puts the parameter count at ~2.2 M, matching the
  spec's "2M params".

The temporal "-T" aspect enters through edge/node features (window-relative
first/last-seen offsets, rates, spans — built in `graph/builder.py`) and a
sinusoidal encoding of the window's position in the stream.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from nerrf_tpu.graph.builder import AUX_VOCAB
from nerrf_tpu.ops import gather_rows, sage_aggregate, segment_mean

# The largest node bucket `auto` sends to the dense adjacency on a TPU: the
# largest at which it wins the whole train step and fits, as measured on a
# v5e in benchmarks/results/kernel_bench_v5e.json (`python
# benchmarks/run_kernel_bench.py --stack`: a stack of 28 aggregates, forward
# and backward, vmap batch 8, H = 160, bf16, e = 2n, the [N,N] build inside).
# No crossover in time was found: dense_adj beat what the sweep ran beside
# it (the hand-written kernels that `fused` and `segment` rode on a TPU until
# PR 31) by 30x / 25x / 12x / 10x and more at 1024 / 2048 / 4096 / 8192 (the
# whole step, PERF.md §6 PR 27: 160 ms against 549 at 4096, 459 against
# 1,522 at 8192).  Memory sets the bound: at 8192 the step holds 9.1 GB of
# 16 (an [8,N,N] f32 scatter, its transpose and the bf16 adjacency kept for
# the backward); at 16384 the compiler refuses the batch-8 stack (20.0 GB),
# so `segment` serves there.  Move this only with a new sweep.
DENSE_ADJ_MAX_NODES = 8192


def fused_edge_views(edge_src, edge_dst, w32, num_nodes):
    """Per-forward normalized edge views for the aggregation modes that
    do one op a layer — THE single definition of the precompute both
    `GraphSAGET` and the kernel microbenchmark
    (benchmarks/run_kernel_bench.py) run, so the artifact the `auto`
    routing threshold cites cannot drift from the shape the model
    executes.

    Returns ``(edges, d_fwd, d_rev, inv_f, inv_r)`` where ``edges`` is the
    8-tuple `ops.sage_aggregate` takes (both sorted edge orders, each
    direction's pre-normalized weights ``ŵ = w·inv`` in both orders) and
    ``d``/``inv`` are the per-node weight totals / safe inverses (the
    dense path's row/col normalizations; the e_emb/bias folding reuses
    them).  ``edge_dst`` must be the builder's sorted-by-dst ids and
    ``w32`` float32 edge weights with masked edges already zeroed."""
    d_fwd = jax.ops.segment_sum(w32, edge_dst, num_segments=num_nodes,
                                indices_are_sorted=True)
    d_rev = jax.ops.segment_sum(w32, edge_src, num_segments=num_nodes)
    inv_f = 1.0 / jnp.maximum(d_fwd, 1e-6)
    inv_r = 1.0 / jnp.maximum(d_rev, 1e-6)
    src_order = jnp.argsort(edge_src)
    wf_d = w32 * jnp.take(inv_f, edge_dst)
    wr_d = w32 * jnp.take(inv_r, edge_src)
    edges = (edge_dst,                          # nondecreasing dst ids
             edge_src,                          # message source per edge
             jnp.take(edge_src, src_order),     # nondecreasing src ids
             jnp.take(edge_dst, src_order),     # message source, src order
             wf_d,
             jnp.take(wf_d, src_order),
             jnp.take(wr_d, src_order),
             wr_d)
    return edges, d_fwd, d_rev, inv_f, inv_r


def dense_adjacency(edge_src, edge_dst, w32, inv_f, inv_r, num_nodes, dtype):
    """The `dense_adj` route's per-forward build (shared, like
    `fused_edge_views`, with benchmarks/run_kernel_bench.py, whose sweep
    sets DENSE_ADJ_MAX_NODES): one [E]→[N·N] scatter builds the raw
    weighted adjacency, whose form normalized in f32 by both directions'
    inverses and cast to ``dtype`` serves every layer as one
    [N,N]@[N,H] matmul."""
    n = num_nodes
    flat = edge_dst.astype(jnp.int32) * n + edge_src.astype(jnp.int32)
    w_raw = jax.ops.segment_sum(w32, flat, num_segments=n * n).reshape(n, n)
    return (w_raw * inv_f[:, None] + w_raw.T * inv_r[:, None]).astype(dtype)


@dataclasses.dataclass(frozen=True)
class GraphSAGEConfig:
    hidden: int = 160
    num_layers: int = 28
    dropout: float = 0.1
    dtype: Any = jnp.bfloat16
    # Three parity-tested aggregation shapes (docs/kernel-paths.md), every
    # one of them compiler-written on every backend.  "dense_adj": ONE
    # [N,N]@[N,H] matmul per layer against a normalized adjacency built once
    # per forward: pure MXU work, O(N²·H), and on a v5e the fastest at every
    # bucket it fits (0.04-2.7 ms a layer, forward + backward, batch 8, from
    # 1024 to 8192 nodes: benchmarks/results/kernel_bench_v5e.json).
    # "segment": per-layer row gather + weighted segment mean, O(E); what
    # every backend but a TPU runs, and a TPU past DENSE_ADJ_MAX_NODES.
    # "fused": the same sums over edge views normalized once per forward
    # (ops.sage_aggregate), so a layer is two gathers and two scatter-adds
    # with no division; kept because the benchmark's reference test names
    # it (ROADMAP.md, named debts).  "auto" (default): on a TPU dense_adj up
    # to DENSE_ADJ_MAX_NODES, segment everywhere else.
    aggregation: str = "auto"
    # Per-rung kernel routing table fitted by `nerrf tune` (docs/tuning.md):
    # sorted ((max_nodes, mode), ...) pairs consulted BEFORE the auto
    # constant — the smallest entry whose max_nodes covers the padded node
    # bucket wins, buckets past the table fall through to the auto rule.
    # None (the default) keeps the single measured DENSE_ADJ_MAX_NODES
    # constant, so untuned deployments are bit-for-bit what they were.
    # The table rides repr(), so serve_program_key / the compile cache key
    # change with it — a tuned routing can never collide with an untuned
    # executable.
    routing: Optional[Tuple[Tuple[int, str], ...]] = None

    def __post_init__(self):
        # canonicalize the routing table (JSON round-trips hand back
        # lists; repr() is cache-key material, so the shape must be ONE
        # shape) and reject junk at construction, not trace time
        if self.routing is not None:
            table = tuple(sorted((int(cap), str(mode))
                                 for cap, mode in self.routing))
            for cap, mode in table:
                if mode not in ("fused", "dense_adj", "segment"):
                    raise ValueError(
                        f"unknown aggregation {mode!r} in routing table; "
                        "expected 'fused', 'dense_adj' or 'segment'")
                if cap <= 0:
                    raise ValueError(
                        f"routing table max_nodes must be positive, "
                        f"got {cap}")
            object.__setattr__(self, "routing", table)

    @property
    def small(self) -> "GraphSAGEConfig":
        """A CPU-test-sized variant (same code path, tiny shapes)."""
        return dataclasses.replace(self, hidden=32, num_layers=4)

    def resolved_aggregation(self, num_nodes: int | None = None) -> str:
        """The aggregation mode the forward actually uses on this
        process's default backend — the single definition of the "auto"
        rule (the model and the bench's kernel_path attribution both call
        this, so the artifact cannot drift from the compute).  ``num_nodes``
        is the padded node bucket: a tuned per-rung routing table (see
        ``routing``) wins first; otherwise on TPU, `auto` takes the dense
        adjacency wherever it was measured to win the step and fit
        (≤ DENSE_ADJ_MAX_NODES — see the constant); bigger buckets, a call
        with no bucket given and every other backend get `segment`."""
        if self.aggregation != "auto":
            if self.aggregation not in ("fused", "dense_adj", "segment"):
                raise ValueError(
                    f"unknown aggregation {self.aggregation!r}; expected "
                    "'auto', 'fused', 'dense_adj' or 'segment'")
            return self.aggregation
        if self.routing and num_nodes is not None:
            for cap, mode in self.routing:  # sorted: smallest cover wins
                if num_nodes <= cap:
                    return mode
        if (jax.default_backend() == "tpu" and num_nodes is not None
                and num_nodes <= DENSE_ADJ_MAX_NODES):
            return "dense_adj"
        return "segment"


class SageBlock(nn.Module):
    """One residual GraphSAGE block: pre-LN, bidirectional mean aggregation.

    Forward (src→dst) and reverse (dst→src) neighborhoods are aggregated with
    shared message weights plus a per-direction bias, then combined with the
    self path.  Reverse flow matters here: an attack process node must hear
    from the files it touched and vice versa.
    """

    hidden: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h, e_emb, edge_src, edge_dst, edge_w, num_nodes,
                 rev_view=None, dense_view=None, fused_view=None):
        hn = nn.LayerNorm(dtype=self.dtype, name="ln")(h)
        msg = nn.Dense(self.hidden, dtype=self.dtype, name="w_msg")(hn)
        dir_bias = self.param(
            "dir_bias", nn.initializers.zeros, (2, self.hidden), jnp.float32
        ).astype(self.dtype)
        if fused_view is not None:
            # fused aggregation: the whole bidirectional weighted mean of
            # `msg` is ONE sage_aggregate call over GraphSAGET's
            # pre-normalized sorted edge views — same decomposition as the
            # dense path below (e_emb's mean in c_sum, the empty-segment
            # zeroing in s_f/s_r), but O(E) work and no [N,N]
            # materialization
            edges, c_sum, s_f, s_r = fused_view
            agg = (sage_aggregate(msg, *edges, num_nodes) + c_sum
                   + dir_bias[0] * s_f[:, None] + dir_bias[1] * s_r[:, None])
            upd = nn.Dense(self.hidden, dtype=self.dtype, name="w_self")(
                jnp.concatenate([hn, agg], axis=-1)
            )
            return h + nn.gelu(upd)
        if dense_view is not None:
            # dense-adjacency aggregation: same weighted-mean math as the
            # segment path below, but the whole bidirectional aggregate is
            # ONE [N,N]@[N,H] matmul against the per-forward normalized
            # adjacency (GraphSAGET precomputes it; e_emb's mean lives in
            # c_sum, and s_f/s_r carry the empty-segment zeroing the
            # segment path gets from its max(denom, eps) guard)
            adj, c_sum, s_f, s_r = dense_view
            # the scope `ops.sage_aggregate` carries: the same work under
            # the same name in a device trace, whichever route served it
            with jax.named_scope("sage_aggregate"):
                agg = adj @ msg
            agg = (agg + c_sum
                   + dir_bias[0] * s_f[:, None] + dir_bias[1] * s_r[:, None])
            upd = nn.Dense(self.hidden, dtype=self.dtype, name="w_self")(
                jnp.concatenate([hn, agg], axis=-1)
            )
            return h + nn.gelu(upd)
        # src→dst messages land on dst (builder-sorted ids)
        m_fwd = gather_rows(msg, edge_src) + e_emb + dir_bias[0]
        agg_fwd = segment_mean(m_fwd, edge_dst, num_nodes, weights=edge_w, sorted_ids=True)
        if rev_view is not None:
            # dst→src messages, iterated in src-sorted edge order (the
            # per-window argsort view GraphSAGET precomputes) so this
            # direction's ids are sorted too; summation order differs only
            # by a permutation
            src_sorted, dst_srcorder, e_emb_s, w_s = rev_view
            m_rev = gather_rows(msg, dst_srcorder) + e_emb_s + dir_bias[1]
            agg_rev = segment_mean(m_rev, src_sorted, num_nodes, weights=w_s,
                                   sorted_ids=True)
        else:
            # dst→src messages land on src (unsorted ids)
            m_rev = gather_rows(msg, edge_dst) + e_emb + dir_bias[1]
            agg_rev = segment_mean(m_rev, edge_src, num_nodes, weights=edge_w,
                                   sorted_ids=False)
        upd = nn.Dense(self.hidden, dtype=self.dtype, name="w_self")(
            jnp.concatenate([hn, agg_fwd + agg_rev], axis=-1)
        )
        return h + nn.gelu(upd)


class GraphSAGET(nn.Module):
    """Edge + node anomaly scorer over one padded window graph.

    Inputs are the `GraphBatch` arrays (single window; vmap for batches).
    Returns dict with `edge_logit` [E], `node_logit` [N], `node_emb` [N, H].
    """

    cfg: GraphSAGEConfig

    @nn.compact
    def __call__(
        self,
        node_feat,  # [N, F_n] float32
        node_type,  # [N] int32
        node_aux,   # [N] int32 identity bucket (extension / comm hash)
        node_mask,  # [N] bool
        edge_src,   # [E] int32
        edge_dst,   # [E] int32 (sorted)
        edge_feat,  # [E, F_e] float32
        edge_mask,  # [E] bool
        *,
        deterministic: bool = True,
    ) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        n = node_feat.shape[0]
        dt = cfg.dtype

        with jax.named_scope("encoders"):
            type_emb = nn.Embed(4, cfg.hidden, dtype=dt, name="type_emb")(node_type)
            aux_emb = nn.Embed(AUX_VOCAB, cfg.hidden, dtype=dt, name="aux_emb")(node_aux)
            h = nn.Dense(cfg.hidden, dtype=dt, name="node_enc")(node_feat.astype(dt))
            h = nn.gelu(h + type_emb + aux_emb)
            h = h * node_mask[:, None].astype(dt)

            e_emb = nn.Dense(cfg.hidden, dtype=dt, name="edge_enc")(edge_feat.astype(dt))
            e_emb = nn.gelu(e_emb)
        # causality weight (edge_feat[:, 12]) gates messages; masked edges → 0
        w32 = (edge_feat[:, 12] + 0.1) * edge_mask.astype(jnp.float32)
        edge_w = w32.astype(dt)

        rev_view = dense_view = fused_view = None
        agg_mode = cfg.resolved_aggregation(n)
        # everything the layers share is computed once per forward, under
        # one scope (the layers' own scopes hold the per-layer work alone)
        with jax.named_scope("agg_views"):
            if agg_mode in ("dense_adj", "fused"):
                # Per-forward aggregation state shared by all layers, so each
                # of the 28 layers costs ONE op (a matmul or a
                # sage_aggregate) with no normalize on the layer critical
                # path.  fused_edge_views is the shared precompute
                # (normalizations + both sorted pre-weighted edge orders);
                # the (layer-invariant) e_emb term folds into c_sum, and
                # s_f/s_r carry the empty-segment zeroing the segment path
                # gets from its max(denom, eps) guard.
                edges, d_fwd, d_rev, inv_f, inv_r = fused_edge_views(
                    edge_src, edge_dst, w32, n)
                we = w32[:, None] * e_emb.astype(jnp.float32)
                c_f = jax.ops.segment_sum(we, edge_dst, num_segments=n,
                                          indices_are_sorted=True)
                c_r = jax.ops.segment_sum(we, edge_src, num_segments=n)
                c_sum = (c_f * inv_f[:, None] + c_r * inv_r[:, None]).astype(dt)
                s_f = (d_fwd * inv_f).astype(dt)
                s_r = (d_rev * inv_r).astype(dt)
            if agg_mode == "dense_adj":
                adj = dense_adjacency(edge_src, edge_dst, w32, inv_f, inv_r,
                                      n, dt)
                dense_view = (adj, c_sum, s_f, s_r)
            elif agg_mode == "fused":
                fused_view = (edges, c_sum, s_f, s_r)
            elif agg_mode == "segment":
                # src-sorted edge view, computed once and shared by every layer:
                # with it the reverse aggregation also declares sorted ids
                # (one [E] argsort per window; whether `indices_are_sorted`
                # pays for it is ROADMAP.md's named debt iii)
                src_order = jnp.argsort(edge_src)
                rev_view = (
                    jnp.take(edge_src, src_order),   # nondecreasing segment ids
                    jnp.take(edge_dst, src_order),   # message source per edge
                    jnp.take(e_emb, src_order, axis=0),
                    jnp.take(edge_w, src_order),
                )
            else:
                raise ValueError(f"unknown aggregation mode {agg_mode!r}")

        # named scopes mirror the host tracing spine: XLA trace rows for
        # each layer show up as gnn_layer_<i> in Perfetto, next to the
        # train_step_call host span that dispatched them
        for i in range(cfg.num_layers):
            with jax.named_scope(f"gnn_layer_{i}"):
                h = SageBlock(cfg.hidden, dtype=dt, name=f"block_{i}")(
                    h, e_emb, edge_src, edge_dst, edge_w, n,
                    rev_view=rev_view, dense_view=dense_view,
                    fused_view=fused_view
                )
                h = h * node_mask[:, None].astype(dt)

        with jax.named_scope("gnn_heads"):
            h = nn.LayerNorm(dtype=dt, name="final_ln")(h)
            if cfg.dropout > 0:
                h = nn.Dropout(cfg.dropout, deterministic=deterministic)(h)

            node_logit = nn.Dense(
                1, dtype=jnp.float32, name="node_head")(h)[:, 0]

            # a scope of their own inside the heads': a device trace names
            # the two edge-row gathers (and, under transpose(jvp(...)),
            # their adjoints) whatever route `ops.gather_rows` takes
            with jax.named_scope("row_gather"):
                h_src = gather_rows(h, edge_src)
                h_dst = gather_rows(h, edge_dst)
            pair = jnp.concatenate(
                [h_src, h_dst, h_src * h_dst, e_emb], axis=-1)
            z = nn.gelu(
                nn.Dense(cfg.hidden, dtype=dt, name="edge_head_1")(pair))
            edge_logit = nn.Dense(
                1, dtype=jnp.float32, name="edge_head_2")(z)[:, 0]

        return {
            "edge_logit": jnp.where(edge_mask, edge_logit, -30.0),
            "node_logit": jnp.where(node_mask, node_logit, -30.0),
            "node_emb": h.astype(jnp.float32),
        }


def count_params(params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
