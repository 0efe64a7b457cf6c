"""StreamNet: long-context event-stream encoder over whole traces.

Complements the spec'd models: GraphSAGE-T scores edges within a 30–60 s
window and the BiLSTM scores the last 100 events of one file
(`/root/reference/docs/content/docs/architecture.mdx:45-59`) — both are
bounded-context.  StreamNet reads the *entire* event stream of a trace
(causally: each event sees all history), so cross-window, slow-burn
attack structure — recon minutes before encryption, a ransom-note write long
after — is visible to a single model.  The reference never built a
long-context path (SURVEY.md §5 "Long-context"); this is ours.

The backbone is a stack built from a list of layer kinds
(``StreamConfig.kinds``; docs/stream-backbone.md):

* ``block`` — the original pre-LN causal transformer block (one fused
  ``qkv``, GELU MLP), attention through `parallel/ring.py`, so with an
  ``sp`` mesh axis the time axis shards over chips.  ``kinds=None`` is
  ``num_layers`` of these: the per-event detector (features in, one BCE
  logit an event out).
* ``mamba``, ``swa``, ``full``, ``gmu``, ``cross`` — the decoder-hybrid-
  decoder layers (selective scan; differential attention inside a window,
  over the whole document, and onto another layer's keys and values; gated
  memory unit), each followed by a SwiGLU MLP.  Two values flow **down**
  the stack beside the residual: the scan output ``m`` of the last ``mamba``
  layer before the ``full`` layer, which every ``gmu`` gates, and the
  ``full`` layer's projected ``K, V``, which every ``cross`` layer attends
  to.  They are outputs of their layer's `nn.remat` and inputs of their
  readers', so rematerialization carries them.  These kinds read event
  *tokens* (``vocab_size`` > 0: an embedding tied to the output head) in
  packed sequences with segment ids, train on the next token, and run on
  one chip's local attention path (``sp`` > 1 is refused by the ring).
* ``dsa_moe`` — a sparse-attention, sparse-expert decoder layer (RMSNorm;
  grouped-query attention with per-head q/k norm and rotary positions that
  restart at each packed document, over the ``index_topk`` keys a learned
  indexer chooses for each query: `ops/dsa.py`; then a router over
  ``num_experts`` experts, ``experts_per_token`` a token, of which this
  chip computes the ``held_experts`` from ``first_expert`` on:
  `ops/moe.py`).  It reads event tokens like the hybrid kinds, ends in an
  RMSNorm and, with ``tie_head`` false, a head of its own; beside the
  residual it returns the indexer's loss and its routing and selection
  counts, which `StreamNet` sums into ``aux``.
* ``mla_dense``, ``mla_moe`` — one decoder layer (`_LatentLayer`) with
  latent attention (queries through a ``q_lora_rank`` bottleneck with a norm
  inside it, keys and values rebuilt from one ``kv_lora_rank`` latent a
  token, one rotary key shared by all heads: `ops/mla.py`) and, after it, a
  dense SwiGLU of ``mlp_dim`` (``mla_dense``: the stack's leading layers)
  or sigmoid-routed experts beside a shared expert every token passes
  (``mla_moe``: `ops/moe.py::route_sigmoid` in front of the dispatch the
  ``dsa_moe`` kind uses; the router's correction bias is a parameter no
  gradient reaches and the optimizer never moves).  A stack of these is not
  uniform: ``kinds`` lists it.  With ``mtp_layers`` 1 a multi-token-
  prediction module follows the stack: the stack's output before the final
  norm and the embedding of the NEXT token, each normalised, joined and
  projected back to ``dim``, one more ``mla_moe`` layer under the same
  masks, a norm, and the main model's head (`mtp_loss`: targets two ahead).
  The embedding and the head are the main model's: two uses, one gradient.
* ``gqa_full_dense``, ``gqa_full_moe``, ``gqa_swa_moe`` — one grouped-query
  decoder layer (`_GroupedLayer`): ``num_kv_heads`` key-value heads shared
  by groups of query heads, a sigmoid gate a query head on the attention's
  output, the rotary embedding of its kind (``rope_full``: a share of a
  head's dimensions, YaRN's frequencies; ``rope_window``), over the whole
  document with ``num_heads`` query heads (``gqa_full_*``) or inside
  ``window`` with ``window_heads`` (``gqa_swa_*``), all through
  `ops/mla.py::attention`; then a dense SwiGLU (``_dense``) or the softmax
  router's held experts, times ``router_scale``, beside a shared expert
  (``_moe``).  Beside the routing counts its ``aux`` carries the pairs each
  kind attended (``window_pairs``, ``full_pairs``).

The ``block`` and hybrid kinds carry no positional encoding (event streams
are irregularly sampled — wall-clock gaps carry signal, so Δt enters as a
feature or a token, not a position index); ``dsa_moe``, the latent and the
grouped-query kinds carry their source's rotary embedding, counted inside a
document.  bfloat16 compute, float32 parameters.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from nerrf_tpu.ops import dsa, mla, moe
from nerrf_tpu.ops.ssm import causal_conv1d, selective_scan
from nerrf_tpu.parallel.ring import ring_self_attention

HYBRID_KINDS = ("mamba", "swa", "full", "gmu", "cross")
SPARSE_KIND = "dsa_moe"
LATENT_KINDS = ("mla_dense", "mla_moe")
# grouped-query attention over the whole document or inside the window, then
# a dense SwiGLU or routed experts beside a shared one
GQA_KINDS = ("gqa_full_dense", "gqa_full_moe", "gqa_swa_moe")
WINDOW_KINDS = ("gqa_swa_moe",)
# the kinds that end in routed experts
ROUTED_KINDS = (SPARSE_KIND, "mla_moe", "gqa_full_moe", "gqa_swa_moe")


def layer_kinds(num_layers: int) -> Tuple[str, ...]:
    """The decoder-hybrid-decoder stack at ``num_layers``.  32 is the
    published one: layers 0-15 ``mamba, swa`` alternating, 16 ``mamba`` (the
    source of ``m``), 17 ``full`` (the source of ``K, V``), 18-31 ``gmu,
    cross`` alternating.  6 is its cut to one period of each half with both
    hand-downs: every kind, in the published order."""
    if num_layers == 32:
        return (("mamba", "swa") * 8 + ("mamba", "full")
                + ("gmu", "cross") * 7)
    if num_layers == 6:
        return ("mamba", "swa", "mamba", "full", "gmu", "cross")
    raise ValueError(f"no decoder-hybrid-decoder stack of {num_layers} "
                     "layers is defined (32 as published, 6 as cut)")


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One attention kind's rotary embedding: its base, the share of a
    head's dimensions that turn (the first ones), and YaRN where
    ``yarn_factor`` > 1: the frequencies blended over ``yarn_original``
    positions between ``beta_fast`` and ``beta_slow`` turns
    (`ops/dsa.py::yarn_frequencies`), the cosines and sines times
    ``attention_factor``."""

    theta: float = 1e4
    fraction: float = 1.0
    yarn_factor: float = 1.0
    yarn_original: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def apply(self, x, pos):
        """``x`` [T, heads, d], positions ``pos`` [T] -> ``x`` turned."""
        d = x.shape[-1]
        rotary = int(d * self.fraction)
        freq = None
        if self.yarn_factor > 1.0:
            freq = dsa.yarn_frequencies(rotary, self.theta, self.yarn_factor,
                                        self.yarn_original, self.beta_fast,
                                        self.beta_slow)
        return dsa.rope(x, pos, self.theta, rotary=rotary, freq=freq,
                        mscale=self.attention_factor)


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    dim: int = 128
    # one 128-wide head: TPU MXU matmuls contract over the head dim, and a
    # 32-wide head runs the systolic array at 25% utilization (measured 3.2×
    # slower end-to-end than head_dim=128 at 12×4096 bench shapes).  Event
    # streams carry one temporal relation per layer; width beats head count.
    num_heads: int = 1
    num_layers: int = 4
    mlp_mult: int = 4
    dropout: float = 0.1
    dtype: Any = jnp.bfloat16
    # rematerialize each transformer block in the backward pass: activation
    # memory becomes O(num_layers · B·T·dim) params-side only, which is what
    # lets whole-trace streams train on one chip's HBM
    remat: bool = True
    # the stack, layer by layer (module docstring); None = num_layers x
    # ``block``.  A list in JSON, a tuple here
    kinds: Optional[Tuple[str, ...]] = None
    # > 0: event tokens in, an embedding of this many rows tied to the
    # output head, next-token objective.  0: features in, per-event BCE
    vocab_size: int = 0
    # the hybrid kinds' widths (``dim`` is the residual's)
    num_kv_heads: int = 1
    head_dim: int = 64
    mlp_dim: int = 512
    window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 8
    # each layer's index in the published stack, where the stack is a cut
    # of one: differential attention's lam_init is a function of it
    published_layers: Tuple[int, ...] = ()
    # the ``dsa_moe`` kind.  Attention: rotary base, the indexer's heads,
    # their size, the keys a query keeps, the weight of the indexer's loss
    rope_theta: float = 1e7
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    index_loss_weight: float = 1.0
    # its experts: the router's outputs, the experts a token is sent to,
    # an expert's width, and this chip's share ``[first_expert,
    # first_expert + held_experts)``
    num_experts: int = 128
    experts_per_token: int = 8
    expert_dim: int = 768
    first_expert: int = 0
    held_experts: int = 16
    rms_eps: float = 1e-6
    # False: the output head is a matrix of its own (``lm_head``), not the
    # embedding
    tie_head: bool = True
    # the latent kinds.  Attention: the queries' and the keys-and-values'
    # latent widths, a head's un-rotated and rotary query/key widths, its
    # value width.  (``mlp_dim`` is the dense layer's width, ``expert_dim``
    # an expert's, ``num_experts`` .. ``held_experts`` as above)
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_dim: int = 192
    qk_rope_dim: int = 64
    v_head_dim: int = 256
    # the router's weights are scaled by this (the sigmoid router's; the
    # softmax router's in the ``gqa_*_moe`` kinds); the shared expert's
    # width
    router_scale: float = 1.8
    shared_dim: int = 1536
    # multi-token-prediction modules behind the stack (0 or 1) and the
    # weight of their loss term
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3
    # the ``gqa_*`` kinds (``num_heads``, ``num_kv_heads`` and ``head_dim``
    # are the full kind's; ``window`` the window kind's reach): the window
    # kind's query heads, each kind's rotary, and the softmax router's
    # scale (``router_scale``)
    window_heads: int = 0
    rope_full: Rotary = Rotary()
    rope_window: Rotary = Rotary()

    def __post_init__(self):
        for name in ("kinds", "published_layers"):
            v = getattr(self, name)
            if isinstance(v, list):  # from JSON
                object.__setattr__(self, name, tuple(v))
        for name in ("rope_full", "rope_window"):
            v = getattr(self, name)
            if isinstance(v, dict):  # from JSON
                object.__setattr__(self, name, Rotary(**v))
        for name in ("kinds", "published_layers"):
            v = getattr(self, name)
            if v and len(v) != self.num_layers:
                raise ValueError(f"{name} names {len(v)} layers, num_layers "
                                 f"is {self.num_layers}")

    @property
    def stack(self) -> Tuple[str, ...]:
        return self.kinds if self.kinds is not None else (
            ("block",) * self.num_layers)

    def published_index(self, i: int) -> int:
        """Layer ``i`` of this stack's index in the published stack."""
        return self.published_layers[i] if self.published_layers else i

    @property
    def routed_layers(self) -> int:
        """Layers that route tokens to experts, the MTP module's included."""
        return sum(k in ROUTED_KINDS for k in self.stack) + self.mtp_layers


class _Block(nn.Module):
    cfg: StreamConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, deterministic: bool):
        # `deterministic` is positional so nn.remat can mark it static
        cfg = self.cfg
        h, d = cfg.num_heads, cfg.dim // cfg.num_heads
        dt = cfg.dtype

        y = nn.LayerNorm(dtype=dt, name="attn_ln")(x)
        qkv = nn.Dense(3 * cfg.dim, dtype=dt, name="qkv")(y)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = y.shape[:-1] + (h, d)
        out = ring_self_attention(
            q.reshape(shape), k.reshape(shape), v.reshape(shape),
            self.mesh, causal=True,
        )
        out = nn.Dense(cfg.dim, dtype=dt, name="proj")(out.reshape(y.shape))
        if cfg.dropout > 0:
            out = nn.Dropout(cfg.dropout, deterministic=deterministic)(out)
        x = x + out

        y = nn.LayerNorm(dtype=dt, name="mlp_ln")(x)
        y = nn.Dense(cfg.mlp_mult * cfg.dim, dtype=dt, name="mlp_in")(y)
        y = nn.gelu(y)
        y = nn.Dense(cfg.dim, dtype=dt, name="mlp_out")(y)
        if cfg.dropout > 0:
            y = nn.Dropout(cfg.dropout, deterministic=deterministic)(y)
        return x + y


def _dense(features, cfg, name, bias=False):
    return nn.Dense(features, use_bias=bias, dtype=cfg.dtype, name=name)


def lam_init_of(published_index: int) -> float:
    """Differential attention's ``lam_init`` (arXiv:2410.05258, eq. 3)."""
    return 0.8 - 0.6 * math.exp(-0.3 * published_index)


class _SwiGLU(nn.Module):
    cfg: StreamConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        with jax.named_scope("stream_mlp"):
            u = nn.LayerNorm(epsilon=1e-5, dtype=cfg.dtype, name="mlp_ln")(x)
            g = _dense(cfg.mlp_dim, cfg, "gate")(u)
            g = nn.silu(g) * _dense(cfg.mlp_dim, cfg, "up")(u)
            return x + _dense(cfg.dim, cfg, "down")(g)


class _DiffAttention(nn.Module):
    """Differential attention (heads in adjacent pairs) inside ``window``
    (``swa``), over the whole document (``full``: also returns its ``K, V``)
    or, with ``kv`` given, onto another layer's keys and values
    (``cross``: own ``W_q``, ``W_o`` only)."""

    cfg: StreamConfig
    kind: str
    index: int

    @nn.compact
    def __call__(self, u, seg, kv=None):
        cfg = self.cfg
        hq, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        b, t, _ = u.shape
        q = _dense(hq * d, cfg, "wq")(u).reshape(b, t, hq, d)
        if kv is None:
            k = _dense(hk * d, cfg, "wk")(u).reshape(b, t, hk, d)
            v = _dense(hk * d, cfg, "wv")(u).reshape(b, t, hk, d)
        else:
            k, v = kv
        # query head h = pair h // 2, softmax h % 2; a key-value pair
        # serves hq / hk query pairs.  Softmax c of a pair reads key c of
        # its key-value pair, and both read the value pair [V1, V2]
        heads = jnp.arange(hq)
        group = (heads // 2) // (hq // hk)
        k_of = jnp.take(k, 2 * group + heads % 2, axis=2)
        v_of = jnp.take(v.reshape(b, t, hk // 2, 2 * d), group, axis=2)
        with jax.named_scope(f"{self.kind}_attention"):
            att = ring_self_attention(
                q, k_of, v_of, None, causal=True,
                window=cfg.window if self.kind == "swa" else None,
                q_seg=seg, k_seg=seg)
        lam0 = lam_init_of(cfg.published_index(self.index))
        vec = lambda name: self.param(
            name, nn.initializers.normal(0.1), (d,), jnp.float32)
        lam = (jnp.exp(jnp.sum(vec("lq1") * vec("lk1")))
               - jnp.exp(jnp.sum(vec("lq2") * vec("lk2"))) + lam0)
        att = att.astype(jnp.float32)
        o = att[:, :, 0::2] - lam * att[:, :, 1::2]      # [B, T, hq/2, 2d]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-5)
        o = o * self.param("subln", nn.initializers.ones, (2 * d,),
                           jnp.float32) * (1.0 - lam0)
        out = _dense(cfg.dim, cfg, "wo")(
            o.astype(cfg.dtype).reshape(b, t, hq * d))
        return out, (k, v)


class _Mamba(nn.Module):
    """Mamba-1 mixer; also returns the scan output ``y`` before its gate."""

    cfg: StreamConfig

    @nn.compact
    def __call__(self, u, seg):
        cfg = self.cfg
        di, n, r = cfg.expand * cfg.dim, cfg.d_state, cfg.dt_rank
        xz = _dense(2 * di, cfg, "in_proj")(u)
        x, z = xz[..., :di], xz[..., di:]
        conv_w = self.param("conv_w", nn.initializers.normal(
            1.0 / math.sqrt(cfg.d_conv)), (cfg.d_conv, di), jnp.float32)
        conv_b = self.param("conv_b", nn.initializers.zeros, (di,),
                            jnp.float32)
        x = nn.silu(jax.vmap(causal_conv1d, in_axes=(0, None, None, 0))(
            x, conv_w.astype(cfg.dtype), conv_b.astype(cfg.dtype), seg))
        dbc = _dense(r + 2 * n, cfg, "x_proj")(x).astype(jnp.float32)
        dt = nn.Dense(di, dtype=jnp.float32, name="dt_proj",
                      bias_init=nn.initializers.constant(-4.6))(dbc[..., :r])
        a_log = self.param(
            "A_log", lambda _k, shape: jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), shape),
            (di, n))
        skip = self.param("D", nn.initializers.ones, (di,), jnp.float32)
        first = jnp.concatenate(
            [jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], 1)
        y = jax.vmap(selective_scan, in_axes=(0, 0, None, 0, 0, None, 0))(
            x, jax.nn.softplus(dt), -jnp.exp(a_log), dbc[..., r:r + n],
            dbc[..., r + n:], skip, first).astype(cfg.dtype)
        return _dense(cfg.dim, cfg, "out_proj")(y * nn.silu(z)), y


class _HybridLayer(nn.Module):
    """One decoder-hybrid-decoder layer: ``h = x + Mixer(LN(x))``, ``y = h +
    MLP(LN(h))``.  Takes and returns the two hand-downs beside the
    residual: ``(x, seg, m, kv) -> (x, m, kv)``, replacing ``m`` / ``kv``
    where it is a source and passing them through where it is not."""

    cfg: StreamConfig
    kind: str
    index: int

    @nn.compact
    def __call__(self, x, seg, m, kv):
        cfg, kind = self.cfg, self.kind
        with jax.named_scope(f"stream_layer_{self.index}"):
            u = nn.LayerNorm(epsilon=1e-5, dtype=cfg.dtype, name="mix_ln")(x)
            if kind == "mamba":
                out, m = _Mamba(cfg, name="mamba")(u, seg)
            elif kind in ("swa", "full"):
                out, own = _DiffAttention(cfg, kind, self.index,
                                          name="attn")(u, seg)
                if kind == "full":
                    kv = own
            elif kind == "cross":
                out, _ = _DiffAttention(cfg, kind, self.index,
                                        name="attn")(u, seg, kv)
            elif kind == "gmu":
                with jax.named_scope("gmu"):
                    gate = nn.silu(_dense(cfg.expand * cfg.dim, cfg,
                                          "gmu_in")(u))
                    out = _dense(cfg.dim, cfg, "gmu_out")(m * gate)
            else:
                raise ValueError(f"unknown layer kind {kind!r}")
            return _SwiGLU(cfg, name="mlp")(x + out), m, kv


class _SparseMoELayer(nn.Module):
    """One ``dsa_moe`` decoder layer: ``h = x + W_o Attn_S(RMSNorm(x))``
    over the keys the indexer chooses, ``y = h + Experts(RMSNorm(h))`` over
    the experts held here.  ``(x, seg) -> (y, aux)``; ``aux`` holds float32
    scalars: the indexer's loss (mean ``KL_t`` over real tokens), the
    selected and the allowed pairs, the assignments to held experts and
    their largest count over the mean."""

    cfg: StreamConfig
    index: int

    @nn.compact
    def __call__(self, x, seg):
        cfg = self.cfg
        hq, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        b, t, _ = x.shape
        f32 = jnp.float32
        norm = lambda name: nn.RMSNorm(epsilon=cfg.rms_eps, dtype=cfg.dtype,
                                       name=name)
        exact = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=f32, name=name,
            precision=jax.lax.Precision.HIGHEST)
        with jax.named_scope(f"stream_layer_{self.index}"):
            u = norm("attn_norm")(x)
            q = norm("q_norm")(
                _dense(hq * d, cfg, "wq")(u).reshape(b, t, hq, d))
            k = norm("k_norm")(
                _dense(hk * d, cfg, "wk")(u).reshape(b, t, hk, d))
            v = _dense(hk * d, cfg, "wv")(u).reshape(b, t, hk, d)
            # the indexer reads the layer's input without moving it
            ui = jax.lax.stop_gradient(u).astype(f32)
            j, e = cfg.index_heads, cfg.index_head_dim
            qi = exact(j * e, "index_q")(ui).reshape(b, t, j, e)
            ki = nn.LayerNorm(epsilon=cfg.rms_eps, dtype=f32,
                              name="index_k_norm")(exact(e, "index_k")(ui))
            wi = exact(j, "index_w")(ui) * (j * e) ** -0.5
            # read only by `apply(..., mutable=["intermediates"])`
            self.sow("intermediates", "index_inputs", (qi, ki, wi))

            def one(q, k, v, qi, ki, wi, seg):
                pos = dsa.doc_positions(seg)
                return dsa.sparse_attention(
                    dsa.rope(q, pos, cfg.rope_theta),
                    dsa.rope(k, pos, cfg.rope_theta), v, qi, ki, wi, seg,
                    topk=cfg.index_topk) + (dsa.causal_pairs(seg),)

            o, kl, chosen, allowed = jax.vmap(one)(q, k, v, qi, ki, wi, seg)
            h = x + _dense(cfg.dim, cfg, "wo")(o.reshape(b, t, hq * d))

            z = norm("moe_norm")(h).reshape(b * t, cfg.dim)
            logits = exact(cfg.num_experts, "router")(z.astype(f32))
            self.sow("intermediates", "router_logits", logits)
            shape = (cfg.held_experts, cfg.dim, cfg.expert_dim)
            init = lambda fan_in: nn.initializers.normal(fan_in ** -0.5)
            w = [self.param(name, init(s[1]), s, f32)
                 for name, s in (("w_gate", shape), ("w_up", shape),
                                 ("w_down", (shape[0], shape[2], shape[1])))]
            y, counts = moe.moe_share(z, logits, *w,
                                      k=cfg.experts_per_token,
                                      first=cfg.first_expert)
            real = jnp.maximum(jnp.sum(seg > 0), 1).astype(f32)
            counts = counts.astype(f32)
            aux = {"index_loss": jnp.sum(kl) / real,
                   "selected_pairs": jnp.sum(chosen).astype(f32),
                   "allowed_pairs": jnp.sum(allowed),
                   "held_assignments": jnp.sum(counts),
                   "load_max_over_mean": jnp.max(counts) / jnp.maximum(
                       jnp.mean(counts), 1.0)}
            return h + y.reshape(b, t, cfg.dim).astype(cfg.dtype), aux


def _swiglu(cfg: StreamConfig, z, width: int, prefix: str = ""):
    g = nn.silu(_dense(width, cfg, prefix + "gate")(z))
    return _dense(cfg.dim, cfg, prefix + "down")(
        g * _dense(width, cfg, prefix + "up")(z))


class _LatentLayer(nn.Module):
    """One latent-attention decoder layer: ``h = x + W_o Attn(RMSNorm(x))``
    with queries, keys and values rebuilt from their latents, then ``y = h +
    SwiGLU(RMSNorm(h))`` (``dense``) or ``y = h + Experts(z) + Shared(z)``,
    ``z = RMSNorm(h)``: the held experts' part under the sigmoid router and
    a shared expert every token passes.  ``(x, seg) -> (y, aux)``; ``aux``
    is empty for a dense layer, else the assignments to held experts and
    their largest count over the mean (float32 scalars).  ``label`` names
    the layer in a trace (``stream_layer_<i>``, ``mtp_block``)."""

    cfg: StreamConfig
    dense: bool
    label: str

    @nn.compact
    def __call__(self, x, seg):
        cfg = self.cfg
        heads, nope, rot = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
        b, t, _ = x.shape
        f32 = jnp.float32
        norm = lambda name: nn.RMSNorm(epsilon=cfg.rms_eps, dtype=cfg.dtype,
                                       name=name)
        with jax.named_scope(self.label):
            u = norm("attn_norm")(x)
            with jax.named_scope("mla_latent"):
                q = _dense(heads * (nope + rot), cfg, "wq_b")(
                    norm("q_a_norm")(_dense(cfg.q_lora_rank, cfg, "wq_a")(u)))
                latent = _dense(cfg.kv_lora_rank + rot, cfg, "wkv_a")(u)
                kv = _dense(heads * (nope + cfg.v_head_dim), cfg, "wkv_b")(
                    norm("kv_a_norm")(latent[..., :cfg.kv_lora_rank]))
                q, k, v = jax.vmap(lambda q, k_r, kv, seg: mla.assemble(
                    q, k_r, kv, dsa.doc_positions(seg), nope=nope,
                    theta=cfg.rope_theta))(
                        q.reshape(b, t, heads, nope + rot),
                        latent[..., cfg.kv_lora_rank:],
                        kv.reshape(b, t, heads, nope + cfg.v_head_dim), seg)
            o = jax.vmap(mla.attention)(q, k, v, seg)
            with jax.named_scope("mla_latent"):
                h = x + _dense(cfg.dim, cfg, "wo")(
                    o.reshape(b, t, heads * cfg.v_head_dim))

            z = norm("mlp_norm")(h)
            if self.dense:
                with jax.named_scope("dense_mlp"):
                    return h + _swiglu(cfg, z, cfg.mlp_dim), {}
            # a buffer in the parameters' tree: seeded, read under
            # `stop_gradient`, frozen by the trainer (`make_stream_tx`)
            bias = self.param("router_bias", nn.initializers.normal(0.1),
                              (cfg.num_experts,), f32)
            y, aux = _beside_shared(self, cfg, z.reshape(b * t, cfg.dim),
                                    partial(moe.route_sigmoid, bias=bias,
                                            scale=cfg.router_scale))
            return h + y.reshape(b, t, cfg.dim), aux


def _beside_shared(module: nn.Module, cfg: StreamConfig, z, router):
    """The held experts under ``router`` (`ops/moe.py`) beside a shared
    expert every token passes, inside ``module`` (their parameters are
    its): ``z`` [N, dim] -> (y [N, dim], aux: the assignments to held
    experts and their largest count over the mean, float32 scalars)."""
    f32 = jnp.float32
    logits = nn.Dense(cfg.num_experts, use_bias=False, dtype=f32,
                      name="router", precision=jax.lax.Precision.HIGHEST)(
                          z.astype(f32))
    # read only by `apply(..., mutable=["intermediates"])`
    module.sow("intermediates", "router_logits", logits)
    shape = (cfg.held_experts, cfg.dim, cfg.expert_dim)
    init = lambda fan_in: nn.initializers.normal(fan_in ** -0.5)
    w = [module.param(name, init(s[1]), s, f32)
         for name, s in (("w_gate", shape), ("w_up", shape),
                         ("w_down", (shape[0], shape[2], shape[1])))]
    y, counts = moe.moe_share(z, logits, *w, k=cfg.experts_per_token,
                              first=cfg.first_expert, router=router)
    with jax.named_scope("moe_shared"):
        y = y.astype(cfg.dtype) + _swiglu(cfg, z, cfg.shared_dim, "shared_")
    counts = counts.astype(f32)
    return y, {"held_assignments": jnp.sum(counts),
               "load_max_over_mean": jnp.max(counts) / jnp.maximum(
                   jnp.mean(counts), 1.0)}


class _GroupedLayer(nn.Module):
    """One grouped-query decoder layer: ``h = x + W_o (g * Attn(RMSNorm(x)))``
    with rotary queries and keys, ``num_kv_heads`` key-value heads shared by
    groups of query heads, and a sigmoid gate a query head, ``g_h =
    sigmoid(u . w_g,h)`` of the attention's input ``u``; the
    attention over the whole document (``gqa_full_*``: ``num_heads`` query
    heads, ``rope_full``) or inside ``window`` (``gqa_swa_*``:
    ``window_heads``, ``rope_window``).  Then ``y = h + SwiGLU(RMSNorm(h))``
    (``_dense``) or the softmax router's held experts (times
    ``router_scale``) beside a shared expert (``_moe``).  ``(x, seg) -> (y,
    aux)``: ``aux`` holds the pairs the attention attended
    (``attention_pairs``) and, after experts, `_beside_shared`'s counts."""

    cfg: StreamConfig
    kind: str
    label: str

    @nn.compact
    def __call__(self, x, seg):
        cfg = self.cfg
        window = self.kind in WINDOW_KINDS
        heads = cfg.window_heads if window else cfg.num_heads
        hk, d = cfg.num_kv_heads, cfg.head_dim
        rotary = cfg.rope_window if window else cfg.rope_full
        b, t, _ = x.shape
        norm = lambda name: nn.RMSNorm(epsilon=cfg.rms_eps, dtype=cfg.dtype,
                                       name=name)
        with jax.named_scope(self.label):
            u = norm("attn_norm")(x)
            with jax.named_scope("gqa_proj"):
                q = _dense(heads * d, cfg, "wq")(u).reshape(b, t, heads, d)
                k = _dense(hk * d, cfg, "wk")(u).reshape(b, t, hk, d)
                v = _dense(hk * d, cfg, "wv")(u).reshape(b, t, hk, d)
                # ``wg``: ``gate`` is the dense SwiGLU's
                gate = jax.nn.sigmoid(_dense(heads, cfg, "wg")(u).astype(
                    jnp.float32))

                def turn(q, k, seg):
                    pos = dsa.doc_positions(seg)
                    return rotary.apply(q, pos), rotary.apply(k, pos)

                q, k = jax.vmap(turn)(q, k, seg)
            reach = cfg.window if window else None
            o = jax.vmap(partial(
                mla.attention, window=reach,
                scope="gqa_window_attention" if window
                else "gqa_full_attention"))(q, k, v, seg)
            with jax.named_scope("gqa_proj"):
                o = (o * gate[..., None]).astype(cfg.dtype)
                h = x + _dense(cfg.dim, cfg, "wo")(o.reshape(b, t, heads * d))
            aux = {"attention_pairs": jnp.sum(jax.vmap(
                partial(dsa.causal_pairs, window=reach))(seg))}
            z = norm("mlp_norm")(h)
            if self.kind.endswith("_dense"):
                with jax.named_scope("dense_mlp"):
                    return h + _swiglu(cfg, z, cfg.mlp_dim), aux
            y, routed = _beside_shared(
                self, cfg, z.reshape(b * t, cfg.dim),
                partial(moe.route, scale=cfg.router_scale))
            return h + y.reshape(b, t, cfg.dim), {**aux, **routed}


class StreamNet(nn.Module):
    """The event-stream encoder.

    ``vocab_size`` 0 (the per-event detector): ``(feat [B, T, F] float32,
    mask [B, T] bool)`` -> per-event attack logits ``event_logits`` [B, T]
    and their ``stream_logit``.  ``vocab_size`` > 0 (the pretrainer):
    ``(tokens [B, T] int32, segments [B, T] int32: a packed document's id,
    0 = padding)`` -> ``hidden`` [B, T, dim] after the final norm; the
    logits are ``hidden @ embedding.T`` (``hidden @ lm_head.T`` where the
    head is untied) and are never built whole (`next_token_loss`).  A stack
    with routed layers also returns ``aux``: their counts summed, their
    load imbalance averaged, and for ``dsa_moe`` layers their indexer
    losses summed; with ``mtp_layers`` also ``mtp_hidden`` [B, T, dim], the
    multi-token-prediction module's output after its norm (`mtp_loss`).

    ``mesh`` is a static module attribute: when it carries an ``sp`` axis of
    size > 1, every ``block`` layer runs as ring attention with T sharded
    over it.  Semantics are identical either way (exact attention).
    """

    cfg: StreamConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(
        self,
        feat,  # [B, T, F] float32 — or tokens [B, T] int32
        mask,  # [B, T] bool (True = real event) — or segment ids [B, T]
        *,
        deterministic: bool = True,
    ) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        dt = cfg.dtype
        if cfg.vocab_size:
            seg = mask
            embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=dt,
                             name="tok_embed")
            x = embed(feat)
        else:
            seg = mask.astype(jnp.int32)
            x = nn.Dense(cfg.dim, dtype=dt, name="embed")(feat.astype(dt))
            x = nn.gelu(x)
        block_cls = nn.remat(_Block, static_argnums=(2,)) if cfg.remat else _Block
        layer_cls = nn.remat(_HybridLayer) if cfg.remat else _HybridLayer
        # its remat keeps what `ops/dsa.py` names: the backward pass then
        # does not run the indexer, the selection and the attention again
        sparse_cls = (nn.remat(
            _SparseMoELayer,
            policy=jax.checkpoint_policies.save_only_these_names(dsa.SAVED))
            if cfg.remat else _SparseMoELayer)
        latent_cls = (nn.remat(
            _LatentLayer,
            policy=jax.checkpoint_policies.save_only_these_names(mla.SAVED))
            if cfg.remat else _LatentLayer)
        grouped_cls = (nn.remat(
            _GroupedLayer,
            policy=jax.checkpoint_policies.save_only_these_names(mla.SAVED))
            if cfg.remat else _GroupedLayer)
        m = kv = None
        sparse_aux = []
        # the pairs each attention kind attended, summed over its layers
        pairs = {}
        for i, kind in enumerate(cfg.stack):
            if kind == "block":
                x = block_cls(cfg, self.mesh, name=f"block_{i}")(
                    x, deterministic
                )
            elif kind == SPARSE_KIND:
                x, aux = sparse_cls(cfg, i, name=f"layer_{i}")(x, seg)
                sparse_aux.append(aux)
            elif kind in LATENT_KINDS:
                x, aux = latent_cls(cfg, kind == "mla_dense",
                                    f"stream_layer_{i}",
                                    name=f"layer_{i}")(x, seg)
                if aux:
                    sparse_aux.append(aux)
            elif kind in GQA_KINDS:
                x, aux = grouped_cls(cfg, kind, f"stream_layer_{i}",
                                     name=f"layer_{i}")(x, seg)
                which = ("window_pairs" if kind in WINDOW_KINDS
                         else "full_pairs")
                pairs[which] = pairs.get(which, 0.0) + aux.pop(
                    "attention_pairs")
                if aux:
                    sparse_aux.append(aux)
            else:
                x, m, kv = layer_cls(cfg, kind, i, name=f"layer_{i}")(
                    x, seg, m, kv)
        out = {}
        if cfg.mtp_layers:
            # h'_i = W_eh [RMSNorm(Emb(t_{i+1})); RMSNorm(H_i)], H the
            # stack's output before the final norm
            rms = lambda name: nn.RMSNorm(epsilon=cfg.rms_eps, dtype=dt,
                                          name=name)
            with jax.named_scope("mtp_embed_proj"):
                nxt = jnp.concatenate([feat[:, 1:], feat[:, :1]], axis=1)
                y = _dense(cfg.dim, cfg, "mtp_eh_proj")(jnp.concatenate(
                    [rms("mtp_enorm")(embed(nxt)), rms("mtp_hnorm")(x)], -1))
            y, aux = latent_cls(cfg, False, "mtp_block",
                                name="mtp_block")(y, seg)
            sparse_aux.append(aux)
            out["mtp_hidden"] = rms("mtp_norm")(y)
        if sparse_aux or cfg.stack[-1] in LATENT_KINDS + GQA_KINDS:
            x = nn.RMSNorm(epsilon=cfg.rms_eps, dtype=dt, name="final_norm")(x)
        else:
            x = nn.LayerNorm(epsilon=1e-5 if cfg.vocab_size else 1e-6,
                             dtype=dt, name="final_ln")(x)
        if cfg.vocab_size and not cfg.tie_head:
            self.param("lm_head", nn.initializers.normal(cfg.dim ** -0.5),
                       (cfg.vocab_size, cfg.dim), jnp.float32)
        if sparse_aux:
            aux = jax.tree_util.tree_map(lambda *v: sum(v), *sparse_aux)
            aux["load_max_over_mean"] /= len(sparse_aux)
            # every position is routed, padding too
            aux["routed_tokens"] = jnp.float32(x.shape[0] * x.shape[1])
            return {"hidden": x, "aux": {**aux, **pairs}, **out}
        if cfg.vocab_size:
            return {"hidden": x}
        logits = nn.Dense(1, dtype=jnp.float32, name="head")(x)[..., 0]
        logits = jnp.where(mask, logits, 0.0)

        # stream-level summary: max event logit over valid steps (an attack
        # trace is one whose stream contains attack events)
        stream_logit = jnp.where(mask, logits, -1e30).max(axis=-1)
        return {"event_logits": logits, "stream_logit": stream_logit}


def next_token_targets(tokens, seg):
    """-> (targets [B, T], weights [B, T] float32): position t predicts
    token t + 1 where that is a real token of the same document (so a
    document's first token is never a target, and padding never counts)."""
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    same = jnp.concatenate(
        [(seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] > 0),
         jnp.zeros_like(seg[:, :1], bool)], axis=1)
    return nxt, same.astype(jnp.float32)


def mtp_targets(tokens, seg):
    """-> (targets [B, T], weights [B, T] float32): position t predicts
    token t + 2 where tokens t + 1 and t + 2 are real tokens of t's
    document (the module read token t + 1's embedding at t)."""
    two = jnp.concatenate([tokens[:, 2:], tokens[:, :2]], axis=1)
    same = jnp.concatenate(
        [(seg[:, 1:-1] == seg[:, :-2]) & (seg[:, 2:] == seg[:, :-2])
         & (seg[:, :-2] > 0), jnp.zeros_like(seg[:, :2], bool)], axis=1)
    return two, same.astype(jnp.float32)


# positions of the next-token loss computed at a time (the logits of a whole
# 8192-token sequence are 0.8 GB a copy in float32)
LOSS_CHUNK = 1024


def next_token_loss(cfg: StreamConfig, params, hidden, tokens, seg,
                    chunk: int = LOSS_CHUNK):
    """Mean next-token cross-entropy over the held vocabulary rows, through
    the head (the embedding itself, or ``lm_head`` where ``tie_head`` is
    false), ``chunk`` positions at a time: a chunk's logits live only
    inside its `jax.checkpoint`."""
    return _vocab_loss(cfg, params, hidden, next_token_targets, tokens, seg,
                       chunk, "lm_head_loss")[0]


def mtp_loss(cfg: StreamConfig, params, mtp_hidden, tokens, seg,
             chunk: int = LOSS_CHUNK):
    """The multi-token-prediction term: mean cross-entropy of the token two
    ahead over the positions that carry such a target, through the main
    model's head -> (loss, the number of those positions)."""
    return _vocab_loss(cfg, params, mtp_hidden, mtp_targets, tokens, seg,
                       chunk, "mtp_head_loss")


def _vocab_loss(cfg, params, hidden, targets_of, tokens, seg, chunk, scope):
    """-> (the mean cross-entropy over ``targets_of(tokens, seg)``'s
    weighted positions, the summed weights)."""
    emb = (params["tok_embed"]["embedding"] if cfg.tie_head
           else params["lm_head"]).astype(cfg.dtype)
    b, t, dim = hidden.shape
    chunk = min(chunk, b * t)
    if (b * t) % chunk:
        raise ValueError(f"{b * t} positions are not whole chunks of {chunk}")
    targets, weights = targets_of(tokens, seg)

    @partial(jax.checkpoint, prevent_cse=False)
    def chunk_nll(x, y, w):
        logits = jnp.einsum("td,vd->tv", x, emb,
                            preferred_element_type=jnp.float32)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * w)

    with jax.named_scope(scope):
        cut = lambda v: v.reshape((b * t // chunk, chunk) + v.shape[2:])
        total = jax.lax.map(lambda a: chunk_nll(*a),
                            (cut(hidden), cut(targets), cut(weights)))
        total, count = jnp.sum(total), jnp.sum(weights)
        return total / jnp.maximum(count, 1.0), count


def stream_loss(outputs, labels, mask):
    """Masked per-event sigmoid BCE.  labels float32 [B, T] ∈ {0, 1}."""
    from nerrf_tpu.train.loop import _weighted_bce

    return _weighted_bce(
        outputs["event_logits"], labels, mask.astype(jnp.float32), 1.0
    )
