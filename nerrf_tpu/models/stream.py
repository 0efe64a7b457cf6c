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

No positional encoding anywhere (event streams are irregularly sampled —
wall-clock gaps carry signal, so Δt enters as a feature or a token, not a
position index); bfloat16 compute, float32 parameters.
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

from nerrf_tpu.ops.ssm import causal_conv1d, selective_scan
from nerrf_tpu.parallel.ring import ring_self_attention

HYBRID_KINDS = ("mamba", "swa", "full", "gmu", "cross")


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

    def __post_init__(self):
        for name in ("kinds", "published_layers"):
            v = getattr(self, name)
            if isinstance(v, list):  # from JSON
                object.__setattr__(self, name, tuple(v))
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


class StreamNet(nn.Module):
    """The event-stream encoder.

    ``vocab_size`` 0 (the per-event detector): ``(feat [B, T, F] float32,
    mask [B, T] bool)`` -> per-event attack logits ``event_logits`` [B, T]
    and their ``stream_logit``.  ``vocab_size`` > 0 (the pretrainer):
    ``(tokens [B, T] int32, segments [B, T] int32: a packed document's id,
    0 = padding)`` -> ``hidden`` [B, T, dim] after the final LayerNorm; the
    logits are ``hidden @ embedding.T`` and are never built whole
    (`next_token_loss`).

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
            x = nn.Embed(cfg.vocab_size, cfg.dim, dtype=dt,
                         name="tok_embed")(feat)
        else:
            seg = mask.astype(jnp.int32)
            x = nn.Dense(cfg.dim, dtype=dt, name="embed")(feat.astype(dt))
            x = nn.gelu(x)
        block_cls = nn.remat(_Block, static_argnums=(2,)) if cfg.remat else _Block
        layer_cls = nn.remat(_HybridLayer) if cfg.remat else _HybridLayer
        m = kv = None
        for i, kind in enumerate(cfg.stack):
            if kind == "block":
                x = block_cls(cfg, self.mesh, name=f"block_{i}")(
                    x, deterministic
                )
            else:
                x, m, kv = layer_cls(cfg, kind, i, name=f"layer_{i}")(
                    x, seg, m, kv)
        x = nn.LayerNorm(epsilon=1e-5 if cfg.vocab_size else 1e-6, dtype=dt,
                         name="final_ln")(x)
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


# positions of the next-token loss computed at a time (the logits of a whole
# 8192-token sequence are 0.8 GB a copy in float32)
LOSS_CHUNK = 1024


def next_token_loss(cfg: StreamConfig, params, hidden, tokens, seg,
                    chunk: int = LOSS_CHUNK):
    """Mean next-token cross-entropy over the held vocabulary rows, through
    the head tied to the embedding, ``chunk`` positions at a time: a chunk's
    logits live only inside its `jax.checkpoint`."""
    emb = params["tok_embed"]["embedding"].astype(cfg.dtype)
    b, t, dim = hidden.shape
    chunk = min(chunk, b * t)
    if (b * t) % chunk:
        raise ValueError(f"{b * t} positions are not whole chunks of {chunk}")
    targets, weights = next_token_targets(tokens, seg)

    @partial(jax.checkpoint, prevent_cse=False)
    def chunk_nll(x, y, w):
        logits = jnp.einsum("td,vd->tv", x, emb,
                            preferred_element_type=jnp.float32)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * w)

    with jax.named_scope("lm_head_loss"):
        cut = lambda v: v.reshape((b * t // chunk, chunk) + v.shape[2:])
        total = jax.lax.map(lambda a: chunk_nll(*a),
                            (cut(hidden), cut(targets), cut(weights)))
        return jnp.sum(total) / jnp.maximum(jnp.sum(weights), 1.0)


def stream_loss(outputs, labels, mask):
    """Masked per-event sigmoid BCE.  labels float32 [B, T] ∈ {0, 1}."""
    from nerrf_tpu.train.loop import _weighted_bce

    return _weighted_bce(
        outputs["event_logits"], labels, mask.astype(jnp.float32), 1.0
    )
