"""Bidirectional LSTM impact predictor.

Realizes the reference's specified sequence model
(`/root/reference/docs/content/docs/architecture.mdx:55-59`: BiLSTM, 256
hidden, 2 layers, input = last 100 events per file, output = encrypt/
ransomware probability, target F1 ≥ 0.95).  TPU-native shape: the recurrence
is a single fused `lax.scan` per layer — both directions ride one scan
(stacked on a leading axis; one batched matmul per timestep), and the
input-side gate projections are hoisted out of the scan as one big matmul
per direction over all timesteps, written time-major from a time-major copy
of the narrow layer input, so the 4H-wide scan inputs are never re-laid.
The reverse direction reads the same prefix-first sequence reversed along
time by a static `jnp.flip` — its padding first — and multiplies its new
`h` and `c` by the step's validity: its state is exactly zero until its
first real event and its outputs on padding are exactly zero, with no
data-dependent gather in the forward program and no scatter in the backward
one (reversing inside each valid prefix by `take_along_axis` costs 13 ms a
step of 8 x 128 sequences on a v5e; PERF.md, PR 32).  An operation inside
the loop costs 2-11 us on that chip, so what the loop body holds is counted
in fusions, not in FLOPs.

The recurrence itself is `bilstm_recurrence`, a function of arrays with a
hand-written reverse rule (PERF.md, PR 34).  `jax.grad` of `lax.scan` kept
13 stacked tensors of `[T,2,B,H]` a layer, and what they cost was bytes,
not fusions: at 8 windows x 128 sequences x 100 steps one is 104.9 MB, a
step zero-filled and then stored 24 of them (2.52 GB each way, both near
the memory's speed, 8 of the BiLSTM's 29 ms).  The rule keeps `h` (the
output anyway), the cell state each step started from and the four
activated gates as ONE `[T,2,B,4H]` tensor; its backward loop carries
`(dh, dc, c_t)`, is one gate-derivative fusion and one product an
iteration, and stacks one tensor, `dgates`, which IS the cotangent of the
hoisted projections.  The weight gradient is one product AFTER the loop
(`h_{t-1}` against `dgates`, K = T x B, float32 out), because the training
step is `jax.grad` of a `jax.vmap` over windows with the parameters closed
over (`train/loop.py`): a rule's weight gradient comes out a window and is
summed by `custom_vjp`'s batching rule, so inside the loop it would be a
window's worth of K = B products an iteration.  Asked for no gradient the
function is the plain scan, one stacked output.

Param tree is bit-compatible with the previous
`flax.linen.RNN(OptimizedLSTMCell)` implementation
(``OptimizedLSTMCell_{2i}``=fwd / ``_{2i+1}``=bwd, ``ii..io``/``hi..ho``
leaves), which remains available as ``LSTMConfig.impl="rnn"`` and is
parity-tested against the fused path.  Sequences are left-padded with a
step mask; pooling is mask-aware so padding never leaks into the
prediction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LSTMConfig:
    hidden: int = 256
    num_layers: int = 2
    dropout: float = 0.1
    dtype: Any = jnp.bfloat16
    # "fused": both directions in one scan, input projections hoisted and
    # written time-major, the reverse direction a masked scan over the
    # statically reversed input (the TPU-shaped path; no gather, no
    # scatter).  "rnn": the original flax RNN/OptimizedLSTMCell pair with
    # `seq_lengths` — same math, same param tree (outputs and gradients
    # parity-tested in f32), and ~1.5x faster on CPU where the
    # batched-einsum layout is not cheap.  "auto" (default): fused on the
    # TPU backend, rnn elsewhere.
    impl: str = "auto"

    @property
    def small(self) -> "LSTMConfig":
        return dataclasses.replace(self, hidden=32, num_layers=1)

    def resolved_impl(self) -> str:
        """The implementation the forward actually uses on this process's
        default backend — single definition of the "auto" rule, shared
        with the bench's kernel_path attribution."""
        if self.impl != "auto":
            return self.impl
        return "fused" if jax.default_backend() == "tpu" else "rnn"


class _GateParams(nn.Module):
    """Param holder replicating one flax LSTMCell dense block (``ii``…,
    ``hi``…): same names, shapes, and initializers, so checkpoints trained
    on either implementation load into the other."""

    features: int
    use_bias: bool
    recurrent: bool

    @nn.compact
    def __call__(self, in_features: int):
        init = (nn.initializers.orthogonal() if self.recurrent
                else nn.initializers.lecun_normal())
        k = self.param("kernel", init, (in_features, self.features))
        b = (self.param("bias", nn.initializers.zeros, (self.features,))
             if self.use_bias else None)
        return k, b


class _CellParams(nn.Module):
    """One LSTM cell's param tree (``ii..io`` input kernels, ``hi..ho``
    recurrent kernels + biases), concatenated per side for the fused path."""

    hidden: int

    @nn.compact
    def __call__(self, in_features: int):
        ki, kh, bh = [], [], []
        for gate in ("i", "f", "g", "o"):
            k, _ = _GateParams(self.hidden, use_bias=False, recurrent=False,
                               name=f"i{gate}")(in_features)
            ki.append(k)
            k, b = _GateParams(self.hidden, use_bias=True, recurrent=True,
                               name=f"h{gate}")(self.hidden)
            kh.append(k)
            bh.append(b)
        return (jnp.concatenate(ki, axis=1), jnp.concatenate(kh, axis=1),
                jnp.concatenate(bh, axis=0))


def _loop_operands(like, wh, bias):
    """The recurrent kernel and bias in the loops' arithmetic type (that of
    ``like``, one step's ``[2, *batch, n]``), the bias shaped to broadcast
    against ``[2, *batch, 4H]`` whatever the batch rank, and the zero
    state."""
    dt = like.dtype
    bias = bias.astype(dt).reshape((2,) + (1,) * (like.ndim - 2) + (-1,))
    h0 = jnp.zeros(like.shape[:-1] + (wh.shape[1],), dt)
    return wh.astype(dt), bias, h0


def _cell(h, c, x_fwd, x_bwd, keep_t, wh, bias):
    """One step of both directions: the new ``h``, ``c`` and the activated
    gates ``(i, f, g, o)`` as one ``[2, *batch, 4H]`` tensor."""
    gates = (jnp.stack([x_fwd, x_bwd])
             + jnp.einsum("d...h,dhg->d...g", h, wh) + bias)
    gi, gf, gg, go = jnp.split(gates, 4, axis=-1)
    i, f, g, o = nn.sigmoid(gi), nn.sigmoid(gf), jnp.tanh(gg), nn.sigmoid(go)
    c = (f * c + i * g) * keep_t
    h = o * jnp.tanh(c) * keep_t
    return h, c, jnp.concatenate([i, f, g, o], axis=-1)


def _recurrence(xs_fwd, xs_bwd, keep, wh, bias):
    """Both directions of one BiLSTM layer as a single scan over time.

    ``xs_fwd`` / ``xs_bwd`` ``[T, *batch, 4H]``: the hoisted input
    projections, time-major, the second already reversed along time;
    ``keep`` ``[T, 2, *batch, 1]``: what a step's new ``h`` and ``c`` are
    multiplied by; ``wh`` ``[2, H, 4H]`` and ``bias`` ``[2, 4H]`` in the
    parameters' own type.  Returns ``hs`` ``[T, 2, *batch, H]``.  This is
    the whole definition: `bilstm_recurrence` is this function with a
    hand-written reverse rule, and `jax.grad` of this one is what the tests
    hold the rule to."""
    wh, bias, h0 = _loop_operands(keep[0], wh, bias)

    def step(carry, inp):
        h, c, _ = _cell(*carry, *inp, wh, bias)
        return (h, c), h

    _, hs = jax.lax.scan(step, (h0, h0), (xs_fwd, xs_bwd, keep))
    return hs


bilstm_recurrence = jax.custom_vjp(_recurrence)


def _recurrence_fwd(xs_fwd, xs_bwd, keep, wh, bias):
    """The same scan under a gradient.  Stacked beside ``hs``: the cell
    state each step STARTED from (so the reverse loop reads ``c_{t-1}``
    where it reads everything else, at ``t``, and carries ``c_t`` along)
    and the activated gates as one tensor."""
    whc, bc, h0 = _loop_operands(keep[0], wh, bias)

    def step(carry, inp):
        h, c, acts = _cell(*carry, *inp, whc, bc)
        return (h, c), (h, carry[1], acts)

    (_, c_last), (hs, cs_prev, acts) = jax.lax.scan(
        step, (h0, h0), (xs_fwd, xs_bwd, keep))
    return hs, (hs, cs_prev, c_last, acts, keep, wh, bias)


def _recurrence_bwd(res, dhs):
    hs, cs_prev, c_last, acts, keep, wh, bias = res
    whc, _, h0 = _loop_operands(c_last, wh, bias)

    def step(carry, inp):
        dh, dc, c = carry
        dh_t, acts_t, c_prev, keep_t = inp
        i, f, g, o = jnp.split(acts_t, 4, axis=-1)
        tanh_c = jnp.tanh(c)
        dh = (dh + dh_t) * keep_t
        dc = (dc + dh * o * (1 - tanh_c * tanh_c)) * keep_t
        dgates = jnp.concatenate(
            [dc * g * i * (1 - i), dc * c_prev * f * (1 - f),
             dc * i * (1 - g * g), dh * tanh_c * o * (1 - o)], axis=-1)
        dh_prev = jnp.einsum("d...g,dhg->d...h", dgates, whc)
        return (dh_prev, dc * f, c_prev), dgates

    # the operations of a hand-written reverse pass need not carry the
    # forward call's scope (PERF.md, PR 33): open the recurrence's own, so
    # the trace reads the whole BiLSTM under `lstm`
    with jax.named_scope("lstm_scan"):
        _, dgates = jax.lax.scan(step, (h0, h0, c_last),
                                 (dhs, acts, cs_prev, keep),
                                 reverse=True)               # [T,2,B,4H]
        # after the loop, not in it: one product with K = T x batch, and
        # under `jax.grad` of a `jax.vmap` with the parameters closed over
        # (train/loop.py) one a window, summed in float32 by custom_vjp's
        # batching rule
        hs_prev = jnp.concatenate([jnp.zeros_like(hs[:1]), hs[:-1]], axis=0)
        dwh = jnp.einsum("td...h,td...g->dhg", hs_prev, dgates,
                         preferred_element_type=jnp.float32)
        dbias = dgates.sum(axis=(0,) + tuple(range(2, dgates.ndim - 1)),
                           dtype=jnp.float32)
    return (dgates[:, 0], dgates[:, 1], jnp.zeros_like(keep),
            dwh.astype(wh.dtype), dbias.astype(bias.dtype))


bilstm_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


class ImpactLSTM(nn.Module):
    """[B, T, F] event sequences → encrypt-probability logits [B] + embedding.

    Returns dict with `seq_logit` [B] and `seq_emb` [B, hidden].
    """

    cfg: LSTMConfig

    def _fused_bilayer(self, x, valid, layer: int):
        """One BiLSTM layer as a single scan: [B,T,H_in] → (fwd, bwd).

        ``x`` is prefix-first (real events, then padding); ``valid`` [B,T] is
        1 on its real steps.  Direction 1 reads the statically reversed
        sequence — padding first — and its new ``h`` / ``c`` are multiplied
        by that step's validity, so its state is exactly zero until the
        first real event and its outputs on padding are exactly zero.
        Direction 0 takes no mask: its padding follows its real steps and
        the caller masks it after the merge."""
        cfg = self.cfg
        dt = cfg.dtype
        H = cfg.hidden
        in_f = x.shape[-1]
        # Param scopes named exactly like the RNN implementation's cells
        # (creation order there: layer0 fwd, layer0 bwd, layer1 fwd, ...).
        cells = []
        for d in range(2):
            ki, kh, bh = _CellParams(
                H, name=f"OptimizedLSTMCell_{2 * layer + d}")(in_f)
            cells.append((ki.astype(dt), kh, bh))

        # time-major from the narrow side: the H_in-wide input is re-laid
        # once, and the hoisted input projections (one matmul per direction
        # over ALL timesteps — nothing input-dependent remains inside the
        # scan) write the 4H-wide scan inputs as [T,B,4H] directly
        x_tm = jnp.moveaxis(x.astype(dt), -2, 0)            # [T,B,H_in]
        xs_fwd = x_tm @ cells[0][0]                         # [T,B,4H]
        xs_bwd = jnp.flip(x_tm, axis=0) @ cells[1][0]
        keep_bwd = jnp.flip(jnp.moveaxis(valid.astype(dt), -1, 0),
                            axis=0)[..., None]              # [T,B,1]
        keep = jnp.stack([jnp.ones_like(keep_bwd), keep_bwd], axis=1)
        wh = jnp.stack([cells[0][1], cells[1][1]])          # [2,H,4H]
        bias = jnp.stack([cells[0][2], cells[1][2]])        # [2,4H]

        # named scope mirrors the host tracing spine: the recurrence's XLA
        # trace rows appear as lstm_scan in Perfetto next to the
        # train_step_call host span (the reverse rule opens it itself)
        with jax.named_scope("lstm_scan"):
            hs = bilstm_recurrence(xs_fwd, xs_bwd, keep, wh,
                                   bias)                    # [T,2,B,H]
        fwd = jnp.moveaxis(hs[:, 0], 0, -2)                 # [B,T,H]
        # back to original time order
        bwd = jnp.moveaxis(jnp.flip(hs[:, 1], axis=0), 0, -2)
        return fwd, bwd

    @nn.compact
    def __call__(
        self,
        seq_feat,  # [B, T, F] float32
        seq_mask,  # [B, T] bool (True = real event)
        *,
        deterministic: bool = True,
    ) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        dt = cfg.dtype
        x = nn.Dense(cfg.hidden, dtype=dt, name="in_proj")(seq_feat.astype(dt))
        x = nn.gelu(x)
        x = x * seq_mask[..., None].astype(dt)

        # left-padded input → flip to prefix-first layout, so "lengths"
        # bounds the valid prefix for both implementations
        lengths = seq_mask.sum(axis=-1).astype(jnp.int32)
        x = jnp.flip(x, axis=-2)
        mask_pf = jnp.flip(seq_mask, axis=-1)[..., None].astype(dt)
        impl = cfg.resolved_impl()
        for i in range(cfg.num_layers):
            with jax.named_scope(f"lstm_layer_{i}"):
                if impl == "fused":
                    fwd, bwd = self._fused_bilayer(x, mask_pf[..., 0], i)
                else:
                    fwd = nn.RNN(nn.OptimizedLSTMCell(cfg.hidden, dtype=dt),
                                 name=f"fwd_{i}")(x, seq_lengths=lengths)
                    bwd = nn.RNN(nn.OptimizedLSTMCell(cfg.hidden, dtype=dt),
                                 reverse=True, keep_order=True,
                                 name=f"bwd_{i}")(x, seq_lengths=lengths)
                y = jnp.concatenate([fwd, bwd], axis=-1)
                x = nn.Dense(cfg.hidden, dtype=dt, name=f"merge_{i}")(y)
                x = nn.gelu(x)
                x = x * mask_pf

        # mask-aware mean pool over valid steps
        pooled = (x * mask_pf).sum(axis=-2) / jnp.maximum(
            mask_pf.sum(axis=-2), 1.0)
        pooled = nn.LayerNorm(dtype=dt, name="pool_ln")(pooled)
        if cfg.dropout > 0:
            pooled = nn.Dropout(cfg.dropout, deterministic=deterministic)(pooled)
        logit = nn.Dense(1, dtype=jnp.float32, name="head")(pooled)[:, 0]
        return {"seq_logit": logit, "seq_emb": pooled.astype(jnp.float32)}
