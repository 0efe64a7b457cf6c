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
in fusions, not in FLOPs.  Param tree is bit-compatible with the previous
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
            cells.append((ki.astype(dt), kh.astype(dt), bh.astype(dt)))

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

        batch_shape = x.shape[:-2]  # [B] (or () for unbatched input)
        # bias must broadcast against [2, *batch_shape, 4H] whatever the
        # batch rank — a fixed [:, None, :] breaks the unbatched case
        bias = jnp.stack([cells[0][2], cells[1][2]]).reshape(
            (2,) + (1,) * len(batch_shape) + (-1,))
        h0 = jnp.zeros((2,) + batch_shape + (H,), dt)
        c0 = jnp.zeros_like(h0)

        def step(carry, inp):
            h, c = carry
            x_fwd, x_bwd, keep_t = inp
            gates = (jnp.stack([x_fwd, x_bwd])
                     + jnp.einsum("d...h,dhg->d...g", h, wh) + bias)
            gi, gf, gg, go = jnp.split(gates, 4, axis=-1)
            c = (nn.sigmoid(gf) * c + nn.sigmoid(gi) * jnp.tanh(gg)) * keep_t
            h = nn.sigmoid(go) * jnp.tanh(c) * keep_t
            return (h, c), h

        # named scope mirrors the host tracing spine: the recurrence's XLA
        # trace rows appear as lstm_scan in Perfetto next to the
        # train_step_call host span
        with jax.named_scope("lstm_scan"):
            (_, _), hs = jax.lax.scan(
                step, (h0, c0), (xs_fwd, xs_bwd, keep))     # [T,2,B,H]
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
