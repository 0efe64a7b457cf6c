"""Selective state-space scan (Mamba-1) and its causal depthwise convolution.

The recurrence, per channel ``d`` and state ``n`` (Gu & Dao 2023, eq. 2 with
the zero-order-hold ``A`` and the simplified ``B``):

    s_t = exp(dt_t[d] * A[d, n]) * s_{t-1} + dt_t[d] * x_t[d] * B_t[n]
    y_t[d] = sum_n s_t[d, n] * C_t[n] + D[d] * x_t[d]

with ``s`` reset to zero before the first token of every packed document.

At the stream encoder's widths (``d_inner`` 5120, ``d_state`` 16, T = 8192)
the states of one sequence are ``[T, 5120, 16]`` float32 = 2.7 GB, which
must never exist.  So time is cut into chunks: an outer `lax.scan` carries
the ``[16, 5120]`` state from chunk to chunk, each chunk's body sits behind
`jax.checkpoint`, and reverse mode therefore keeps one state a chunk (10 MB
at 256 steps a chunk) and recomputes a chunk's states when its turn comes.
Inside a chunk time runs as an unrolled `lax.scan`: the state stays
``[16, 5120]`` (channels on the 128 lanes; ``[5120, 16]`` would pad every
state row to 128 lanes and waste seven eighths of each vector operation).

XLA only: no Pallas kernel (PERF.md section 6, PR 28 has the device times
that would justify one).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

SCAN_CHUNK = 256   # steps a chunk: what reverse mode recomputes at a time
SCAN_UNROLL = 8    # steps a loop iteration inside a chunk


def _chunk_states(s0, x, dt, b, c, keep, *, a_t):
    """One chunk of the recurrence.  ``s0`` [N, D]; ``x``, ``dt`` [L, D];
    ``b``, ``c`` [L, N]; ``keep`` [L] (0 where a document starts);
    ``a_t`` [N, D].  -> (state after the chunk, ``sum_n s_t C_t`` [L, D])."""

    def step(s, inp):
        x_t, dt_t, b_t, c_t, keep_t = inp
        decay = jnp.exp(dt_t[None, :] * a_t) * keep_t
        s = decay * s + (dt_t * x_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    return jax.lax.scan(step, s0, (x, dt, b, c, keep), unroll=SCAN_UNROLL)


def selective_scan(x, dt, a, b, c, d, first, chunk: int = SCAN_CHUNK):
    """``x``, ``dt`` [T, D] (``dt`` after its softplus); ``a`` [D, N]
    (negative); ``b``, ``c`` [T, N]; ``d`` [D]; ``first`` [T] bool, true at
    a document's first token.  -> ``y`` [T, D] float32.  One sequence:
    `jax.vmap` it over a batch."""
    t, width = x.shape
    n = a.shape[1]
    chunk = min(chunk, t)
    if t % chunk:
        raise ValueError(f"sequence length {t} is not whole chunks of {chunk}")
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    keep = 1.0 - first.astype(f32)
    a_t = a.astype(f32).T
    with jax.named_scope("ssm_scan"):
        body = jax.checkpoint(partial(_chunk_states, a_t=a_t),
                              prevent_cse=False)
        cut = lambda v: v.reshape((t // chunk, chunk) + v.shape[1:])

        def outer(s, inp):
            return body(s, *inp)

        _, y = jax.lax.scan(outer, jnp.zeros((n, width), f32),
                            (cut(x), cut(dt), cut(b), cut(c), cut(keep)))
        y = y.reshape(t, width)
    return y + d.astype(f32)[None, :] * x


def causal_conv1d(x, w, bias, seg):
    """Depthwise causal convolution over time, within a document.  ``x``
    [T, D]; ``w`` [K, D] (``w[K-1]`` weighs the current step); ``bias`` [D];
    ``seg`` [T] int segment ids.  A tap that would reach across a document's
    start reads zero."""
    k = w.shape[0]
    with jax.named_scope("ssm_conv"):
        out = x * w[k - 1][None, :]
        for j in range(1, k):
            same = jnp.concatenate(
                [jnp.zeros((j,), bool), seg[j:] == seg[:-j]])
            past = jnp.concatenate(
                [jnp.zeros((j,) + x.shape[1:], x.dtype), x[:-j]])
            out = out + jnp.where(same[:, None], past, 0) * w[k - 1 - j][None, :]
        return out + bias[None, :]
