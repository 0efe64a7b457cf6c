"""The channel-gated delta rule (Kimi Delta Attention, arXiv:2510.26692) over
one packed sequence, in chunked form.

Per head, with a ``[d_k, d_v]`` state ``S``, a per-channel log-decay ``g_t``
(``<= 0``, float32) and a write strength ``beta_t`` in (0, 1):

    S <- Diag(exp g_t) S                          (decay, then the update)
    S <- S + beta_t k_t (v_t - S^T k_t)^T
    o_t = S^T q_t

that is ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t
v_t^T``, with ``S`` zeroed before the first token of every packed document.

Chunked form (the WY / UT representation of the delta rule with a decay per
channel).  Time is cut into chunks of ``CHUNK`` steps; inside a chunk ``G_i``
is the cumulative log-decay up to and including step ``i`` and ``S_0`` the
state entering the chunk.  Every update is a pseudo-value ``u_j`` written
along ``k_j``: ``S_i = Diag(exp G_i) S_0 + sum_{j <= i} Diag(exp(G_i - G_j))
k_j u_j^T``, and the chunk's ``u`` solve one unit lower-triangular system

    (I + A) u = beta * (v - (exp(G) * k) S_0),
    A_ij = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])      (j < i)
    o = (exp(G) * q) S_0 + P u,
    P_ij = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])             (j <= i)

and the state leaving it is ``exp(G_C) * S_0 + (exp(G_C - G_j) * k_j)^T u``.
A positive cumulative log-decay is never exponentiated: ``exp(G_i)`` and
``exp(-G_j)`` apart would overflow float32 once a chunk's decays sum under
about -88 (one step of this gate can give -320).  The chunk is cut into
sub-blocks of ``SUB`` steps, and with ``R`` the cumulative log-decay before
``i``'s sub-block, ``exp(G_i - G_j) = exp(G_i - R) exp(R - G_j)`` for ``j``
in an earlier sub-block: both exponents are ``<= 0``, so ``A`` and ``P``
below the diagonal sub-blocks are matrix products; inside a diagonal
sub-block ``exp(G_i - G_j)`` is formed from the difference, ``j <= i`` only,
a ``[SUB, SUB, d_k]`` array a head and sub-block.  A document's start inside
a chunk cuts every pair across it and the state entering the chunk from
every step after it (masks from the count of starts so far, never an
infinite decay).

All of it is float32 (the state, the decays, every product) from operands in
the compute type; the output leaves in the compute type.  Reverse mode is
written by hand (`jax.custom_vjp`): the forward pass keeps the state entering
every chunk and ``o`` under the name `SAVED`, and the backward pass walks the
chunks in reverse, recomputing one chunk's forward from its kept state with
`jax.vjp`.  At 8192 tokens, 32 heads of 128 x 128 and chunks of 64, a layer's
remat keeps the 128 chunk-start states ``[128, 32, 128, 128]`` float32 (268
MB) and ``o`` ``[8192, 32, 128]`` in bfloat16 (67 MB): 335 MB a layer.

One form on every backend, plain XLA: an outer `lax.scan` over the chunks
carrying the state.  Under ``jax.named_scope("kda_core")`` forward and
backward.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# steps a chunk, and a sub-block of it: below the diagonal sub-blocks a
# chunk's pairwise decays are two matrix products, inside them [heads,
# CHUNK / SUB, SUB, SUB, d_k] float32 (17 MB at 32 heads of 128)
CHUNK = 64
SUB = 16
# the name under which the forward pass's residuals can be kept by a remat
# policy (`jax.checkpoint_policies.save_only_these_names`)
SAVED = "kda_saved"
SCOPE = "kda_core"


def _gated_products(q, k, cum, sub: int):
    """-> (``sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])``, the same with
    ``q_i``), each [H, C, C], for ``j <= i`` (0 above the diagonal).  Below
    the diagonal sub-blocks of ``sub`` steps the decay is ``exp(G_i - R)
    exp(R - G_j)``, ``R`` the cumulative log-decay before ``i``'s sub-block:
    both exponents are ``<= 0``, and the pairs are matrix products; inside a
    diagonal sub-block it is formed from the difference."""
    f32 = jnp.float32
    h, c, dk = k.shape
    n = c // sub
    blocks = lambda x: x.reshape(h, n, sub, dk)
    # R_a [H, n, dk]: the cumulative log-decay before sub-block a
    ref = jnp.concatenate([jnp.zeros((h, 1, dk), f32),
                           cum[:, sub - 1:c - 1:sub]], axis=1)
    near = jnp.exp(blocks(cum) - ref[:, :, None])           # i in block a
    before = jnp.arange(c)[None, :] < sub * jnp.arange(n)[:, None]
    far = jnp.exp(jnp.where(before[None, :, :, None],
                            ref[:, :, None] - cum[:, None],
                            -jnp.inf)) * k[:, None]         # [H, n, C, dk]
    t = jnp.arange(sub)
    inner = jnp.exp(jnp.where(
        (t[None, :] <= t[:, None])[None, None, :, :, None],
        blocks(cum)[:, :, :, None] - blocks(cum)[:, :, None],
        -jnp.inf))                                          # [H, n, S, S, dk]
    place = jnp.eye(n, dtype=f32)

    def products(x):
        off = jnp.einsum("hasc,hajc->hasj", blocks(x) * near, far)
        diag = jnp.einsum("hasc,hastc,hatc->hast", blocks(x), inner,
                          blocks(k))
        return (off + jnp.einsum("hast,ab->hasbt", diag, place).reshape(
            h, n, sub, c)).reshape(h, c, c)

    return products(k), products(q)


def _chunk(s0, q, k, v, g, beta, first):
    """One chunk.  ``s0`` [H, dk, dv] float32; ``q``, ``k`` [C, H, dk];
    ``v`` [C, H, dv]; ``g`` [C, H, dk] float32; ``beta`` [C, H] float32;
    ``first`` [C] bool -> (the state after the chunk, o [C, H, dv]
    float32)."""
    f32 = jnp.float32
    heads_first = lambda x: jnp.swapaxes(x.astype(f32), 0, 1)
    q, k, v, g = (heads_first(x) for x in (q, k, v, g))
    beta = beta.astype(f32).T[..., None]                    # [H, C, 1]
    c = q.shape[1]
    cum = jnp.cumsum(g, axis=1)                             # G_i [H, C, dk]
    starts = jnp.cumsum(first.astype(jnp.int32))            # [C]
    # the steps that still read the state entering the chunk
    carry = (starts == 0).astype(f32)[None, :, None]
    i = jnp.arange(c)
    same = starts[:, None] == starts[None, :]               # [C, C] (i, j)
    kk, qk = _gated_products(q, k, cum, math.gcd(SUB, c))
    a = beta * kk * (same & (i[None, :] < i[:, None]))
    p = qk * (same & (i[None, :] <= i[:, None]))
    grow = jnp.exp(cum) * carry
    rhs = beta * (v - jnp.einsum("hic,hcd->hid", grow * k, s0))
    u = jax.lax.linalg.triangular_solve(
        a + jnp.eye(c, dtype=f32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    o = jnp.einsum("hic,hcd->hid", grow * q, s0) + jnp.einsum(
        "hij,hjd->hid", p, u)
    last = starts == starts[-1]
    tail = jnp.exp(jnp.where(last[None, :, None], cum[:, -1:] - cum,
                             -jnp.inf)) * k                 # [H, C, dk]
    s1 = (grow[:, -1, :, None] * s0
          + jnp.einsum("hjc,hjd->hcd", tail, u))
    return s1, jnp.swapaxes(o, 0, 1)


def _cut(x, chunk):
    return x.reshape((x.shape[0] // chunk, chunk) + x.shape[1:])


def _forward(q, k, v, g, beta, first, chunk):
    """-> (o [T, H, dv] float32, the state entering every chunk [T / chunk,
    H, dk, dv] float32)."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(s, xs):
        s1, o = _chunk(s, *xs)
        return s1, (o, s)

    _, (o, states) = jax.lax.scan(
        step, jnp.zeros((h, dk, dv), jnp.float32),
        tuple(_cut(x, chunk) for x in (q, k, v, g, beta, first)))
    return o.reshape((-1,) + o.shape[2:]), states


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _delta(q, k, v, g, beta, first, chunk):
    return _forward(q, k, v, g, beta, first, chunk)[0].astype(v.dtype)


def _delta_fwd(q, k, v, g, beta, first, chunk):
    o, states = _forward(q, k, v, g, beta, first, chunk)
    o = o.astype(v.dtype)
    # named so that a layer's remat can keep them (`SAVED`): what the
    # backward pass needs of the forward pass's walk over the chunks
    o, states = checkpoint_name(o, SAVED), checkpoint_name(states, SAVED)
    return o, (q, k, v, g, beta, first, states)


def _delta_bwd(chunk, res, d_o):
    q, k, v, g, beta, first, states = res
    h, dk = q.shape[1], q.shape[2]

    def step(d_s, xs):
        s0, q_c, k_c, v_c, g_c, beta_c, first_c, d_o_c = xs
        _, pull = jax.vjp(
            lambda s0, q_c, k_c, v_c, g_c, beta_c: _chunk(
                s0, q_c, k_c, v_c, g_c, beta_c, first_c),
            s0, q_c, k_c, v_c, g_c, beta_c)
        d_s0, *grads = pull((d_s, d_o_c.astype(jnp.float32)))
        return d_s0, tuple(grads)

    with jax.named_scope(SCOPE):
        _, grads = jax.lax.scan(
            step, jnp.zeros((h, dk, v.shape[2]), jnp.float32),
            (states,) + tuple(_cut(x, chunk) for x in (
                q, k, v, g, beta, first, d_o)), reverse=True)
    grads = [x.reshape((-1,) + x.shape[2:]) for x in grads]
    return tuple(d.astype(x.dtype) for d, x in zip(
        grads, (q, k, v, g, beta))) + (None,)


_delta.defvjp(_delta_fwd, _delta_bwd)


def chunk_delta(q, k, v, g, beta, seg, *, chunk: int = CHUNK):
    """One packed sequence.  ``q``, ``k`` [T, H, dk] (``q`` already scaled,
    both L2-normalised), ``v`` [T, H, dv] in the compute type; ``g`` [T, H,
    dk] float32 log-decays (``<= 0``); ``beta`` [T, H] float32; ``seg`` [T]
    segment ids (a document starts where the id changes, and at 0) -> o [T,
    H, dv] in ``v``'s type.  ``T`` need not be whole chunks: the last is
    padded with steps that write nothing."""
    t = q.shape[0]
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    with jax.named_scope(SCOPE):
        pad = -t % chunk
        if pad:
            grow = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            q, k, v, g, beta, first = (grow(x) for x in (q, k, v, g, beta,
                                                         first))
        o = _delta(q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32),
                   first, chunk)[:t]
        return checkpoint_name(o, SAVED)
