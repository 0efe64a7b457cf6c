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
chunks in reverse, recomputing one chunk's forward from its kept state.  At
8192 tokens, 32 heads of 128 x 128 and chunks of 64, a layer's remat keeps
the 128 chunk-start states ``[128, 32, 128, 128]`` float32 (268 MB) and
``o`` ``[8192, 32, 128]`` in bfloat16 (67 MB): 335 MB a layer.

Two routes, chosen by `delta_route` from the backend and the static shapes
and by nothing else (docs/kernel-paths.md), both under
``jax.named_scope("kda_core")`` forward and backward:

* ``pallas`` (a TPU, both head widths whole lanes): the chunk walk as two
  fused kernels, the state in the chip's vector memory for the whole
  sequence and a chunk's pairwise work never leaving it.  ``kda_fwd`` walks
  the chunks of a block of `HEAD_BLOCK` heads and keeps the state at each
  chunk's start; ``kda_bwd`` walks them in reverse, recomputes a chunk from
  its kept state and carries the state's gradient.  The triangular solve is
  a blocked substitution: the diagonal sub-blocks' inverses on the vector
  unit, the blocks above them as ``T - T E T`` on the matrix unit.
* ``xla_chunked`` (everywhere else; the fused route's oracle): an outer
  `lax.scan` over the chunks carrying the state, the solve XLA's
  `triangular_solve`, and the backward pass one chunk's `jax.vjp` at a time.

Both take each product at the precision of the other: the einsums at the
default (one pass of bf16 operands on a TPU), the diagonal sub-blocks'
channel sums in float32, the solve and its transpose at ``highest``.
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


def delta_route(t: int, d_k: int, d_v: int) -> str:
    """Which form `chunk_delta` traces for ``t`` steps of heads with ``d_k``
    keys and ``d_v`` values: a function of the backend and these static
    shapes, nothing else.  ``"pallas"`` (the fused kernels) on a TPU when
    both widths are whole lanes and ``t``, padded to whole chunks, is whole
    kernel chunks; ``"xla_chunked"`` otherwise (docs/kernel-paths.md)."""
    padded = t + -t % CHUNK
    if (jax.default_backend() == "tpu" and d_k % _LANES == 0
            and d_v % _LANES == 0 and padded % KERNEL_CHUNK == 0):
        return "pallas"
    return "xla_chunked"


def chunk_delta(q, k, v, g, beta, seg, *, chunk: int = CHUNK):
    """One packed sequence.  ``q``, ``k`` [T, H, dk] (``q`` already scaled,
    both L2-normalised), ``v`` [T, H, dv] in the compute type; ``g`` [T, H,
    dk] float32 log-decays (``<= 0``); ``beta`` [T, H] float32; ``seg`` [T]
    segment ids (a document starts where the id changes, and at 0) -> o [T,
    H, dv] in ``v``'s type.  ``T`` need not be whole chunks: the last is
    padded with steps that write nothing.  ``chunk`` is the
    ``xla_chunked`` route's."""
    t = q.shape[0]
    fused = delta_route(t, q.shape[2], v.shape[2]) == "pallas"
    if fused:
        chunk = KERNEL_CHUNK
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    with jax.named_scope(SCOPE):
        pad = -t % chunk
        if pad:
            grow = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            q, k, v, g, beta, first = (grow(x) for x in (q, k, v, g, beta,
                                                         first))
        args = (q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32),
                first)
        o = (_fused(*args) if fused else _delta(*args, chunk))[:t]
        return checkpoint_name(o, SAVED)


# --------------------------------------------------------------------------
# the fused route: the state and a chunk's pairwise work never leave the
# chip's vector memory
# --------------------------------------------------------------------------

# steps a grid step of the kernels (a chunk of the chunked form, cut into
# sub-blocks of `SUB`)
KERNEL_CHUNK = 64
# heads a grid step: their chunks are independent work the scheduler
# interleaves (the solve's dependent products of one head fill the gaps
# of another's)
HEAD_BLOCK = 4
_LANES = 128
# rows of a float32 tile; diagonal (step, tile) pairs between a head's
# yields (`_interleave`)
_ROWS = 8
_STRIDE = 6
HIGHEST = jax.lax.Precision.HIGHEST


def _head_block(heads: int) -> int:
    return next(b for b in (HEAD_BLOCK, 4, 2, 1) if heads % b == 0)


def _dot(a, b, ca: int, cb: int, exact: bool = False):
    """``a`` contracted on its axis ``ca`` with ``b`` on ``cb``, float32
    out: at the default precision (one pass of bf16 operands on a TPU, as
    the chunked form's einsums), or at ``highest`` where the chunked form's
    triangular solve and its transpose compute."""
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())),
        precision=HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _scan_rows(x, reverse: bool = False):
    """Inclusive sums along the rows of ``x`` [L, W] (from the last row
    when ``reverse``), in float32 adds: log2(L) rounds of a sublane
    rotation, a select and an add."""
    from jax.experimental.pallas import tpu as pltpu

    n = x.shape[0]
    rows = _iota(x.shape, 0)
    shift = 1
    while shift < n:
        if reverse:
            x = x + jnp.where(rows < n - shift, pltpu.roll(x, n - shift, 0),
                              0.0)
        else:
            x = x + jnp.where(rows >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _refs(cum):
    """-> (the cumulative log-decay before each sub-block, ``[1, dk]``
    rows, the first ``None``; the same behind every row of the chunk)."""
    c, dk = cum.shape
    refs = [None] + [cum[a * SUB - 1:a * SUB] for a in range(1, c // SUB)]
    rows = jnp.concatenate([jnp.zeros((SUB, dk), jnp.float32)] + [
        jnp.broadcast_to(r, (SUB, dk)) for r in refs[1:]], axis=0)
    return refs, rows


def _far(ref, cum, k, lo: int):
    """``exp(R - G_j)`` for the ``lo`` steps before a sub-block (both
    exponents ``<= 0``), zero after them, and that times ``k_j``."""
    c, dk = cum.shape
    e = jnp.concatenate([jnp.exp(ref - cum[:lo]),
                         jnp.zeros((c - lo, dk), jnp.float32)], axis=0)
    return e, e * k


def _inner(cum, j: int, rows: int):
    """``exp(G_i - G_j)`` for the `_ROWS` steps ``i`` from ``rows``: zero
    for ``i < j`` (a positive exponent is never taken)."""
    tile = cum[rows:rows + _ROWS] - cum[j:j + 1]
    if rows < j:
        tile = jnp.where(_iota(tile.shape, 0) + rows >= j, tile, -jnp.inf)
    return jnp.exp(tile)


def _gated(q, k, cum):
    """The kernels' `_gated_products` for one head and chunk: ``q``, ``k``,
    ``cum`` [C, dk] float32 -> (kk, qk) [C, C], ``j <= i`` only.  Below the
    diagonal sub-blocks two matrix products a sub-block at the default
    precision; inside them the float32 products of the elementwise decay,
    summed over the channels.  A generator (`_interleave`)."""
    c = cum.shape[0]
    refs, ref_rows = _refs(cum)
    near = jnp.exp(cum - ref_rows)
    kn, qn = k * near, q * near
    lanes = _iota((_ROWS, c), 1)
    kk, qk = {}, {}
    for a in range(c // SUB):
        lo = a * SUB
        if a:
            _, far = _far(refs[a], cum, k, lo)
            off = _dot(jnp.concatenate([kn[lo:lo + SUB], qn[lo:lo + SUB]]),
                       far, 1, 1)
        else:
            off = jnp.zeros((2 * SUB, c), jnp.float32)
        n = 0
        for r in range(lo, lo + SUB, _ROWS):
            kk[r] = off[r - lo:r - lo + _ROWS]
            qk[r] = off[SUB + r - lo:SUB + r - lo + _ROWS]
            # a tile of rows r and the steps j of the block up to its last
            # row
            for j in range(lo, r + _ROWS):
                ek = _inner(cum, j, r) * k[j:j + 1]
                at = lanes == j
                kk[r] = jnp.where(at, jnp.sum(k[r:r + _ROWS] * ek, axis=1,
                                              keepdims=True), kk[r])
                qk[r] = jnp.where(at, jnp.sum(q[r:r + _ROWS] * ek, axis=1,
                                              keepdims=True), qk[r])
                n += 1
                if n % _STRIDE == 0:
                    yield
    rows = range(0, c, _ROWS)
    return (jnp.concatenate([kk[r] for r in rows]),
            jnp.concatenate([qk[r] for r in rows]))


def _gated_bwd(q, k, cum, dkk, dqk):
    """Reverse mode of `_gated` from the gradients of ``kk`` and ``qk`` ->
    (``sum_j dKK_ij E_ij k_j``, ``sum_j dQK_ij E_ij k_j``, ``sum_i (dKK_ij
    k_i + dQK_ij q_i) E_ij``), each [C, dk]: the gradients of ``k`` as a
    row, of ``q``, and of ``k`` as a column.  The log-decays' follow
    from them (``d G_i = k_i dk_row_i + q_i dq_i``, ``d G_j = -k_j
    dk_col_j``): a reference point cancels out of every pair.  A
    generator."""
    c, dk = cum.shape
    refs, ref_rows = _refs(cum)
    near = jnp.exp(cum - ref_rows)
    zero = jnp.zeros((SUB, dk), jnp.float32)
    dk_row, dq_row, col = {}, {}, {}
    dk_col = jnp.zeros((c, dk), jnp.float32)
    for a in range(c // SUB):
        lo = a * SUB
        if a:
            e, far = _far(refs[a], cum, k, lo)
            nb = near[lo:lo + SUB]
            d = jnp.concatenate([dkk[lo:lo + SUB], dqk[lo:lo + SUB]])
            to_row = _dot(d, far, 1, 0)
            dkr, dqr = to_row[:SUB] * nb, to_row[SUB:] * nb
            dk_col = dk_col + e * _dot(d, jnp.concatenate(
                [k[lo:lo + SUB] * nb, q[lo:lo + SUB] * nb]), 0, 0)
        else:
            dkr = dqr = zero
        n = 0
        for r in range(lo, lo + SUB, _ROWS):
            dk_row[r] = dkr[r - lo:r - lo + _ROWS]
            dq_row[r] = dqr[r - lo:r - lo + _ROWS]
            # a tile of rows r and the steps j of the block up to its last
            # row, a tile of them at a time (their column sums: `_row_sums`)
            for g in range(lo, r + 1, _ROWS):
                parts = []
                for j in range(g, g + _ROWS):
                    e = _inner(cum, j, r)
                    ek = e * k[j:j + 1]
                    dkk_j = dkk[r:r + _ROWS, j:j + 1]
                    dqk_j = dqk[r:r + _ROWS, j:j + 1]
                    dk_row[r] = dk_row[r] + dkk_j * ek
                    dq_row[r] = dq_row[r] + dqk_j * ek
                    parts.append((dkk_j * k[r:r + _ROWS]
                                  + dqk_j * q[r:r + _ROWS]) * e)
                    n += 1
                    if n % _STRIDE == 0:
                        yield
                part = _row_sums(parts)
                col[g] = part if g not in col else col[g] + part
    rows = range(0, c, _ROWS)
    return (jnp.concatenate([dk_row[r] for r in rows]),
            jnp.concatenate([dq_row[r] for r in rows]),
            dk_col + jnp.concatenate([col[r] for r in rows]))


def _row_sums(parts):
    """`_ROWS` arrays [`_ROWS`, W] -> [`_ROWS`, W] whose row m is the sum of
    ``parts[m]``'s rows: three rounds that each fold two tiles of partial
    sums into one (a sublane rotation, a select, an add)."""
    from jax.experimental.pallas import tpu as pltpu

    rows = _iota(parts[0].shape, 0)
    shift = 1
    while len(parts) > 1:
        upper = (rows & shift) != 0
        parts = [jnp.where(upper, b + pltpu.roll(b, shift, 0),
                           a + pltpu.roll(a, _ROWS - shift, 0))
                 for a, b in zip(parts[::2], parts[1::2])]
        shift *= 2
    return parts[0]


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` [C, C] strictly lower triangular, C a power
    of two times `SUB`, as a blocked substitution.  The diagonal blocks of
    `SUB` steps by forward substitution in float32 on the vector unit, all
    of them at once (`SUB` - 1 dependent rounds: row ``t`` of every block
    is final, then subtracted from the rows below it); then the inverses of
    the diagonal blocks twice as large, in turn, each from those below as
    ``T - T E T`` (``E`` the lower-left quadrants of the larger blocks), the
    products at ``highest`` on the matrix unit.  A generator."""
    c = a.shape[0]
    i, j = _iota(a.shape, 0), _iota(a.shape, 1)
    eye = (i == j).astype(jnp.float32)
    # the diagonal blocks, a tile of `_ROWS` rows at a time
    base = lambda r: r - r % SUB
    diag = {r: jnp.where((j[:_ROWS] >= base(r))
                         & (j[:_ROWS] < base(r) + SUB), a[r:r + _ROWS], 0.0)
            for r in range(0, c, _ROWS)}
    inv = {r: eye[r:r + _ROWS] for r in range(0, c, _ROWS)}
    for t in range(SUB - 1):
        for b in range(0, c, SUB):
            row = inv[b + t - t % _ROWS][t % _ROWS:t % _ROWS + 1]
            for r in range(b, b + SUB, _ROWS):
                if r + _ROWS - 1 > b + t:
                    # the coefficients of row t: column b + t
                    coef = jnp.sum(jnp.where(j[:_ROWS] == b + t, diag[r],
                                             0.0), axis=1, keepdims=True)
                    inv[r] = inv[r] - coef * row
        yield
    inv = jnp.concatenate([inv[r] for r in range(0, c, _ROWS)])
    s = SUB
    while s < c:
        e = jnp.where((i // (2 * s) == j // (2 * s)) & ((i & s) != 0)
                      & ((j & s) == 0), a, 0.0)
        inv_e = _dot(e, inv, 1, 0, exact=True)
        yield
        inv = inv - _dot(inv, inv_e, 1, 0, exact=True)
        yield
        s *= 2
    return inv


def _recompute(s0, q, k, v, g, beta, mask, carry, last):
    """One head's chunk, forward, from the state entering it: ``s0`` the
    state transposed, [dv, dk]; ``q``, ``k``, ``g`` [C, dk], ``v`` [C, dv],
    float32; ``beta``, ``carry`` (the steps that read ``s0``), ``last``
    (the steps of the chunk's last document) [C, 1]; ``mask`` [C, C] the
    pairs of one document with ``j < i`` -> a dict of what the output, the
    state after the chunk and reverse mode read.  A generator."""
    c = q.shape[0]
    cum = _scan_rows(g)
    yield
    kk, qk = yield from _gated(q, k, cum)
    eye = (_iota((c, c), 0) == _iota((c, c), 1)).astype(jnp.float32)
    a = beta * kk * mask
    p = qk * (mask + eye)
    grow = jnp.exp(cum) * carry
    w, qg = grow * k, grow * q
    vm = v - _dot(w, s0, 1, 1)
    inv = yield from _unit_lower_inverse(a)
    u = _dot(inv, beta * vm, 1, 0, exact=True)
    yield
    tail_e = jnp.exp(jnp.where(last > 0, cum[c - 1:] - cum, -jnp.inf))
    return dict(cum=cum, kk=kk, p=p, grow=grow, w=w, qg=qg, vm=vm, inv=inv,
                u=u, tail_e=tail_e, tail=tail_e * k, eye=eye)


def _head_forward(s0, *args):
    """-> (the state after the chunk, transposed, ``o`` [C, dv]).  A
    generator."""
    r = yield from _recompute(s0, *args)
    o = _dot(r["qg"], s0, 1, 1) + _dot(r["p"], r["u"], 1, 0)
    s1 = s0 * r["grow"][-1:] + _dot(r["u"], r["tail"], 0, 0)
    return s1, o


def _head_reverse(s0, d_s1, q, k, v, g, beta, mask, carry, last, d_o):
    """Reverse mode of one head's chunk: ``d_s1`` the gradient of the state
    after it (transposed), ``d_o`` of ``o`` -> (the gradient of ``s0``, dq,
    dk, dv, dg [C, *], dbeta [C, 1]).  A generator."""
    c = q.shape[0]
    r = yield from _recompute(s0, q, k, v, g, beta, mask, carry, last)
    gl = r["grow"][-1:]
    # o = qg s0 + p u;  s1 = s0 * gl + u^T tail
    d_qg = _dot(d_o, s0, 1, 0)
    d_s0 = _dot(d_o, r["qg"], 0, 0) + d_s1 * gl
    d_p = _dot(d_o, r["u"], 1, 1)
    d_u = _dot(r["p"], d_o, 0, 0) + _dot(r["tail"], d_s1, 1, 1)
    d_gl = jnp.sum(d_s1 * s0, axis=0, keepdims=True)
    d_tail = _dot(r["u"], d_s1, 1, 0)
    yield
    # u = (I + a)^-1 beta vm, the solve's transpose at its precision
    d_rhs = _dot(r["inv"], d_u, 0, 0, exact=True)
    yield
    d_a = -_dot(d_rhs, r["u"], 1, 1, exact=True)
    d_vm = beta * d_rhs
    d_beta = (jnp.sum(d_rhs * r["vm"], axis=1, keepdims=True)
              + jnp.sum(d_a * r["kk"] * mask, axis=1, keepdims=True))
    d_w = -_dot(d_vm, s0, 1, 0)
    d_s0 = d_s0 - _dot(d_vm, r["w"], 0, 0)
    yield
    # a = beta kk mask, p = qk (mask + I)
    dk_row, dq_row, dk_col = yield from _gated_bwd(
        q, k, r["cum"], beta * d_a * mask, d_p * (mask + r["eye"]))
    grow = r["grow"]
    dq = dq_row + d_qg * grow
    dk = dk_row + dk_col + d_w * grow + d_tail * r["tail_e"]
    tt = d_tail * r["tail"]
    d_cum = (k * dk_row + q * dq_row - k * dk_col
             + (d_w * k + d_qg * q) * grow - tt)
    at_end = jnp.sum(tt, axis=0, keepdims=True) + d_gl * gl
    d_cum = d_cum + jnp.where(_iota(d_cum.shape, 0) == c - 1, at_end, 0.0)
    return d_s0, dq, dk, d_vm, _scan_rows(d_cum, reverse=True), d_beta


def _interleave(programs):
    """Run generators to their ends, a step of each in turn -> their
    return values.  The heads of a grid step are independent, and traced
    in turn between their yields their operations alternate in the
    kernel's program: the matrix unit's queues and the scheduler then
    overlap one head's dependent chain with another's."""
    done = [None] * len(programs)
    live = list(range(len(programs)))
    while live:
        for h in list(live):
            try:
                next(programs[h])
            except StopIteration as end:
                done[h] = end.value
                live.remove(h)
    return done


def _heads(ref, h: int, width: int):
    return ref[:, h * width:(h + 1) * width].astype(jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, mask_ref, flag_ref,
                o_ref, start_ref, s_ref):
    """One (head block, chunk) grid step: ``q``, ``k``, ``g`` [C, hb dk],
    ``v`` [C, hb dv]; ``beta`` [C, hb]; ``mask`` [C, C]; ``flag`` [C, 2]
    (``carry``, ``last``) -> ``o`` [C, hb dv], ``start`` [hb, dv, dk] the
    states entering the chunk; ``s`` the states carried across the chunks
    of a head block, transposed."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)

    hb, dv, dk = s_ref.shape
    mask, carry, last = mask_ref[...], flag_ref[:, 0:1], flag_ref[:, 1:2]
    s0 = [s_ref[h] for h in range(hb)]
    out = _interleave([_head_forward(
        s0[h], _heads(q_ref, h, dk), _heads(k_ref, h, dk),
        _heads(v_ref, h, dv), _heads(g_ref, h, dk), beta_ref[:, h:h + 1],
        mask, carry, last) for h in range(hb)])
    for h, (s1, o) in enumerate(out):
        start_ref[h] = s0[h]
        s_ref[h] = s1
        o_ref[:, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, mask_ref, flag_ref,
                start_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                ds_ref):
    """One (head block, chunk) grid step, the chunks in reverse: each head's
    chunk recomputed from the state kept at its start, then its reverse
    mode with ``ds`` [hb, dv, dk], the gradient reaching the state from the
    chunks after this one."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros(ds_ref.shape, jnp.float32)

    hb, dv, dk = ds_ref.shape
    mask, carry, last = mask_ref[...], flag_ref[:, 0:1], flag_ref[:, 1:2]
    out = _interleave([_head_reverse(
        start_ref[h], ds_ref[h], _heads(q_ref, h, dk), _heads(k_ref, h, dk),
        _heads(v_ref, h, dv), _heads(g_ref, h, dk), beta_ref[:, h:h + 1],
        mask, carry, last, _heads(do_ref, h, dv)) for h in range(hb)])
    lanes = _iota(dbeta_ref.shape, 1)
    d_beta = jnp.zeros(dbeta_ref.shape, jnp.float32)
    for h, (d_s0, dq, dkh, dvh, dg, db) in enumerate(out):
        ds_ref[h] = d_s0
        for ref, x, width in ((dq_ref, dq, dk), (dk_ref, dkh, dk),
                              (dv_ref, dvh, dv), (dg_ref, dg, dk)):
            ref[:, h * width:(h + 1) * width] = x.astype(ref.dtype)
        d_beta = jnp.where(lanes == h, db, d_beta)
    dbeta_ref[...] = d_beta


def _layout(beta, first):
    """What the kernels read beside the rows: ``beta`` [H / hb, T, hb];
    ``mask`` [T / C, C, C] (one document's pairs with ``j < i`` in each
    chunk, float32); ``flag`` [T, 2] (the steps before the chunk's first
    document start, the steps of its last document)."""
    t, heads = beta.shape
    hb, c = _head_block(heads), KERNEL_CHUNK
    starts = jnp.cumsum(first.reshape(t // c, c).astype(jnp.int32), axis=1)
    i = jnp.arange(c)
    mask = ((starts[:, :, None] == starts[:, None, :])
            & (i[None, :] < i[:, None])).astype(jnp.float32)
    flag = jnp.stack([starts == 0, starts == starts[:, -1:]],
                     axis=-1).reshape(t, 2).astype(jnp.float32)
    beta = beta.reshape(t, heads // hb, hb).transpose(1, 0, 2)
    return hb, beta, mask, flag


def _specs(t, hb, dk, dv, back=False):
    from jax.experimental import pallas as pl

    n, c = t // KERNEL_CHUNK, KERNEL_CHUNK
    at = (lambda k: n - 1 - k) if back else (lambda k: k)
    keys = pl.BlockSpec((c, hb * dk), lambda i, k: (at(k), i))
    vals = pl.BlockSpec((c, hb * dv), lambda i, k: (at(k), i))
    beta = pl.BlockSpec((None, c, hb), lambda i, k: (i, at(k), 0))
    mask = pl.BlockSpec((None, c, c), lambda i, k: (at(k), 0, 0))
    flag = pl.BlockSpec((c, 2), lambda i, k: (at(k), 0))
    state = pl.BlockSpec((None, hb, dv, dk), lambda i, k: (at(k), i, 0, 0))
    return keys, vals, beta, mask, flag, state


def _kernel_forward(q, k, v, g, beta, first):
    """-> (o [T, H, dv] in ``v``'s type, the state entering every chunk,
    transposed, [T / C, H, dv, dk] float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, heads, dk = q.shape
    dv = v.shape[2]
    hb, beta, mask, flag = _layout(beta, first)
    keys, vals, beta_s, mask_s, flag_s, state = _specs(t, hb, dk, dv)
    flat = lambda x: x.reshape(t, -1)
    o, states = pl.pallas_call(
        _fwd_kernel,
        grid=(heads // hb, t // KERNEL_CHUNK),
        in_specs=[keys, keys, vals, keys, beta_s, mask_s, flag_s],
        out_specs=[vals, state],
        out_shape=[jax.ShapeDtypeStruct((t, heads * dv), v.dtype),
                   jax.ShapeDtypeStruct((t // KERNEL_CHUNK, heads, dv, dk),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        name="kda_fwd",
    )(flat(q), flat(k), flat(v), flat(g), beta, mask, flag)
    return o.reshape(t, heads, dv), states


def _kernel_backward(q, k, v, g, beta, first, states, d_o):
    """-> (dq, dk, dv in their inputs' types, dg, dbeta float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, heads, dk = q.shape
    dv = v.shape[2]
    hb, beta, mask, flag = _layout(beta, first)
    keys, vals, beta_s, mask_s, flag_s, state = _specs(t, hb, dk, dv,
                                                       back=True)
    flat = lambda x: x.reshape(t, -1)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        _bwd_kernel,
        grid=(heads // hb, t // KERNEL_CHUNK),
        in_specs=[keys, keys, vals, keys, beta_s, mask_s, flag_s, state,
                  vals],
        out_specs=[keys, keys, vals, keys, beta_s],
        out_shape=[jax.ShapeDtypeStruct((t, heads * dk), q.dtype),
                   jax.ShapeDtypeStruct((t, heads * dk), k.dtype),
                   jax.ShapeDtypeStruct((t, heads * dv), v.dtype),
                   jax.ShapeDtypeStruct((t, heads * dk), jnp.float32),
                   jax.ShapeDtypeStruct(beta.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        name="kda_bwd",
    )(flat(q), flat(k), flat(v), flat(g), beta, mask, flag, states,
      flat(d_o))
    dbeta = dbeta.transpose(1, 0, 2).reshape(t, heads)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape), dbeta)


@jax.custom_vjp
def _fused(q, k, v, g, beta, first):
    return _fused_fwd(q, k, v, g, beta, first)[0]


def _fused_fwd(q, k, v, g, beta, first):
    with jax.named_scope(SCOPE):
        o, states = _kernel_forward(q, k, v, g, beta, first)
    # kept as the chunked form keeps its own (`SAVED`)
    o, states = checkpoint_name(o, SAVED), checkpoint_name(states, SAVED)
    return o, (q, k, v, g, beta, first, states)


def _fused_bwd(res, d_o):
    with jax.named_scope(SCOPE):
        grads = _kernel_backward(*res, d_o)
    return grads + (None,)


_fused.defvjp(_fused_fwd, _fused_bwd)
