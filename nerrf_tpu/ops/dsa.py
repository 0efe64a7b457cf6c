"""Attention over a set of keys the model chooses: a learned indexer scores
every earlier token of a query's document, the ``topk`` best are kept, and
the query attends to those alone (the DeepSeek-Sparse-Attention form).

For a query ``t`` and a key ``s <= t`` of the same document:

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          (`dsa_indexer`)
    S_t     = the min(t + 1, topk) keys of largest I[t, s]    (`dsa_topk`)
    o_t     = sum_{s in S_t} softmax_{S_t}(q_t . k_s / sqrt(d)) v_s
                                                              (`dsa_attention`)
    KL_t    = KL(P_t || softmax_{S_t}(I[t, .]))               (`dsa_indexer_loss`)

with ``P_t`` the attention's probabilities averaged over the query heads,
under `stop_gradient`: the indexer learns to rank the keys as the attention
weighs them, and the selection itself passes no gradient.

The selection is EXACT (`select_topk`): the ``topk``-th largest score of a
row is found bit by bit over an order-preserving integer image of the
float32 scores (32 counting passes: no sort), keys that tie with it are
taken from the earliest on, and of two scores that differ the larger always
wins.  The scores are float32 from float32 operands at ``highest``
precision, so that the set does not depend on a rounding the reference does
not make.

A block of queries at a time against the keys before the block's end,
rounded up to a span (`sparse_attention`); a block whose keys number ``topk``
or fewer keeps every key its masks allow and is never ranked.  The chosen set
is a mask over the block's scores: on a TPU a gather of 2048 keys a query
would move more bytes than the dense products it saves.

The indexer's scores, the selection and the indexer's loss are plain XLA on
every backend, in the scan over the blocks.  The ATTENTION part (the scope
`dsa_attention`, forward and backward) has two routes, chosen by
`attention_route` from the backend and the static shapes and by nothing else
(docs/kernel-paths.md):

* ``pallas_flash`` (a TPU, whole lanes, whole tiles): fused kernels in which
  a tile's scores, probabilities and their derivatives live in the chip's
  vector memory only.  Forward, a block at a time inside the scan
  (`dsa_flash_fwd`): every head of the block against a key tile a step, the
  tile of the chosen set (int8) one more mask term shared by the heads; an
  online softmax, then a second pass over the scores for the heads' mean
  probability, the only [Q, L] array that leaves.  Backward, ONE kernel over
  the whole sequence (`dsa_flash_bwd`): a key-value head's group of query
  heads a step, the scores once more from the saved log-sum-exp, all three
  gradients from them.
* ``xla_blocked`` (everywhere else; the fused route's oracle): the block's
  float32 scores [heads, block, keys] through HBM, alive only while the
  block is computed, forward and backward (a scan over the blocks each way).

Both round where the other rounds (bf16 products accumulated in float32,
softmax in float32, probabilities and the scores' gradient cast to the
compute type before their products), and both keep the same residuals under
the name `SAVED`.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from nerrf_tpu.parallel.ring import _pair_mask

# queries a block.  A block's scores are [heads, block, keys] float32: 0.25 GB
# at 32 heads and 8192 keys, and a pass holds a few such
QUERY_BLOCK = 256
# a block's keys end at a multiple of this: the step program holds one copy
# of the block, forward and backward, for each such length, and the compiler
# gives each copy scratch of its own.  At 1024 a block computes a tenth fewer
# pairs and the six-layer step compiles in 94 s against 65 (compile for a
# described v5e, PR 33); not measured on the chip
KEY_SPAN = 2048
# the name under which the forward pass's residuals can be kept by a remat
# policy (`jax.checkpoint_policies.save_only_these_names`)
SAVED = "dsa_saved"
HIGHEST = jax.lax.Precision.HIGHEST


def doc_positions(seg):
    """Segment ids [T] of packed documents (contiguous) -> each token's
    position inside its document, from 0."""
    t = seg.shape[0]
    idx = jnp.arange(t, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    return idx - jax.lax.cummax(jnp.where(first, idx, 0))


def rope(x, pos, theta: float, *, rotary: int = None, freq=None,
         mscale: float = 1.0):
    """Rotary embedding, rotate-half convention: ``x`` [T, heads, d] with
    the pairs ``(i, i + d / 2)`` turned by ``pos * theta^(-2 i / d)``.
    float32 inside, the input's type out.  ``rotary`` r < d turns the first
    r dimensions alone (pairs ``(i, i + r / 2)``) and passes the rest;
    ``freq`` [r / 2] replaces theta's frequencies (`yarn_frequencies`);
    ``mscale`` multiplies the cosines and sines (YaRN's attention factor:
    the turned dimensions alone are scaled)."""
    d = x.shape[-1]
    r = rotary or d
    # nerrflint: ok[recompile-hazard] freq is None or a numpy constant (`yarn_frequencies` of the static configuration), never a traced value
    if freq is None:
        freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = pos.astype(jnp.float32)[:, None] * freq            # [T, r / 2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    # nerrflint: ok[recompile-hazard] mscale is a Python float of the static configuration (`Rotary.attention_factor`), never a traced value
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    a, b = jnp.split(x[..., :r].astype(jnp.float32), 2, axis=-1)
    turned = [a * cos - b * sin, b * cos + a * sin]
    if r < d:
        turned.append(x[..., r:].astype(jnp.float32))
    return jnp.concatenate(turned, axis=-1).astype(x.dtype)


def yarn_frequencies(rotary: int, theta: float, factor: float,
                     original: int, beta_fast: float, beta_slow: float):
    """YaRN's per-frequency blend of ``rotary`` / 2 rotary frequencies
    (`transformers`' ``_compute_yarn_parameters``, ``truncate`` on): the
    frequencies that turn fewer than ``beta_slow`` times over ``original``
    positions are divided by ``factor``, those that turn more than
    ``beta_fast`` times are kept, and a linear ramp between the floor and the
    ceiling of the two dimensions where that happens blends the rest ->
    float32 [rotary / 2] (a numpy constant)."""
    import numpy as np

    def dim_of(turns):
        return (rotary * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), rotary - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rotary // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    base = theta ** (np.arange(0, rotary, 2, dtype=np.float32) / rotary)
    return ((1.0 / (factor * base)) * (1.0 - keep)
            + (1.0 / base) * keep).astype(np.float32)


def _order_key(x):
    """float32 -> uint32 with the same order; +0 and -0 alike; every finite
    value lands above 0."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    bits = jnp.where(x == 0, jnp.int32(0), bits)
    return jax.lax.bitcast_convert_type(bits, jnp.uint32) ^ jnp.uint32(1 << 31)


def select_topk(scores, valid, k: int):
    """``scores`` [Q, L] float32, ``valid`` [Q, L] bool -> bool [Q, L]: per
    row the ``min(k, valid keys)`` valid keys of largest score; among keys
    that tie with the last one kept, the earliest."""
    key = jnp.where(valid, _order_key(scores), jnp.uint32(0))

    def bit(i, kth):
        # the largest value that k keys reach, one bit a pass from the top
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        reach = jnp.sum(key >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(reach >= k, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(key.shape[:1], jnp.uint32))
    above = key > kth[:, None]
    tie = (key == kth[:, None]) & valid
    room = k - jnp.sum(above, axis=1, dtype=jnp.int32)
    return above | (tie & (jnp.cumsum(tie, axis=1, dtype=jnp.int32)
                           <= room[:, None]))


def _index_dots(qi, ki):
    """``qi`` [Q, J, e], ``ki`` [L, e], float32 -> [J, Q, L], before relu."""
    return jnp.einsum("qje,le->jql", qi, ki, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def index_scores(qi, ki, wi):
    """``qi`` [Q, J, e], ``ki`` [L, e], ``wi`` [Q, J], float32 -> I [Q, L]."""
    return jnp.sum(jax.nn.relu(_index_dots(qi, ki)) * wi.T[:, :, None],
                   axis=0)


def _allowed(nq: int, nk: int, q_seg, k_seg, q_pos0):
    q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, (nq, 1), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, nk), 1)
    return _pair_mask(q_pos, k_pos, True, None, q_seg[None], k_seg[None])[0]


def _choose(qi, ki, wi, q_seg, k_seg, q_pos0, topk: int):
    """A block of queries against the keys ``[0, L)`` -> the chosen keys
    [Q, L] bool."""
    valid = _allowed(qi.shape[0], ki.shape[0], q_seg, k_seg, q_pos0)
    if ki.shape[0] <= topk:
        return valid
    return select_topk(index_scores(qi, ki, wi), valid, topk)


def _attend_xla(q, k, v, chosen, q_pos0):
    """The attention of one block over its chosen keys, plain XLA: ``q``
    [Q, Hk, G, d], ``k``, ``v`` [L, Hk, d], ``chosen`` [Q, L] -> (o [Q, Hk,
    G, d] float32, each head's log-sum-exp [Hk, G, Q], the heads' mean
    probability [Q, L]).  The float32 scores [Hk, G, Q, L] go through HBM."""
    del q_pos0
    logits = jnp.einsum("qhgd,lhd->hgql", q, k,
                        preferred_element_type=jnp.float32)
    logits = jnp.where(chosen, logits * q.shape[-1] ** -0.5, -1e9)
    top = jnp.max(logits, axis=-1, keepdims=True)
    pexp = jnp.exp(logits - top)
    norm = jnp.sum(pexp, axis=-1, keepdims=True)
    o = jnp.einsum("hgql,lhd->qhgd", pexp.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o / jnp.moveaxis(norm[..., 0], -1, 0)[..., None]
    lse = (top + jnp.log(norm))[..., 0]
    target = jnp.sum(pexp / norm, axis=(0, 1)) / (q.shape[1] * q.shape[2])
    return o, lse, target


def _block_forward(q, k, v, qi, ki, wi, q_seg, k_seg, q_pos0, *, topk: int,
                   attend=_attend_xla):
    """One block of queries against the keys ``[0, L)`` (``ki`` [L, e]).
    ``attend`` is the route's attention over the chosen keys (`_attend_xla`:
    ``q`` [Q, Hk, G, d], ``k``, ``v`` [L, Hk, d]; `_attend_flash`: its own
    layouts) -> (o, each head's log-sum-exp, the chosen keys [Q, L], the
    block's summed KL and selected pairs over real queries, and the KL's
    gradient with respect to ``qi`` [Q, J, e], ``ki`` [L, e], ``wi`` [Q, J]:
    its target is a constant, so the forward pass knows it whole)."""
    nq, nk = qi.shape[0], ki.shape[0]
    valid = _allowed(nq, nk, q_seg, k_seg, q_pos0)
    real = (q_seg > 0)[:, None]
    with jax.named_scope("dsa_indexer"):
        dots = _index_dots(qi, ki)
        scores = jnp.sum(jax.nn.relu(dots) * wi.T[:, :, None], axis=0)
    with jax.named_scope("dsa_topk"):
        chosen = valid if nk <= topk else select_topk(scores, valid, topk)
    with jax.named_scope("dsa_attention"):
        o, lse, target = attend(q, k, v, chosen, q_pos0)
    with jax.named_scope("dsa_indexer_loss"):
        masked = jnp.where(chosen, scores, -1e30)
        log_q = masked - jax.nn.logsumexp(masked, axis=-1, keepdims=True)
        counted = chosen & real
        kl = jnp.sum(jnp.where(counted, jax.scipy.special.xlogy(
            target, target) - target * log_q, 0.0))
        # d KL_t / d I[t, s] = softmax_S(I)[t, s] - P_t[s]
        d_scores = jnp.where(counted, jnp.exp(log_q) - target, 0.0)
        g_wi = jnp.sum(jax.nn.relu(dots) * d_scores[None], axis=-1).T
        d_dots = jnp.where(dots > 0, d_scores[None] * wi.T[:, :, None], 0.0)
        g_qi = jnp.einsum("jql,le->qje", d_dots, ki)
        g_ki = jnp.einsum("jql,qje->le", d_dots, qi)
    return (o.astype(q.dtype), lse, chosen, kl,
            jnp.sum(counted, dtype=jnp.int32), g_qi, g_ki, g_wi)


def _block_backward(q, k, v, o, lse, chosen, d_o):
    """Flash-style backward of one block: the scores are computed again
    from ``q``, ``k`` and the saved log-sum-exp -> (dq [Q, Hk, G, d], dk,
    dv [L, Hk, d] float32)."""
    dt = q.dtype
    scale = q.shape[-1] ** -0.5
    f32 = dict(preferred_element_type=jnp.float32)
    logits = jnp.einsum("qhgd,lhd->hgql", q, k, **f32) * scale
    prob = jnp.where(chosen, jnp.exp(logits - lse[..., None]), 0.0)
    d_prob = jnp.einsum("qhgd,lhd->hgql", d_o, v, **f32)
    rows = jnp.sum(d_o.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    d_logits = (prob * (d_prob - jnp.moveaxis(rows, 0, -1)[..., None])
                * scale).astype(dt)
    return (jnp.einsum("hgql,lhd->qhgd", d_logits, k, **f32),
            jnp.einsum("hgql,qhgd->lhd", d_logits, q, **f32),
            jnp.einsum("hgql,qhgd->lhd", prob.astype(dt), d_o, **f32))


def _spans(t: int, block, span):
    block = min(block or QUERY_BLOCK, t)
    span = min(max(span or KEY_SPAN, block), t)
    # nerrflint: ok[recompile-hazard] block and span are Python ints (module constants, or a test's), never traced values: they shape the program
    if t % span or span % block:
        raise ValueError(f"{t} tokens, key spans of {span}, query blocks of "
                         f"{block}: not whole multiples")
    return block, span


def _blocks(x, block: int):
    return x.reshape((x.shape[0] // block, block) + x.shape[1:])


def _grow(x, t: int):
    """Zero rows up to ``t`` (a branch's keys end before the sequence's)."""
    return jnp.pad(x, ((0, t - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _sparse_forward(q, k, v, qi, ki, wi, seg, topk, block, span):
    """The scan over the blocks, one `lax.switch` branch per key length.
    On the fused route the attention part of every block is `_attend_flash`
    (the operands heads-leading, the keys whole) in place of `_attend_xla`;
    the indexer, the selection and the indexer's loss are the same code."""
    t, hq, d = q.shape
    hk = k.shape[1]
    fused = attention_route(t, hq, hk, d) == "pallas_flash"
    block, span = _spans(t, block, span)
    lengths = range(span, t + 1, span)
    if fused:
        k, v = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)

    def branch(n):
        def run(q_b, qi_b, wi_b, seg_b, lo):
            keys, attend = ((k, v), partial(_attend_flash, keys=n)) if fused \
                else ((k[:n], v[:n]), _attend_xla)
            o, lse, chosen, kl, pairs, g_qi, g_ki, g_wi = _block_forward(
                q_b, *keys, qi_b, ki[:n], wi_b, seg_b, seg[:n], lo,
                topk=topk, attend=attend)
            return (o, lse, jnp.pad(chosen, ((0, 0), (0, t - n))), kl, pairs,
                    g_qi, _grow(g_ki, t), g_wi)
        return run

    branches = [branch(n) for n in lengths]

    def step(g_ki, xs):
        o, lse, chosen, kl, pairs, g_qi, g_ki_b, g_wi = jax.lax.switch(
            xs[-1] // span, branches, *xs)
        return g_ki + g_ki_b, (o, lse, chosen, kl, pairs, g_qi, g_wi)

    def q_blocks():
        # traced among the scan's operands: the XLA route's lowering is held
        # to a recorded digest (`tests/test_stream_latent.py`)
        x = _blocks(q.reshape(t, hk, hq // hk, d), block)
        return jnp.transpose(x, (0, 2, 3, 1, 4)) if fused else x

    g_ki, (o, lse, chosen, kl, pairs, g_qi, g_wi) = jax.lax.scan(
        step, jnp.zeros(ki.shape, jnp.float32),
        (q_blocks(), _blocks(qi, block), _blocks(wi, block),
         _blocks(seg, block), jnp.arange(0, t, block)))
    join = lambda x: x.reshape((t,) + x.shape[2:])
    if fused:
        # token-major again, as the backward kernel reads them: o [T, Hq,
        # d], the log-sum-exp [Hq, T]
        o = jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(1, t, hq, d)
        lse = jnp.moveaxis(lse[:, :, 0], 0, 1).reshape(hq, t)
    return ((join(o).reshape(t, hq, d), jnp.sum(kl), jnp.sum(pairs)),
            (join(o), lse, join(chosen), join(g_qi), g_ki, join(g_wi)))


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _sparse(q, k, v, qi, ki, wi, seg, topk, block, span):
    return _sparse_forward(q, k, v, qi, ki, wi, seg, topk, block, span)[0]


def _sparse_fwd(q, k, v, qi, ki, wi, seg, topk, block, span):
    out, saved = _sparse_forward(q, k, v, qi, ki, wi, seg, topk, block, span)
    # named so that a layer's remat can keep them (`SAVED`): what the
    # backward pass needs of the forward pass's [T, T] work
    saved = tuple(checkpoint_name(x, SAVED) for x in saved)
    return out, (q, k, v, seg) + saved


def _sparse_bwd(topk, block, span, res, cts):
    q, k, v, seg, o, lse, chosen, g_qi, g_ki, g_wi = res
    d_o, d_kl, _ = cts
    t, hq, d = q.shape
    hk = k.shape[1]
    route = attention_route(t, hq, hk, d)
    if route == "pallas_flash":
        with jax.named_scope("dsa_attention"):
            dq, dk, dv = _flash_backward(q, k, v, o, lse, chosen, d_o)
        return dq, dk, dv, d_kl * g_qi, d_kl * g_ki, d_kl * g_wi, None
    block, span = _spans(t, block, span)
    shape = (t, hk, hq // hk, d)

    def branch(n):
        def run(q_b, o_b, lse_b, chosen_b, d_o_b):
            dq, dk, dv = _block_backward(q_b, k[:n], v[:n], o_b, lse_b,
                                         chosen_b[:, :n], d_o_b)
            return dq, _grow(dk, t), _grow(dv, t)
        return run

    branches = [branch(n) for n in range(span, t + 1, span)]

    def step(carry, xs):
        dq, dk, dv = jax.lax.switch(xs[-1] // span, branches, *xs[:-1])
        return (carry[0] + dk, carry[1] + dv), dq

    with jax.named_scope("dsa_attention"):
        zeros = jnp.zeros(k.shape, jnp.float32)
        (dk, dv), dq = jax.lax.scan(
            step, (zeros, zeros),
            (_blocks(q.reshape(shape), block), _blocks(o, block), lse,
             _blocks(chosen, block), _blocks(d_o.reshape(shape), block),
             jnp.arange(0, t, block)))
    return (dq.reshape(t, hq, d).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), d_kl * g_qi, d_kl * g_ki, d_kl * g_wi, None)


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


# --------------------------------------------------------------------------
# the fused route: the attention's scores never leave the chip's vector memory
# --------------------------------------------------------------------------

# keys a tile of both kernels and queries a tile of the backward kernel (the
# forward kernel's queries are the scan's block).  The fast memory each kernel
# may plan is what it needs and little more: the scan keeps the indexer's
# block `dots` (up to 96 MiB) in the chip's 128 MiB across the forward kernel,
# and a larger limit pushes it out (`docs/kernel-paths.md`).  The backward
# kernel keeps a key-value head's ``dk``, ``dv`` [T, d] resident (16 bytes an
# element with the output's two buffers), the forward kernel the block's
# every head (20 bytes an element): the rows that fit under those limits
FLASH_BLOCK_K = 512
FLASH_BLOCK_Q = 512
FLASH_VMEM_BYTES = 40 << 20
FLASH_FWD_VMEM_BYTES = 24 << 20
FLASH_MAX_ROW_ELEMENTS = 1 << 20
_LANES = 128
_MASK_ROWS = 32  # an int8 tile's sublanes: the chosen set travels as int8
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def attention_route(t: int, hq: int, hk: int, d: int) -> str:
    """Which form `sparse_attention`'s attention part traces for a sequence
    of ``t`` tokens, ``hq`` query heads on ``hk`` key-value heads of ``d``: a
    function of the backend and these static shapes, nothing else.
    ``"pallas_flash"`` (the fused kernels) on a TPU when ``d`` is whole
    lanes, ``t`` whole tiles, and the rows the kernels keep resident fit;
    ``"xla_blocked"`` otherwise."""
    block = min(QUERY_BLOCK, t)
    span = min(max(KEY_SPAN, block), t)
    # nerrflint: ok[recompile-hazard] t, hq, hk, d are static shapes (Python ints read off `.shape`), never traced values: they choose the program
    if (jax.default_backend() == "tpu" and d % _LANES == 0 and hq % hk == 0
            and t % span == 0 and span % block == 0
            and block % _MASK_ROWS == 0 and span % FLASH_BLOCK_K == 0
            and t % FLASH_BLOCK_Q == 0
            and t * d <= FLASH_MAX_ROW_ELEMENTS
            and hq * block * d <= FLASH_MAX_ROW_ELEMENTS):
        return "pallas_flash"
    return "xla_blocked"


def _flash_params(vmem_bytes, *semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem_bytes)


def _flash_fwd_kernel(lo_ref, q_ref, k_ref, v_ref, chosen_ref, o_ref, lse_ref,
                      target_ref, m_ref, l_ref, acc_ref, *, scale, bk):
    """One (pass, key tile) step of a block of queries, every head in it.
    Pass 0 is the online softmax (``m`` the running maximum, ``l`` the
    running sum, both lane-replicated [Hq, Q, 128], ``acc`` the
    un-normalised output); at its end ``m`` holds the log-sum-exp.  Pass 1
    computes the scores once more and adds each head's normalised
    probabilities into the tile of ``target``."""
    from jax.experimental import pallas as pl

    step, j = pl.program_id(0), pl.program_id(1)
    hk, groups, nq, d = q_ref.shape
    last = pl.num_programs(1) - 1
    # a key tile wholly above the block's diagonal is skipped
    live = j * bk < lo_ref[0] + nq
    f32 = dict(preferred_element_type=jnp.float32)
    wide = lambda x, n: jnp.tile(x, (1, n // _LANES))

    def each_head(body):
        def group(h, _):
            for g in range(groups):
                body(h, g, h * groups + g)
            return _
        jax.lax.fori_loop(0, hk, group, 0)

    @pl.when((step == 0) & (j == 0))
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -1e9, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when((step == 0) & live)
    def _():
        chosen = chosen_ref[...] != 0

        def head(h, g, i):
            s = jax.lax.dot_general(q_ref[h, g], k_ref[h], _NT, **f32) * scale
            s = jnp.where(chosen, s, -1e9)
            m_prev = m_ref[i]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - wide(m_next, bk))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[i] = alpha * l_ref[i] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[i] = m_next
            acc_ref[i] = acc_ref[i] * wide(alpha, d) + jnp.dot(
                p.astype(v_ref.dtype), v_ref[h], **f32)

        each_head(head)

    @pl.when((step == 0) & (j == last))
    def _():
        def head(h, g, i):
            l = l_ref[i]
            o_ref[h, g] = (acc_ref[i] / wide(l, d)).astype(o_ref.dtype)
            lse = m_ref[i] + jnp.log(l)
            m_ref[i] = lse
            # the log-sum-exp leaves as one row of the compact [Hq, 1, Q]
            lse_ref[i] = lse.T[:1]

        each_head(head)

    @pl.when((step == 1) & live)
    def _():
        target_ref[...] = jnp.zeros(target_ref.shape, jnp.float32)

        def head(h, g, i):
            s = jax.lax.dot_general(q_ref[h, g], k_ref[h], _NT, **f32) * scale
            target_ref[...] += jnp.exp(s - wide(m_ref[i], bk))

        each_head(head)
        target_ref[...] = jnp.where(
            chosen_ref[...] != 0, target_ref[...] / (hk * groups), 0.0)


def _attend_flash(q, k, v, chosen, q_pos0, *, keys: int):
    """The attention of one block over its chosen keys, fused: ``q`` [Hk, G,
    Q, d], ``k``, ``v`` [Hk, T, d] whole, of which the first ``keys`` are
    read, ``chosen`` [Q, keys] -> (o [Hk, G, Q, d], each head's log-sum-exp
    [Hq, 1, Q], the heads' mean probability [Q, keys]).  Only [Q, keys]
    arrays cross HBM: the chosen set in (int8), the mean probability out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hk, groups, nq, d = q.shape
    bk = FLASH_BLOCK_K
    tiles = keys // bk
    # a skipped step asks for the key tile it already holds: nothing is
    # copied for it (nor for the values in the second pass)
    held = lambda j, lo: jnp.minimum(j, (lo[0] + nq - 1) // bk)
    # the block's own arrays: one buffer each, nothing to fetch ahead
    whole = lambda shape: pl.BlockSpec(
        shape, lambda s, j, lo: (0,) * len(shape),
        pipeline_mode=pl.Buffered(1))
    o, lse, target = pl.pallas_call(
        partial(_flash_fwd_kernel, scale=d ** -0.5, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(2, tiles),
            in_specs=[
                whole(q.shape),
                pl.BlockSpec((hk, bk, d),
                             lambda s, j, lo: (0, held(j, lo), 0)),
                pl.BlockSpec((hk, bk, d), lambda s, j, lo: (0, jnp.where(
                    s == 0, held(j, lo), held(tiles - 1, lo)), 0)),
                pl.BlockSpec((nq, bk), lambda s, j, lo: (0, held(j, lo)))],
            out_specs=[
                whole(q.shape), whole((hk * groups, 1, nq)),
                pl.BlockSpec((nq, bk), lambda s, j, lo: (0, s * held(j, lo)))],
            scratch_shapes=[
                pltpu.VMEM((hk * groups, nq, _LANES), jnp.float32),
                pltpu.VMEM((hk * groups, nq, _LANES), jnp.float32),
                pltpu.VMEM((hk * groups, nq, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((hk * groups, 1, nq), jnp.float32),
                   jax.ShapeDtypeStruct((nq, keys), jnp.float32)],
        compiler_params=_flash_params(FLASH_FWD_VMEM_BYTES, "arbitrary",
                                      "arbitrary"),
        name="dsa_flash_fwd",
    )(jnp.reshape(q_pos0, (1,)), q, k, v, chosen.astype(jnp.int8))
    # the tiles above the diagonal were never written
    return o, lse, jnp.where(chosen, target, 0.0)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, d_o_ref, lse_ref, rows_ref,
                      chosen_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc,
                      dv_acc, *, scale, bq, bk, d):
    """One (key-value head, query tile, key tile) step of the backward pass,
    the head's whole group of query heads in it (they share the key tile and
    the tile of the chosen set), scores transposed ([bk, bq]: the queries'
    log-sum-exp and ``rows`` = sum(d_o * o) are then plain rows).  The
    scores are computed once for all three gradients: ``dq`` gathers over
    the key tiles in ``dq_acc``; ``dk``, ``dv`` over the group and the query
    tiles in ``dk_acc``, ``dv_acc``, the head's whole [T, d] float32, which
    stay in fast memory while the head's tiles run."""
    from jax.experimental import pallas as pl

    i, j = pl.program_id(1), pl.program_id(2)
    last_i, last_j = pl.num_programs(1) - 1, pl.num_programs(2) - 1
    groups = dq_acc.shape[0]
    f32 = dict(preferred_element_type=jnp.float32)

    @pl.when((i == 0) & (j == 0))
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    @pl.when(j * bk < (i + 1) * bq)
    def _():
        chosen = chosen_ref[...] != 0
        k, v = k_ref[...], v_ref[...]
        dk = jnp.zeros((bk, d), jnp.float32)
        dv = jnp.zeros((bk, d), jnp.float32)
        for g in range(groups):
            cols = slice(g * d, (g + 1) * d)
            q, d_o = q_ref[:, cols], d_o_ref[:, cols]
            s = jax.lax.dot_general(k, q, _NT, **f32) * scale
            prob = jnp.where(chosen, jnp.exp(s - lse_ref[g]), 0.0)
            dv += jnp.dot(prob.astype(d_o.dtype), d_o, **f32)
            d_prob = jax.lax.dot_general(v, d_o, _NT, **f32)
            d_s = (prob * (d_prob - rows_ref[g]) * scale).astype(q.dtype)
            dk += jnp.dot(d_s, q, **f32)
            dq_acc[g] += jax.lax.dot_general(d_s, k, _TN, **f32)
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        dk_acc[rows, :] += dk
        dv_acc[rows, :] += dv

    @pl.when(j == last_j)
    def _():
        for g in range(groups):
            dq_ref[:, g * d:(g + 1) * d] = dq_acc[g].astype(dq_ref.dtype)

    @pl.when((i == last_i) & (j == last_j))
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, chosen, d_o):
    """The whole sequence in one kernel, token-major as the layer holds
    them: ``q``, ``o``, ``d_o`` [T, Hq, d], ``k``, ``v`` [T, Hk, d], ``lse``
    [Hq, T], ``chosen`` [T, T] -> (dq, dk, dv) in the operands' type."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bq, bk = FLASH_BLOCK_Q, FLASH_BLOCK_K
    t, hq, d = q.shape
    hk = k.shape[1]
    groups = hq // hk
    rows = jnp.sum(d_o.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    by_head = lambda x: x.reshape(hk, groups, 1, t)
    flat = lambda x: x.reshape(t, -1)
    # a skipped step asks for the key tile it already holds
    held = lambda i, j: jnp.minimum(j, ((i + 1) * bq - 1) // bk)
    q_spec = pl.BlockSpec((bq, groups * d), lambda h, i, j: (i, h))
    k_spec = pl.BlockSpec((bk, d), lambda h, i, j: (held(i, j), h))
    row_spec = pl.BlockSpec((None, groups, 1, bq),
                            lambda h, i, j: (h, 0, 0, i))
    kv_out = pl.BlockSpec((t, d), lambda h, i, j: (0, h))
    dq, dk, dv = pl.pallas_call(
        partial(_flash_bwd_kernel, scale=d ** -0.5, bq=bq, bk=bk, d=d),
        grid=(hk, t // bq, t // bk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec,
                  pl.BlockSpec((bk, bq), lambda h, i, j: (held(i, j), i))],
        out_specs=[q_spec, kv_out, kv_out],
        out_shape=[jax.ShapeDtypeStruct((t, hq * d), q.dtype),
                   jax.ShapeDtypeStruct((t, hk * d), k.dtype),
                   jax.ShapeDtypeStruct((t, hk * d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((groups, bq, d), jnp.float32),
                        pltpu.VMEM((t, d), jnp.float32),
                        pltpu.VMEM((t, d), jnp.float32)],
        compiler_params=_flash_params(FLASH_VMEM_BYTES, "parallel", "arbitrary",
                                      "arbitrary"),
        name="dsa_flash_bwd",
    )(flat(q), flat(k), flat(v), flat(d_o), by_head(lse),
      by_head(rows.T), chosen.T.astype(jnp.int8))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def sparse_attention(q, k, v, qi, ki, wi, seg, *, topk: int,
                     block: int = None, span: int = None):
    """One packed sequence.  ``q`` [T, Hq, d], ``k``, ``v`` [T, Hk, d] in
    the compute type (a key-value head serves Hq / Hk query heads, in
    order); the indexer's ``qi`` [T, J, e], ``ki`` [T, e], ``wi`` [T, J] in
    float32; ``seg`` [T] -> (o [T, Hq, d], the summed ``KL_t`` over real
    queries (float32), the selected pairs (int32)).  Padding (``seg`` 0)
    attends within itself like a document and counts in neither sum: the
    caller divides by the real tokens.

    The blocks run one after another (`lax.scan`: side by side, their
    scores would not fit), and a block's keys end at the next multiple of
    ``span`` past its own end: one `lax.switch` branch per such length, so
    the program holds T / span shapes of the block, not T / block.

    Its derivative is written by hand (`jax.custom_vjp`).  The forward pass
    keeps, of its [T, T] work, the chosen keys, ``o``, each head's
    log-sum-exp and the KL's gradient with respect to ``qi``, ``ki``,
    ``wi`` (the KL's target is a constant, so that gradient is known when
    the scores are); the backward pass computes a block's attention scores
    once more from them and never the indexer's.  Reverse mode through the
    scan would keep or recompute every block's scores, in float32, and the
    name scopes of what it transposes are lost to the trace."""
    o, kl, pairs = _sparse(q, k, v, qi, ki, wi, seg, topk, block, span)
    # what reads ``o`` (the output projection's gradient) would otherwise
    # make a remat that keeps `SAVED` run the forward pass again for it
    return checkpoint_name(o, SAVED), kl, pairs


def selection(qi, ki, wi, seg, *, topk: int, block: int = None):
    """The chosen keys of one packed sequence, whole: bool [T, T] (what
    `sparse_attention` attends to; for tests and diagnostics)."""
    t = seg.shape[0]
    block = min(block or QUERY_BLOCK, t)
    cut = lambda x: x.reshape((t // block, block) + x.shape[1:])
    return jax.lax.map(
        lambda xs: _choose(xs[0], ki, xs[1], xs[2], seg, xs[3], topk),
        (cut(qi), cut(wi), cut(seg), jnp.arange(0, t, block))).reshape(t, t)


def causal_pairs(seg, window: int = None):
    """Query-key pairs of one packed sequence that the causal and document
    masks allow (float32: 33.5 M at 8192 tokens), real queries only; with a
    ``window`` w, those no more than w - 1 positions back."""
    pos = doc_positions(seg)
    real, inside = seg > 0, pos + 1
    # nerrflint: ok[recompile-hazard] window is None or a Python int of the static configuration (`StreamConfig.window`), never a traced value
    if window is not None:
        inside = jnp.minimum(inside, window)
    return jnp.sum(jnp.where(real, inside, 0).astype(jnp.float32))
