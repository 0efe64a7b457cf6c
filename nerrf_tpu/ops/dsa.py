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

Plain XLA, a block of queries at a time against the keys before the block's
end, rounded up to a span (`sparse_attention`); a block whose keys number
``topk`` or fewer keeps every key its masks allow and is never ranked.  The
chosen set is a mask over the block's scores: on a TPU a gather of 2048 keys
a query would move more bytes than the dense products it saves.  A block's
scores live only while it is computed, forward and backward.
"""

from __future__ import annotations

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


def rope(x, pos, theta: float):
    """Rotary embedding, rotate-half convention: ``x`` [T, heads, d] with
    the pairs ``(i, i + d / 2)`` turned by ``pos * theta^(-2 i / d)``.
    float32 inside, the input's type out."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = pos.astype(jnp.float32)[:, None] * freq            # [T, d / 2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


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


def _block_forward(q, k, v, qi, ki, wi, q_seg, k_seg, q_pos0, *, topk: int):
    """One block of queries against the keys ``[0, L)``.  ``q`` [Q, Hk, G,
    d], ``k``, ``v`` [L, Hk, d] -> (o [Q, Hk, G, d], each head's
    log-sum-exp [Hk, G, Q], the chosen keys [Q, L], the block's summed KL
    and selected pairs over real queries, and the KL's gradient with
    respect to ``qi`` [Q, J, e], ``ki`` [L, e], ``wi`` [Q, J]: its target is
    a constant, so the forward pass knows it whole)."""
    nq, nk = q.shape[0], k.shape[0]
    valid = _allowed(nq, nk, q_seg, k_seg, q_pos0)
    real = (q_seg > 0)[:, None]
    with jax.named_scope("dsa_indexer"):
        dots = _index_dots(qi, ki)
        scores = jnp.sum(jax.nn.relu(dots) * wi.T[:, :, None], axis=0)
    with jax.named_scope("dsa_topk"):
        chosen = valid if nk <= topk else select_topk(scores, valid, topk)
    with jax.named_scope("dsa_attention"):
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
    t, hq, d = q.shape
    hk = k.shape[1]
    block, span = _spans(t, block, span)
    lengths = range(span, t + 1, span)

    def branch(n):
        def run(q_b, qi_b, wi_b, seg_b, lo):
            o, lse, chosen, kl, pairs, g_qi, g_ki, g_wi = _block_forward(
                q_b, k[:n], v[:n], qi_b, ki[:n], wi_b, seg_b, seg[:n], lo,
                topk=topk)
            return (o, lse, jnp.pad(chosen, ((0, 0), (0, t - n))), kl, pairs,
                    g_qi, _grow(g_ki, t), g_wi)
        return run

    branches = [branch(n) for n in lengths]

    def step(g_ki, xs):
        o, lse, chosen, kl, pairs, g_qi, g_ki_b, g_wi = jax.lax.switch(
            xs[-1] // span, branches, *xs)
        return g_ki + g_ki_b, (o, lse, chosen, kl, pairs, g_qi, g_wi)

    g_ki, (o, lse, chosen, kl, pairs, g_qi, g_wi) = jax.lax.scan(
        step, jnp.zeros(ki.shape, jnp.float32),
        (_blocks(q.reshape(t, hk, hq // hk, d), block), _blocks(qi, block),
         _blocks(wi, block), _blocks(seg, block), jnp.arange(0, t, block)))
    join = lambda x: x.reshape((t,) + x.shape[2:])
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


def causal_pairs(seg):
    """Query-key pairs of one packed sequence that the causal and document
    masks allow (float32: 33.5 M at 8192 tokens)."""
    pos = doc_positions(seg)
    return jnp.sum(jnp.where(seg > 0, pos + 1, 0).astype(jnp.float32))
