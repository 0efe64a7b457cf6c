"""`ops.sage_aggregate`, the op behind `aggregation="fused"`, against the
host.  The reference semantics throughout:

    out[n] = Σ_{e: dst(e)=n} ŵf(e)·msg[src(e)] + Σ_{e: src(e)=n} ŵr(e)·msg[dst(e)]

with pre-normalized weights, over the builder's dst-sorted edge list and the
model's src-sorted view.  The op is an XLA composition on every backend; the
oracle is a numpy loop.  The same properties at the level of the model, on
all three aggregation modes, are in test_models.py.
"""

import jax
import jax.numpy as jnp
import numpy as np

from nerrf_tpu.ops import sage_aggregate


def _graph(E, N, seed, zero_frac=0.0):
    """Random graph in both sorted views + both weight vectors in both
    orders — the full sage_aggregate argument tuple (minus msg)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N, E).astype(np.int32)
    dst = np.sort(rng.integers(0, N, E)).astype(np.int32)
    w = rng.uniform(0.1, 1.0, E).astype(np.float32)
    if zero_frac:
        w[rng.random(E) < zero_frac] = 0.0  # masked edges
    order = np.argsort(src)
    wf_d = (w * rng.uniform(0.5, 2.0, E)).astype(np.float32)
    wr_d = (w * rng.uniform(0.5, 2.0, E)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (
        dst, src, src[order], dst[order],
        wf_d, wf_d[order], wr_d[order], wr_d))


def _ref(msg, edges, n):
    """One edge at a time, float64, from the dst-ordered arrays alone."""
    dst, src, _src_s, _dst_s, wf_d, _wf_s, _wr_s, wr_d = map(np.asarray, edges)
    m = np.asarray(msg, np.float64)
    out = np.zeros((n, m.shape[1]))
    for s, d, wf, wr in zip(src, dst, wf_d, wr_d):
        out[d] += wf * m[s]
        out[s] += wr * m[d]
    return out


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def test_masked_edges_contribute_nothing():
    # zero-weight (masked) edges must vanish even though their rows are
    # still gathered
    edges = _graph(200, 64, seed=3, zero_frac=0.4)
    msg = _rand((64, 20), 4)
    np.testing.assert_allclose(sage_aggregate(msg, *edges, 64),
                               _ref(msg, edges, 64), rtol=1e-5, atol=1e-5)


def test_empty_segments_are_exactly_zero():
    # every edge lands on nodes {0, 1}; all other rows must be exact zeros
    # (pre-normalized weights: no eps-division residue)
    E, N, F = 40, 50, 7
    rng = np.random.default_rng(5)
    src = rng.integers(0, 2, E).astype(np.int32)
    dst = np.sort(rng.integers(0, 2, E)).astype(np.int32)
    w = rng.uniform(0.1, 1.0, E).astype(np.float32)
    order = np.argsort(src)
    edges = tuple(jnp.asarray(a) for a in (
        dst, src, src[order], dst[order], w, w[order], w[order], w))
    out = sage_aggregate(_rand((N, F), 6), *edges, N)
    assert float(jnp.max(jnp.abs(out[2:]))) == 0.0
    np.testing.assert_allclose(out, _ref(_rand((N, F), 6), edges, N),
                               rtol=1e-5, atol=1e-5)


def test_degenerate_shapes():
    out = sage_aggregate(
        jnp.zeros((5, 4), jnp.float32),
        *[jnp.zeros((0,), jnp.int32)] * 4,
        *[jnp.zeros((0,), jnp.float32)] * 4, 5)
    assert out.shape == (5, 4) and float(jnp.sum(jnp.abs(out))) == 0.0


def test_under_vmap_and_grad():
    # the training path vmaps the model over the window batch: the op
    # batches there, and its gradient in `msg` is the same sums with the
    # two directions exchanged (the loss is linear in the output)
    B, E, N, F = 3, 150, 40, 9
    per = [_graph(E, N, seed=10 + b) for b in range(B)]
    edges = tuple(jnp.stack([p[i] for p in per]) for i in range(8))
    msg, cot = _rand((B, N, F), 20), _rand((B, N, F), 21)

    f = jax.vmap(lambda m, *e: sage_aggregate(m, *e, N))
    np.testing.assert_allclose(
        f(msg, *edges), np.stack([_ref(msg[b], per[b], N) for b in range(B)]),
        rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda m: jnp.sum(f(m, *edges) * cot))(msg)
    for b in range(B):
        dst, src, _, _, wf_d, _, _, wr_d = map(np.asarray, per[b])
        want = np.zeros((N, F))
        for s, d, wf, wr in zip(src, dst, wf_d, wr_d):
            want[s] += wf * np.asarray(cot[b, d], np.float64)
            want[d] += wr * np.asarray(cot[b, s], np.float64)
        np.testing.assert_allclose(g[b], want, rtol=1e-4, atol=1e-4)
