"""The trainer's optimizer in plain ``jax.numpy``: global-norm clipping,
AdamW, linear warm-up into a cosine decay.  Written from the definitions
(Loshchilov & Hutter 2019; the clip of Pascanu et al. 2013), imports nothing
of the program or of its optimizer library."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

B1, B2, EPS = 0.9, 0.999, 1e-8


def learning_rate(count: int, opt: dict) -> float:
    """Rate applied by the update that has seen ``count`` updates before it:
    0 -> peak linearly over ``warmup_steps``, then a cosine to 0 at
    ``decay_steps``."""
    peak, warm = opt["learning_rate"], opt["warmup_steps"]
    decay = max(opt["num_steps"], warm + 1)
    if count < warm:
        return peak * count / warm
    frac = min(max((count - warm) / (decay - warm), 0.0), 1.0)
    return peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(x * x)
                        for x in jax.tree_util.tree_leaves(tree)))


def clip_by_global_norm(grads, max_norm: float = 1.0):
    norm = global_norm(grads)
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"count": 0, "mu": zeros, "nu": zeros}


def update(params, grads, state, opt: dict):
    """One step -> (params, state, clipped grads)."""
    g = clip_by_global_norm(grads, 1.0)
    count = state["count"] + 1
    mu = jax.tree_util.tree_map(lambda m, x: B1 * m + (1 - B1) * x,
                                state["mu"], g)
    nu = jax.tree_util.tree_map(lambda v, x: B2 * v + (1 - B2) * x * x,
                                state["nu"], g)
    lr = learning_rate(state["count"], opt)
    c1, c2 = 1 - B1 ** count, 1 - B2 ** count

    def step(p, m, v):
        u = (m / c1) / (jnp.sqrt(v / c2) + EPS) + opt["weight_decay"] * p
        return p - lr * u

    new = jax.tree_util.tree_map(step, params, mu, nu)
    return new, {"count": count, "mu": mu, "nu": nu}, g
