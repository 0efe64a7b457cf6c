"""One chip's share of a routed mixture of experts.

A router scores every token against ALL ``num_experts`` experts and keeps
``k`` of them.  There are two, and one dispatch behind both (`moe_share`
takes either): `route` is the softmax router (probabilities over all
experts, the ``k`` largest, renormalised over those ``k``, times a constant
where the model has one: the ``dsa_moe`` and ``gqa_*_moe`` kinds'),
`route_sigmoid` the sigmoid router of the latent-attention kinds
(each expert's score is a sigmoid of its own logit; the ``k`` are chosen by
score PLUS a correction bias that no gradient reaches and that never enters
a weight; the weights are the chosen scores over their sum, times a
constant).  This
chip holds the experts ``[first, first + held)`` and computes their part of
the result for the tokens routed to them; what the absent experts would add
is left out (under expert parallelism it arrives from the chips that hold
them: that exchange is not here, and nothing stands in for it).  No token is
dropped: there is no capacity.

The products are grouped by expert in plain XLA.  `dispatch_plan` gives
every (token, held expert) assignment a row of a buffer in which each
expert's rows are contiguous and padded to whole tiles of ``tile`` rows; the
buffer is sized for the worst routing (every token sends ``min(k, held)``
assignments here), so its shape is static.  `expert_ffn` walks the USED
tiles (a `fori_loop` whose bound the routing gives, so the walk costs what
the routing sent, not the worst case): a tile gathers its rows of ``x``,
multiplies them with ITS expert's three matrices (SwiGLU) and adds its rows
of the result, under their routing weights, to their tokens: the combine is
part of the walk, and no buffer of results is ever built.  Its backward pass
is written by hand as the same walk (`jax.custom_vjp`: reverse mode cannot
follow a loop of unknown length, and left to a `lax.scan` of `lax.cond` it
keeps a copy of the weights for every tile of the worst case).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

# rows a tile.  A tile's products are 2 x 256 FLOPs for every 2 bytes of
# its expert's matrices, whatever their width: about the v5e's FLOPs a byte
# (197 T / 819 G = 240), at 2048 x 768 (three matrices of 3.1 MB in bf16) as
# at 2048 x 1536 (6.3 MB each).  With 512 tokens an expert on average, the
# padding to whole tiles is a fifth of the rows at either width; what the
# width changes is a tile's products (12 us against 25 at the peak) beside
# the gathers and scatter-adds around them, which do not grow with it
TILE = 256


def route(logits, k: int, *, scale: float = 1.0):
    """The softmax router.  Logits [N, E] float32 -> (weights [N, k]
    float32: the softmax over all E, renormalised over the k kept, times
    ``scale``; experts [N, k] int32).  Of two equal probabilities the lower
    expert index is kept first."""
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, experts = jax.lax.top_k(gates, k)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    return (weights if scale == 1.0 else scale * weights), experts


def route_sigmoid(logits, k: int, *, bias, scale: float):
    """The sigmoid router (DeepSeek-V3's ``noaux_tc`` with one group).
    Logits [N, E] float32, the correction ``bias`` [E] -> (weights [N, k]
    float32, experts [N, k] int32): scores ``s = sigmoid(logits)``; the k
    experts of largest ``s + bias`` (of equal ones the lower index first);
    weights ``scale * s_i / (sum of the chosen s + 1e-20)``.  The bias moves
    choices and never weights, and no gradient reaches it."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
    top = jnp.take_along_axis(scores, experts, axis=-1)
    return (scale * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20),
            experts)


class Plan(NamedTuple):
    """The grouped buffer is a map of rows, never an array of activations."""

    src: jnp.ndarray          # [tiles, tile] a buffer row's token (>= N: none)
    tile_expert: jnp.ndarray  # [tiles] the held expert a tile belongs to
    tiles_used: jnp.ndarray   # [] tiles that hold a row
    dest: jnp.ndarray         # [N, k] an assignment's row (rows: not held)
    counts: jnp.ndarray       # [held] assignments of each held expert


def num_tiles(n: int, k: int, held: int, tile: int) -> int:
    """Tiles of the worst routing: every token's ``min(k, held)`` best are
    held here, and each expert's last tile is all but empty."""
    return -(-n * min(k, held) // tile) + held


def dispatch_plan(experts, first: int, held: int, tile: int) -> Plan:
    """``experts`` [N, k] (a token's k are distinct) -> where each
    assignment to a held expert lies in the grouped buffer."""
    n, k = experts.shape
    tiles = num_tiles(n, k, held, tile)
    rows = tiles * tile
    local = experts - first
    here = (local >= 0) & (local < held)
    # member[t, g]: token t is routed to held expert g; its rank among the
    # tokens of g is the count of members before it
    member = jnp.any(
        (local[:, :, None] == jnp.arange(held)) & here[:, :, None],
        axis=1).astype(jnp.int32)
    rank = jnp.cumsum(member, axis=0) - member
    counts = jnp.sum(member, axis=0)
    padded = -(-counts // tile) * tile
    ends = jnp.cumsum(padded)
    starts = ends - padded
    g = jnp.clip(local, 0, held - 1)
    dest = jnp.where(
        here, starts[g] + jnp.take_along_axis(rank, g, axis=1), rows)
    token = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
    # an empty row points past the tokens, each at a row of its own, so
    # that a tile's indices are sorted and unique: its tokens in order,
    # then N + its empty rows' places
    src = (n + jnp.arange(rows, dtype=jnp.int32) % tile).at[
        dest.ravel()].set(token.ravel(), mode="drop", unique_indices=True)
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(tiles) * tile, side="right"),
        held - 1).astype(jnp.int32)
    return Plan(src.reshape(tiles, tile), tile_expert, ends[-1] // tile,
                dest.astype(jnp.int32), counts)


def _tile_forward(rows, w_gate, w_up):
    dot = partial(jnp.dot, preferred_element_type=jnp.float32)
    gate, up = dot(rows, w_gate), dot(rows, w_up)
    return gate, up, jax.nn.silu(gate) * up


def _pad_rows(x, tile: int):
    """``tile`` zero rows after the last: where a tile's empty rows point."""
    return jnp.concatenate([x, jnp.zeros((tile,) + x.shape[1:], x.dtype)])


# a tile's row indices (`dispatch_plan`): what lets the gathers and the
# scatter-adds of the walk skip their checks for order and collisions
_ROWS = dict(indices_are_sorted=True, unique_indices=True)


@jax.custom_vjp
def expert_ffn(x, row_weight, src, tile_expert, tiles_used, w_gate, w_up,
               w_down):
    """``x`` [N, H] in the compute type; `Plan`'s ``src`` [tiles, tile],
    ``tile_expert`` [tiles] and ``tiles_used``; ``row_weight`` [tiles, tile]
    float32, the routing weight of the assignment in each buffer row; the
    held experts' ``w_gate``, ``w_up`` [held, H, F] and ``w_down`` [held, F,
    H] as they are stored (float32: rounded to ``x``'s type here, once a
    pass) -> y [N, H] float32: ``sum p_e W_down,e (silu(W_gate,e x_t) *
    W_up,e x_t)`` over token t's assignments to held experts.  Forward and
    backward each walk the USED tiles once (a `fori_loop` to
    ``tiles_used``): a tile gathers its rows, multiplies them with its
    expert's matrices and adds its weighted rows to their tokens; the
    backward pass recomputes a tile's products and accumulates the weights'
    gradients in float32."""
    return _ffn_forward(x, row_weight, src, tile_expert, tiles_used, w_gate,
                        w_up, w_down)


def _ffn_forward(x, row_weight, src, tile_expert, tiles_used, w_gate, w_up,
                 w_down):
    dt, n = x.dtype, x.shape[0]
    x_pad = _pad_rows(x, src.shape[1])
    w_gate, w_up, w_down = (w.astype(dt) for w in (w_gate, w_up, w_down))

    def one(i, y):
        e = tile_expert[i]
        _, _, mid = _tile_forward(x_pad.at[src[i]].get(**_ROWS), w_gate[e],
                                  w_up[e])
        rows = jnp.dot(mid.astype(dt), w_down[e],
                       preferred_element_type=jnp.float32)
        with jax.named_scope("moe_combine"):
            return y.at[src[i]].add(rows * row_weight[i][:, None], **_ROWS)

    y = jax.lax.fori_loop(0, tiles_used, one,
                          jnp.zeros(x_pad.shape, jnp.float32))
    return y[:n]


def _ffn_fwd(*args):
    return _ffn_forward(*args), args


def _ffn_bwd(res, d_y):
    x, row_weight, src, tile_expert, tiles_used, w_gate, w_up, w_down = res
    dt, n = x.dtype, x.shape[0]
    x_pad, d_y = _pad_rows(x, src.shape[1]), _pad_rows(d_y, src.shape[1])
    stored = [w.dtype for w in (w_gate, w_up, w_down)]
    w_gate, w_up, w_down = (w.astype(dt) for w in (w_gate, w_up, w_down))
    dot = partial(jnp.dot, preferred_element_type=jnp.float32)

    def one(i, carry):
        dx, dw, dg, du, dd = carry
        e = tile_expert[i]
        rows = x_pad.at[src[i]].get(**_ROWS)
        gate, up, mid = _tile_forward(rows, w_gate[e], w_up[e])
        mid = mid.astype(dt)
        with jax.named_scope("moe_combine"):
            d_rows = d_y.at[src[i]].get(**_ROWS)
            dw = dw.at[i].set(jnp.sum(d_rows * dot(mid, w_down[e]), axis=-1))
            d_rows = (d_rows * row_weight[i][:, None]).astype(dt)
        d_mid = dot(d_rows, w_down[e].T)
        sig = jax.nn.sigmoid(gate)
        d_gate = (d_mid * up * sig * (1.0 + gate * (1.0 - sig))).astype(dt)
        d_up = (d_mid * gate * sig).astype(dt)
        add = lambda acc, g: acc.at[e].add(g)
        dx = dx.at[src[i]].add(dot(d_gate, w_gate[e].T)
                               + dot(d_up, w_up[e].T), **_ROWS)
        return (dx, dw, add(dg, dot(rows.T, d_gate)),
                add(du, dot(rows.T, d_up)), add(dd, dot(mid.T, d_rows)))

    zeros = lambda like: jnp.zeros(like.shape, jnp.float32)
    dx, dw, dg, du, dd = jax.lax.fori_loop(
        0, tiles_used, one, (zeros(x_pad), zeros(row_weight), zeros(w_gate),
                             zeros(w_up), zeros(w_down)))
    return (dx[:n].astype(dt), dw, None, None, None, dg.astype(stored[0]),
            du.astype(stored[1]), dd.astype(stored[2]))


expert_ffn.defvjp(_ffn_fwd, _ffn_bwd)


def moe_share(x, logits, w_gate, w_up, w_down, *, k: int, first: int,
              tile: int = None, router=route):
    """The held experts' part of a routed expert layer.  ``x`` [N, H] in
    the compute type, router ``logits`` [N, E] float32 -> (y [N, H]
    float32, counts [held] int32).  ``router(logits, k) -> (weights,
    experts)`` is `route` or `route_sigmoid` with its bias and scale bound;
    everything after it is shared."""
    held = w_gate.shape[0]
    with jax.named_scope("moe_router"):
        weights, experts = router(logits, k)
    with jax.named_scope("moe_dispatch"):
        plan = dispatch_plan(experts, first, held, tile or TILE)
        # each buffer row's routing weight: the scatter's transpose gathers
        # the rows' gradients back to their assignments
        row_weight = jnp.zeros(plan.src.size, jnp.float32).at[
            plan.dest.ravel()].set(weights.ravel(), mode="drop",
                                   unique_indices=True)
    with jax.named_scope("moe_experts"):
        y = expert_ffn(x, row_weight.reshape(plan.src.shape), plan.src,
                       plan.tile_expert, plan.tiles_used, w_gate, w_up,
                       w_down)
    return y, plan.counts
