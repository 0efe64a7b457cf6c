"""The benchmark's own weights for NerrfNet, made on the device from the seed.

`param_shapes` writes the parameter tree down from the configuration alone
(no trace of the program: the CPU tests hold it against the program's own
`model.init` tree); `make_params` fills it in ONE jitted call, float32 (the
type the trainer keeps its parameters in).  Both the program and the plain
reference are handed these same arrays, so neither takes anything the other
made.

Initial distributions follow the layer library's defaults in scale, not bit
for bit: kernels N(0, 1/fan_in), embeddings N(0, 1/features), biases and
`dir_bias` zero, LayerNorm scales one.  (The library draws the LSTM's
recurrent kernels orthogonal; a normal of the same scale trains the same
shapes at the same cost.)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _dense(fan_in, fan_out):
    return {"kernel": ("normal", (fan_in, fan_out), 1.0 / math.sqrt(fan_in)),
            "bias": ("zeros", (fan_out,), 0.0)}


def _ln(width):
    return {"scale": ("ones", (width,), 0.0), "bias": ("zeros", (width,), 0.0)}


def param_spec(config: dict) -> dict:
    """Nested dict of ``(init kind, shape, scale)`` leaves for the
    configuration's NerrfNet."""
    shapes = config["shapes"]
    model = config["train"]["model"]
    hl, hg = model["lstm"]["hidden"], model["gnn"]["hidden"]
    lstm = {"in_proj": _dense(shapes["seq_feature_dim"], hl),
            "pool_ln": _ln(hl), "head": _dense(hl, 1)}
    for i in range(model["lstm"]["num_layers"]):
        for d in range(2):
            cell = {}
            for g in "ifgo":
                cell[f"i{g}"] = {
                    "kernel": ("normal", (hl, hl), 1.0 / math.sqrt(hl))}
                cell[f"h{g}"] = _dense(hl, hl)
            lstm[f"OptimizedLSTMCell_{2 * i + d}"] = cell
        lstm[f"merge_{i}"] = _dense(2 * hl, hl)
    gnn = {
        "type_emb": {"embedding": ("normal", (4, hg), 1.0 / math.sqrt(hg))},
        "aux_emb": {"embedding": ("normal", (shapes["aux_vocab"], hg),
                                  1.0 / math.sqrt(hg))},
        "node_enc": _dense(shapes["node_feature_dim"], hg),
        "edge_enc": _dense(shapes["edge_feature_dim"], hg),
        "final_ln": _ln(hg),
        "node_head": _dense(hg, 1),
        "edge_head_1": _dense(4 * hg, hg),
        "edge_head_2": _dense(hg, 1),
    }
    for i in range(model["gnn"]["num_layers"]):
        gnn[f"block_{i}"] = {
            "ln": _ln(hg), "w_msg": _dense(hg, hg),
            "dir_bias": ("zeros", (2, hg), 0.0),
            "w_self": _dense(2 * hg, hg)}
    out = {"lstm": lstm, "gnn": gnn}
    if model["fuse"]:
        out["seq_to_node"] = _dense(hl, shapes["node_feature_dim"])
    return out


def _is_leaf(x):
    return isinstance(x, tuple)


def param_shapes(config: dict) -> dict:
    return jax.tree_util.tree_map(lambda s: s[1], param_spec(config),
                                  is_leaf=_is_leaf)


def count_params(config: dict) -> int:
    return sum(math.prod(s[1]) for s in jax.tree_util.tree_leaves(
        param_spec(config), is_leaf=_is_leaf))


def make_params(config: dict, key):
    """All weights in one jitted call from ``key``."""
    spec = param_spec(config)
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_leaf)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (kind, shape, scale) in zip(keys, leaves):
            if kind == "normal":
                out.append(scale * jax.random.normal(k, shape, jnp.float32))
            elif kind == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append(jnp.zeros(shape, jnp.float32))
        return out

    return jax.tree_util.tree_unflatten(treedef, build(key))
