"""Required work of NerrfNet's neighbourhood aggregate alone, from shapes.

The aggregate is ``agg[n] = sum over edges into n of w_e * msg[src_e]``, in
both directions, once per SageBlock: what the program scopes as
``sage_aggregate`` (the fused Pallas kernel on one route, ``adj @ msg`` on
the other; the edge embedding's mean and the direction biases are added
outside the scope on both and are left out here).  Per layer and forward
pass: ``2 * 2 * E * H`` FLOPs (a multiply-add per edge, direction and
channel: `dense_adj`'s ``2 N^2 H`` is not required work) and ``2 * N * H *
a + 12 * E`` bytes (``msg`` read once, the aggregate written once, in the
compute type; the edge list, source, destination and weight, once).

Training is 2 x forward, not the 3 x of a layer with weights: the aggregate
has none (the edge weights come from ``edge_feat``, which has no gradient),
so its backward pass is the gradient with respect to ``msg`` alone, the same
aggregate with the directions exchanged.  A floor must not count work
nobody needs.
"""

from __future__ import annotations

from chipbench.work.nerrfnet import _BYTES, shapes_of


def forward_work(config: dict) -> dict:
    """{"flops", "bytes"} of one window's forward pass, all layers."""
    d = shapes_of(config)
    a = _BYTES[config["train"]["model"]["gnn"]["dtype"]]
    return {"flops": d["L"] * 2 * 2 * d["E"] * d["H"],
            "bytes": d["L"] * (2 * d["N"] * d["H"] * a + 12 * d["E"])}


def train_work(config: dict) -> dict:
    """{"flops", "bytes"} of one window's training step: forward and the
    gradient with respect to ``msg``."""
    return {k: 2 * v for k, v in forward_work(config).items()}
