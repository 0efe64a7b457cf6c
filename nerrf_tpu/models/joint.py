"""NerrfNet: joint GraphSAGE-T + BiLSTM detector.

The reference roadmap specifies joint training ("LSTM on edge sequences +
joint loss", `/root/reference/ROADMAP.md:68`).  Here the fusion is
architectural, not just a summed loss: each per-file LSTM embedding is
scattered into its file node's hidden state *before* message passing, so the
GNN's edge classification sees sequence evidence, and both heads train from
one objective.  Sequence→node routing (`seq_node_idx`) is computed host-side
by inode match; -1 routes to a dummy slot.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp

from nerrf_tpu.models.graphsage import GraphSAGEConfig, GraphSAGET
from nerrf_tpu.models.lstm import ImpactLSTM, LSTMConfig


@dataclasses.dataclass(frozen=True)
class JointConfig:
    gnn: GraphSAGEConfig = GraphSAGEConfig()
    lstm: LSTMConfig = LSTMConfig()
    fuse: bool = True

    @property
    def small(self) -> "JointConfig":
        return JointConfig(gnn=self.gnn.small, lstm=self.lstm.small, fuse=self.fuse)


class NerrfNet(nn.Module):
    """One window graph + its per-file sequences → edge/node/seq logits."""

    cfg: JointConfig

    @nn.compact
    def __call__(
        self,
        node_feat, node_type, node_aux, node_mask, edge_src, edge_dst, edge_feat, edge_mask,
        seq_feat,      # [S, T, F_seq]
        seq_mask,      # [S, T]
        seq_node_idx,  # [S] int32: file-node slot for each sequence, -1 = none
        *,
        deterministic: bool = True,
    ) -> Dict[str, jnp.ndarray]:
        lstm_out = ImpactLSTM(self.cfg.lstm, name="lstm")(
            seq_feat, seq_mask, deterministic=deterministic
        )

        if self.cfg.fuse:
            n = node_feat.shape[0]
            h_seq = nn.Dense(
                node_feat.shape[-1], dtype=jnp.float32, name="seq_to_node"
            )(lstm_out["seq_emb"])
            ok = seq_node_idx >= 0
            # route invalid sequences to slot n (dropped by the slice below)
            tgt = jnp.where(ok, seq_node_idx, n)
            fused = jax.ops.segment_sum(
                h_seq * ok[:, None].astype(h_seq.dtype), tgt, num_segments=n + 1
            )[:n]
            node_feat = node_feat + fused

        gnn_out = GraphSAGET(self.cfg.gnn, name="gnn")(
            node_feat, node_type, node_aux, node_mask, edge_src, edge_dst,
            edge_feat, edge_mask, deterministic=deterministic,
        )
        return {**gnn_out, **lstm_out}
