"""Dataset assembly: traces → stacked, padded window samples.

One training sample = one sliding-window graph (`GraphBatch`) plus the
per-file event sequences inside that window (`SequenceBatch`), with a
host-computed ``seq_node_idx`` routing each sequence to its file node (inode
match).  All samples share one static shape, so the whole dataset stacks into
flat [B, ...] arrays that shard trivially over a device mesh's data axis.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from nerrf_tpu.data.labels import derive_event_labels
from nerrf_tpu.data.loaders import Trace
from nerrf_tpu.data.sequences import SEQ_FEATURE_DIM, SequenceBatch, build_file_sequences
from nerrf_tpu.graph.builder import (
    GraphBatch,
    GraphConfig,
    NODE_TYPE_FILE,
    build_window_graph,
    snapshot_windows,
)
from nerrf_tpu.tracing import span


@dataclasses.dataclass(frozen=True)
class DatasetConfig:
    graph: GraphConfig = GraphConfig()
    seq_len: int = 100
    max_seqs: int = 128
    # windows with fewer events than this are skipped (no signal, all padding)
    min_events: int = 4


@dataclasses.dataclass
class WindowDataset:
    """Flat [B, ...] arrays ready for device transfer."""

    arrays: dict[str, np.ndarray]

    def __len__(self) -> int:
        return len(self.arrays["node_feat"])

    @property
    def num_samples(self) -> int:
        return len(self)

    def take(self, idx: np.ndarray) -> "WindowDataset":
        return WindowDataset({k: v[idx] for k, v in self.arrays.items()})

    def split(self, frac: float, seed: int = 0) -> tuple["WindowDataset", "WindowDataset"]:
        n = len(self)
        order = np.random.default_rng(seed).permutation(n)
        k = int(n * (1 - frac))
        return self.take(order[:k]), self.take(order[k:])

    @staticmethod
    def concatenate(parts: List["WindowDataset"]) -> "WindowDataset":
        keys = parts[0].arrays.keys()
        return WindowDataset(
            {k: np.concatenate([p.arrays[k] for p in parts]) for k in keys}
        )


def _seq_node_index(g: GraphBatch, seqs: SequenceBatch) -> np.ndarray:
    """Match each sequence's inode to its file-node slot in g (-1 if absent)."""
    out = np.full(len(seqs), -1, np.int32)
    file_slots = np.nonzero(g.node_mask & (g.node_type == NODE_TYPE_FILE))[0]
    if len(file_slots) == 0 or len(seqs) == 0:
        return out
    key_to_slot = {int(g.node_key[s]): int(s) for s in file_slots}
    for i, ino in enumerate(seqs.inode):
        out[i] = key_to_slot.get(int(ino), -1)
    return out


def window_sample(trace: Trace, lo: int, hi: int, cfg: DatasetConfig,
                  labels: Optional[np.ndarray] = None):
    """Lower ONE window [lo, hi) to a padded sample → ``(sample, stats)``.

    ``sample`` is None when the window carries fewer than ``cfg.min_events``
    events (all padding, no signal).  This is THE per-window lowering, shared
    by the offline dataset path (`windows_of_trace`) and the online serving
    windower (`nerrf_tpu.serve.windower`) — splitting it would let the two
    paths drift and break the serve path's bit-parity with `model_detect`.
    """
    g, stats = build_window_graph(trace.events, trace.strings, lo, hi,
                                  cfg.graph, labels=labels)
    if stats.num_events < cfg.min_events:
        return None, stats
    seqs = build_file_sequences(trace, labels=labels, seq_len=cfg.seq_len,
                                lo_ns=lo, hi_ns=hi)
    if len(seqs) > cfg.max_seqs:
        # keep the most event-dense sequences (they carry the signal)
        density = seqs.mask.sum(axis=1)
        keep = np.argsort(-density, kind="stable")[: cfg.max_seqs]
        keep.sort()
        seqs = SequenceBatch(feat=seqs.feat[keep], mask=seqs.mask[keep],
                             label=seqs.label[keep], inode=seqs.inode[keep])
    seqs = seqs.pad_to(cfg.max_seqs)
    seq_valid = seqs.mask.any(axis=1)
    sample = dict(g.arrays())
    sample.update(
        seq_feat=seqs.feat.astype(np.float32),
        seq_mask=seqs.mask,
        seq_label=seqs.label.astype(np.float32),
        seq_valid=seq_valid,
        seq_node_idx=_seq_node_index(g, seqs),
    )
    return sample, stats


def sample_spec(cfg: DatasetConfig) -> dict[str, tuple[tuple[int, ...], str]]:
    """The static shape contract of `window_sample`: ``key → (shape, dtype)``
    for every array a window lowered at ``cfg`` carries, derived from the
    config alone — no trace, no lowering, no jax.

    This is the shape authority the deep static pass (`nerrf lint --deep`,
    nerrf_tpu/analysis/programs/) proves the serve ladder's signature
    closure against: admission can only ever produce batches of these
    shapes, so warmup compiling exactly these shapes IS the zero-recompile
    contract.  `tests/test_programs.py` cross-checks it against a real
    `window_sample` output so the two can never drift silently."""
    from nerrf_tpu.data.sequences import SEQ_FEATURE_DIM
    from nerrf_tpu.graph.builder import EDGE_FEATURE_DIM, NODE_FEATURE_DIM

    n, e = cfg.graph.max_nodes, cfg.graph.max_edges
    s, t = cfg.max_seqs, cfg.seq_len
    return {
        "node_feat": ((n, NODE_FEATURE_DIM), "float32"),
        "node_type": ((n,), "int32"),
        "node_aux": ((n,), "int32"),
        "node_mask": ((n,), "bool"),
        "node_key": ((n,), "int64"),
        "node_label": ((n,), "float32"),
        "edge_src": ((e,), "int32"),
        "edge_dst": ((e,), "int32"),
        "edge_feat": ((e, EDGE_FEATURE_DIM), "float32"),
        "edge_mask": ((e,), "bool"),
        "edge_label": ((e,), "float32"),
        "seq_feat": ((s, t, SEQ_FEATURE_DIM), "float32"),
        "seq_mask": ((s, t), "bool"),
        "seq_label": ((s,), "float32"),
        "seq_valid": ((s,), "bool"),
        "seq_node_idx": ((s,), "int32"),
    }


def windows_of_trace(trace: Trace, cfg: DatasetConfig,
                     stats_out: Optional[list] = None) -> List[dict[str, np.ndarray]]:
    """All window samples for one trace.

    ``stats_out``, when given, receives one ``WindowStats`` per *emitted*
    sample so callers (corpus generation) can account for capacity overflow —
    the r2 corpus was silently truncating attack-burst windows at the
    256n/512e defaults, which is exactly the signal a detector needs.

    One ``trace_lower`` span a trace: the labels, every window's
    ``graph_lower`` (its children) and the per-file sequences around them,
    which are three quarters of the lowering's time and no span's otherwise.
    """
    with span("trace_lower") as sp:
        labels = derive_event_labels(trace)
        ev = trace.events
        if ev.num_valid == 0:
            return []
        valid_ts = ev.ts_ns[ev.valid]
        out = []
        for lo, hi in snapshot_windows(int(valid_ts.min()),
                                       int(valid_ts.max()), cfg.graph):
            sample, stats = window_sample(trace, lo, hi, cfg, labels=labels)
            if sample is None:
                continue
            if stats_out is not None:
                stats_out.append(stats)
            out.append(sample)
        sp.args["windows"] = len(out)
    return out


def padding_waste_fractions(arrays) -> dict[str, float]:
    """Fraction of padded capacity carrying no real data, per dimension.

    Static shapes mean a padded slot costs exactly as much device compute
    as a real one, so this IS the step-time attribution for bucket sizing:
    train loops stamp it as the ``train_padding_waste_fraction`` gauge and
    the bench artifacts carry it per bucket."""
    masks = (("node", "node_mask"), ("edge", "edge_mask"),
             ("seq", "seq_valid"))
    return {kind: round(float(1.0 - np.asarray(arrays[key]).mean()), 4)
            for kind, key in masks if key in arrays}


def fit_dataset_config(traces: List[Trace],
                       cfg: Optional[DatasetConfig] = None) -> DatasetConfig:
    """A DatasetConfig whose graph capacities fit every window of ``traces``
    with zero drops (GraphConfig.fit_counts bucket policy, corpus-wide max).
    Evaluation datasets must use this: scoring a model on windows that
    silently truncate the attack burst measures the truncation, not the
    model (r2 verdict weak #3)."""
    from nerrf_tpu.graph.builder import measure_window

    cfg = cfg or DatasetConfig()
    max_n = max_e = 0
    for tr in traces:
        ev = tr.events
        if ev.num_valid == 0:
            continue
        ts = ev.ts_ns[ev.valid]
        for lo, hi in snapshot_windows(int(ts.min()), int(ts.max()), cfg.graph):
            n, e = measure_window(ev, lo, hi)
            max_n, max_e = max(max_n, n), max(max_e, e)
    return dataclasses.replace(cfg, graph=cfg.graph.fit_counts(max_n, max_e))


def build_dataset(traces: List[Trace], cfg: Optional[DatasetConfig] = None) -> WindowDataset:
    cfg = cfg or DatasetConfig()
    samples: List[dict[str, np.ndarray]] = []
    for tr in traces:
        samples.extend(windows_of_trace(tr, cfg))
    if not samples:
        raise ValueError("no window samples produced — traces empty?")
    keys = samples[0].keys()
    return WindowDataset({k: np.stack([s[k] for s in samples]) for k in keys})
