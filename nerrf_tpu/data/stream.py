"""Whole-trace event-stream extraction for StreamNet (the long-context path).

Where `sequences.py` slices the last 100 events of one file (the reference's
LSTM input spec), this module lowers the *entire* trace to one time-ordered
feature sequence with per-event labels — the input the sequence-parallel
stream detector attends over.  Long traces are split into consecutive
``max_len`` segments (label structure is preserved: segment boundaries fall
between events, never inside one).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from nerrf_tpu.data.loaders import Trace
from nerrf_tpu.data.sequences import SEQ_FEATURE_DIM, event_features
from nerrf_tpu.schema.events import Syscall

STREAM_FEATURE_DIM = SEQ_FEATURE_DIM  # same per-event feature layout


@dataclasses.dataclass
class StreamBatch:
    feat: np.ndarray    # float32 [B, T, STREAM_FEATURE_DIM]
    mask: np.ndarray    # bool    [B, T]
    label: np.ndarray   # float32 [B, T] per-event attack labels

    def __len__(self) -> int:
        return len(self.feat)

    @staticmethod
    def concatenate(batches: list["StreamBatch"]) -> "StreamBatch":
        return StreamBatch(
            feat=np.concatenate([b.feat for b in batches]),
            mask=np.concatenate([b.mask for b in batches]),
            label=np.concatenate([b.label for b in batches]),
        )

    def arrays(self) -> dict[str, np.ndarray]:
        return {"feat": self.feat, "mask": self.mask, "label": self.label}

    def tile_to_multiple(self, n: int) -> dict[str, np.ndarray]:
        """Arrays with batch tiled (wrapping) up to the next multiple of n.

        Always covers every segment at least once (rounds len up, never
        down), so data-parallel sharding over ``n`` devices drops nothing.
        """
        if len(self) == 0:
            raise ValueError("cannot tile an empty StreamBatch")
        size = max(n, ((len(self) + n - 1) // n) * n)
        idx = np.arange(size) % len(self)
        return {k: v[idx] for k, v in self.arrays().items()}


def build_stream(trace: Trace, max_len: int = 1024) -> StreamBatch:
    """Trace → [num_segments, max_len, F] padded stream segments."""
    ev = trace.events
    lab = (
        trace.labels
        if trace.labels is not None
        else np.zeros(len(ev), np.float32)
    )
    sel = ev.valid & (ev.syscall != int(Syscall.MARKER))
    idx = np.nonzero(sel)[0]
    if len(idx) == 0:
        return StreamBatch(
            feat=np.zeros((0, max_len, STREAM_FEATURE_DIM), np.float32),
            mask=np.zeros((0, max_len), np.bool_),
            label=np.zeros((0, max_len), np.float32),
        )

    ts = ev.ts_ns[idx]
    t0, t1 = int(ts.min()), max(int(ts.max()), int(ts.min()) + 1)
    f = event_features(ev, idx, trace.strings.features(), t0, t1)
    # feature 7 here is the *global* inter-event gap (stream time structure —
    # recon bursts vs the steady encryption cadence), vs per-file in
    # build_file_sequences
    f[:, 7] = np.log1p(np.diff(ts, prepend=ts[0]) / 1e9)

    labels = np.asarray(lab, np.float32)[idx]

    n = len(idx)
    num_seg = (n + max_len - 1) // max_len
    out_feat = np.zeros((num_seg, max_len, STREAM_FEATURE_DIM), np.float32)
    out_mask = np.zeros((num_seg, max_len), np.bool_)
    out_label = np.zeros((num_seg, max_len), np.float32)
    for s in range(num_seg):
        lo, hi = s * max_len, min((s + 1) * max_len, n)
        k = hi - lo
        out_feat[s, :k] = f[lo:hi]
        out_mask[s, :k] = True
        out_label[s, :k] = labels[lo:hi]
    return StreamBatch(feat=out_feat, mask=out_mask, label=out_label)


def build_streams(traces: list[Trace], max_len: int = 1024) -> StreamBatch:
    return StreamBatch.concatenate([build_stream(t, max_len) for t in traces])


# --- event tokens and packed documents (the stream pretrainer's input) -------


@dataclasses.dataclass(frozen=True)
class PackConfig:
    """How a stream experiment's traces become packed token sequences."""

    seq_len: int = 8192       # tokens a packed sequence
    num_seqs: int = 32        # sequences resident on the device
    doc_median: float = 1024.0   # documents: lognormal lengths ...
    doc_sigma: float = 1.0
    doc_min: int = 64            # ... clipped to [doc_min, seq_len]
    seed: int = 0


def tokenize_events(trace: Trace, vocab_size: int) -> np.ndarray:
    """Trace → one int32 token id per event, in stream order (the events
    `build_stream` keeps).  An id is a hash of what an analyst reads off an
    event — its syscall, the path's depth and extension, the size bucket
    (log2 of the bytes moved) and the bucket of the gap since the previous
    event (log2 of the microseconds) — into ``[0, vocab_size)``."""
    from nerrf_tpu.tracing import DEFAULT_TRACER

    with DEFAULT_TRACER.span("stream_tokenize") as sp:
        ev = trace.events
        idx = np.nonzero(ev.valid & (ev.syscall != int(Syscall.MARKER)))[0]
        strings = trace.strings.strings()
        depth = np.array([min(s.count("/"), 15) for s in strings], np.uint64)
        ext = trace.strings.extension_ids().astype(np.uint64)
        path = ev.path_id[idx]
        ts = ev.ts_ns[idx]
        gap_us = np.maximum(np.diff(ts, prepend=ts[:1]), 0) // 1000
        bucket = lambda v: np.minimum(
            np.log2(v.astype(np.float64) + 1.0), 31).astype(np.uint64)
        key = ev.syscall[idx].astype(np.uint64)
        for part in (depth[path], ext[path],
                     bucket(np.maximum(ev.bytes[idx], 0)), bucket(gap_us)):
            key = key * np.uint64(1024) + part
        # splitmix64's finalizer: neighbouring keys land far apart
        key = (key ^ (key >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        key = (key ^ (key >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        key = key ^ (key >> np.uint64(31))
        sp.args["events"] = len(idx)
        return (key % np.uint64(vocab_size)).astype(np.int32)


def cut_documents(tokens: np.ndarray, rng: np.random.Generator,
                  median: float = 1024.0, sigma: float = 1.0,
                  shortest: int = 64, longest: int = 8192) -> list:
    """One trace's tokens → consecutive documents whose lengths are drawn
    from a lognormal (``median``, ``sigma``) clipped to ``[shortest,
    longest]``: heavy-tailed, as incident logs are.  A tail shorter than
    ``shortest`` is dropped."""
    docs, lo = [], 0
    while len(tokens) - lo >= shortest:
        n = int(np.clip(rng.lognormal(np.log(median), sigma), shortest,
                        min(longest, len(tokens) - lo)))
        docs.append(tokens[lo:lo + n])
        lo += n
    return docs


def pack_documents(docs: list, seq_len: int, num_seqs: int):
    """First-fit packing of token documents into ``num_seqs`` sequences of
    ``seq_len`` → (tokens [num_seqs, seq_len] int32, segments [num_seqs,
    seq_len] int32, waste).  A document goes whole into the first sequence
    that has room for it (longer than ``seq_len``: cut to it); a sequence's
    documents are numbered 1, 2, ... in ``segments`` and its unused tail is
    0 (padding: never a target, never attended to by a document).  Sequences
    are opened as needed and the first ``num_seqs`` are returned; fewer is
    an error.  ``waste`` is the padded share of the returned sequences."""
    from nerrf_tpu.observability import DEFAULT_REGISTRY
    from nerrf_tpu.tracing import DEFAULT_TRACER

    with DEFAULT_TRACER.span("stream_pack", documents=len(docs)) as sp:
        used, rows = [], []
        for doc in docs:
            doc = doc[:seq_len]
            for i, n in enumerate(used):
                if n + len(doc) <= seq_len:
                    break
            else:
                i = len(used)
                used.append(0)
                rows.append([])
            rows[i].append(doc)
            used[i] += len(doc)
        if len(rows) < num_seqs:
            raise ValueError(f"{len(docs)} documents fill {len(rows)} "
                             f"sequences of {seq_len}; {num_seqs} are asked")
        tokens = np.zeros((num_seqs, seq_len), np.int32)
        segments = np.zeros((num_seqs, seq_len), np.int32)
        for i, row in enumerate(rows[:num_seqs]):
            lo = 0
            for j, doc in enumerate(row, 1):
                tokens[i, lo:lo + len(doc)] = doc
                segments[i, lo:lo + len(doc)] = j
                lo += len(doc)
        waste = float(np.mean(segments == 0))
        sp.args["waste"] = waste
    DEFAULT_REGISTRY.gauge_set(
        "stream_pack_waste_fraction", waste,
        help="padded share of the packed stream sequences last built")
    DEFAULT_REGISTRY.counter_inc(
        "stream_tokens_total", float(np.sum(segments > 0)),
        help="real event tokens packed into stream training sequences")
    return tokens, segments, waste


def build_packed_streams(traces: list[Trace], vocab_size: int,
                         pack: PackConfig = PackConfig()):
    """Traces → tokenized, cut into documents (`cut_documents`, seeded) and
    packed (`pack_documents`) → {"tokens", "segments"} arrays and the
    packing waste."""
    rng = np.random.default_rng([int(pack.seed), 0xd0c5])
    docs = []
    for trace in traces:
        docs.extend(cut_documents(
            tokenize_events(trace, vocab_size), rng, median=pack.doc_median,
            sigma=pack.doc_sigma, shortest=pack.doc_min,
            longest=pack.seq_len))
    tokens, segments, waste = pack_documents(docs, pack.seq_len,
                                             pack.num_seqs)
    return {"tokens": tokens, "segments": segments}, waste
