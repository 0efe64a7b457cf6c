from nerrf_tpu.data.loaders import (
    GroundTruth,
    Trace,
    load_ground_truth_csv,
    load_trace_jsonl,
)
from nerrf_tpu.data.synth import SimConfig, simulate_trace, make_corpus
from nerrf_tpu.data.labels import derive_event_labels
from nerrf_tpu.data.stream import (
    STREAM_FEATURE_DIM,
    PackConfig,
    StreamBatch,
    build_packed_streams,
    build_stream,
    build_streams,
)

__all__ = [
    "GroundTruth",
    "Trace",
    "load_ground_truth_csv",
    "load_trace_jsonl",
    "SimConfig",
    "simulate_trace",
    "make_corpus",
    "derive_event_labels",
    "StreamBatch",
    "build_stream",
    "build_packed_streams",
    "PackConfig",
    "build_streams",
    "STREAM_FEATURE_DIM",
]
