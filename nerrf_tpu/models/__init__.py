import time as _time

_T_IMPORT = _time.perf_counter()   # for the `module_import` span below

from nerrf_tpu.models.graphsage import GraphSAGET, GraphSAGEConfig
from nerrf_tpu.models.lstm import ImpactLSTM, LSTMConfig
from nerrf_tpu.models.joint import NerrfNet, JointConfig
from nerrf_tpu.models.stream import StreamNet, StreamConfig, stream_loss
from nerrf_tpu.tracing import record as _record

# what the model modules brought in after the tracer's epoch (flax, optax and
# the op libraries: the largest import of a run that reaches for
# `nerrf_tpu.config` first, as the benchmark's generators do)
_record("module_import", _time.perf_counter() - _T_IMPORT, module=__name__)

__all__ = [
    "GraphSAGET",
    "GraphSAGEConfig",
    "ImpactLSTM",
    "LSTMConfig",
    "NerrfNet",
    "JointConfig",
    "StreamNet",
    "StreamConfig",
    "stream_loss",
]
