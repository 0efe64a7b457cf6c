from nerrf_tpu.ops.segment import (
    gather_rows,
    sage_aggregate,
    segment_mean,
)

__all__ = ["segment_mean", "gather_rows", "sage_aggregate"]
