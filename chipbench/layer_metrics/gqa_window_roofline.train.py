"""The window layers' attention core's share of its roofline: least time for
``Q K^T`` and ``P V`` over 72 query heads of 128 (8 key-value heads) at the
pairs a window of 512 lets attend, forward and backward
(`chipbench/work/laguna.py`: window pairs x 72 x (128 + 128) x 2 FLOPs, 3 x
in training; FLOPs bound it), over the device time of every operation traced
under ``gqa_window_attention`` (the core's kernels, forward and backward, and
the XLA around them).  None where the trace has no such scope."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "gqa_window_attention")
