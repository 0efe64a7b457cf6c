"""The full layers' attention core's share of its roofline: least time for
``Q K^T`` and ``P V`` over 48 query heads of 128 (8 key-value heads) at the
causal same-document pairs, forward and backward (`chipbench/work/laguna.py`:
pairs x 48 x (128 + 128) x 2 FLOPs, 3 x in training; FLOPs bound it), over
the device time of every operation traced under ``gqa_full_attention``.
None where the trace has no such scope."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "gqa_full_attention")
