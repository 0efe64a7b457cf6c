"""The grouped-query layers' projections' share of their roofline: least time
for ``W_q``, ``W_k``, ``W_v``, ``W_o`` and the head gate's ``W_g`` by the
real token, forward and backward (`chipbench/work/laguna.py`, 3 x in
training; FLOPs bound it), over the device time of every operation traced
under ``gqa_proj`` (the products, the rotary passes and the gate).  None
where the trace has no such scope."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "gqa_proj")
