"""The delta-rule layers' projections' share of their roofline: least time
for ``W_q``, ``W_k``, ``W_v``, the decay's and the output gate's factors,
``W_b``, ``W_o`` and the short convolutions by the real token, forward and
backward (`chipbench/work/kimilinear.py`, 3 x in training; FLOPs bound it),
over the device time of every operation traced under ``kda_proj`` (the
products, the convolutions, the norms and the gates).  None where the trace
has no such scope."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "kda_proj")
