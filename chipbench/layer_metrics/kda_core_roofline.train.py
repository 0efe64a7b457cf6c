"""The delta rule's share of its roofline: least time for its required work
(`chipbench/work/kimilinear.py`: 7 x d_k x d_v FLOPs a real token and head;
q, k, v, o in the compute type and g in float32 moved once a pass; 3 x in
training), over the device time of every operation traced under
``kda_core``, forward and backward.  None where the trace has no such
scope."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "kda_core")
