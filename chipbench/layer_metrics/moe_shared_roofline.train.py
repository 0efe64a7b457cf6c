"""The shared expert's share of its roofline: least time for the SwiGLU every
real token passes beside the routed experts in the stack's expert layers,
forward and backward (`chipbench/work/glm47flash.py`: 3 x 2 x 2048 x 1536
FLOPs a token, 3 x in training; FLOPs bound it), over the device time of
every operation traced under ``moe_shared`` outside the MTP module.  None
where the trace has no such scope."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "moe_shared")
