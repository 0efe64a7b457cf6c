"""The held experts' grouped products' share of their roofline: least time
for the SwiGLU products of the assignments the router sent to the experts
held here, forward and backward (`chipbench/work/keyevl2.py`: 3 x 2 x 2048 x
768 FLOPs an assignment, the count the program's own; FLOPs bound it), over
the device time of every operation traced under ``moe_experts`` (the walk
over the used tiles: their gathers, the three products, the backward pass's
accumulation).  None where the trace has no such scope."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "moe_experts")
