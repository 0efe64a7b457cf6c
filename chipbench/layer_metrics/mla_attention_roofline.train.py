"""The latent attention core's share of its roofline: least time for ``Q
K^T`` over the 256-wide assembled keys and ``P V`` over the 256-wide values
at the attending pairs of the stack's layers, forward and backward
(`chipbench/work/glm47flash.py`: 2 x 20 x (256 + 256) FLOPs a pair, 3 x in
training; FLOPs bound it), over the device time of every operation traced
under ``mla_attention`` outside the MTP module (the forward blocks and the
hand-written flash-style backward pass).  None where the trace has no such
scope."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "mla_attention")
