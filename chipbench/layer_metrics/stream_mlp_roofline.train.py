"""The SwiGLU MLPs' share of their roofline: least time for their three
products a layer, forward and backward (`chipbench/work/`; FLOPs bound
them), over the device time of every operation traced under
``stream_mlp``."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "stream_mlp")
