"""The tied output head and its loss: least time for the logits' product
over the held vocabulary rows, forward and backward (`chipbench/work/`;
FLOPs bound it), over the device time of every operation traced under
``lm_head_loss``."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "lm_head")
