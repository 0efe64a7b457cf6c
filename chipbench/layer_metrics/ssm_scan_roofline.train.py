"""The selective scan's share of its roofline: least time for the
recurrence's required work, forward and backward (`chipbench/work/`: 6 x
d_inner x d_state FLOPs and (3 x d_inner + 2 x d_state) x 2 bytes a real
token and layer, training 3 x; bytes bound it), over the device time of
every operation traced under ``ssm_scan``."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "ssm_scan")
