"""The BiLSTM's share of its roofline: least time for its required work,
forward and backward (`chipbench/work/`; FLOPs bound it), over the device
time of every operation traced under ``lstm_layer_<i>`` / ``lstm_scan``."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "lstm")
