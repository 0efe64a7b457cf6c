"""The 28 SageBlocks' share of their roofline: least time for their required
work, forward and backward (`chipbench/work/`: FLOPs and bytes from shapes;
bytes bound it at both buckets), over the device time of every operation
traced under a ``gnn_layer_<i>`` scope, the fused aggregate included."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "gnn_layers")
