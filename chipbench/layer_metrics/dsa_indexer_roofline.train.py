"""The indexer's scores' share of their roofline: least time for ``qI . kI``
over every allowed pair forward and over the selected pairs twice backward
(`chipbench/work/keyevl2.py`: 2 x 16 x 64 FLOPs a pair, against the chip's
bf16 peak though the program computes them in float32 at ``highest``
precision), over the device time of every operation traced under
``dsa_indexer``.  None where the trace has no such scope."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "dsa_indexer")
