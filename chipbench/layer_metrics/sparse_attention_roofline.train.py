"""The chosen-set attention's share of its roofline: least time for ``Q K^T``
and ``P V`` at the SELECTED query-key pairs alone, forward and backward
(`chipbench/work/keyevl2.py`: 16,384 FLOPs a pair and layer; the
projections lie outside the scope), over the device time of every operation
traced under ``dsa_attention``.  A program that computes every allowed pair
and masks answers for the difference here.  None where the trace has no
such scope."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "sparse_attention")
