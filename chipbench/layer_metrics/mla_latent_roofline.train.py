"""The latent projections' share of their roofline: least time for the five
products of latent attention (``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``,
``W_o``) by the real token in the stack's layers, forward and backward
(`chipbench/work/glm47flash.py`; FLOPs bound it), over the device time of
every operation traced under ``mla_latent`` outside the MTP module: the
products, the two inner norms, both rotary passes, the assembly of ``q`` and
``k``, and remat's second forward.  None where the trace has no such
scope."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "mla_latent")
