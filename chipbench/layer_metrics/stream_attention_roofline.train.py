"""The stream encoder's attention cores' share of their roofline: least
time for the required work of the window, full and cross layers
(`chipbench/work/`: only the query-key pairs the causal, window and
document masks let attend count; the projections lie outside the scopes),
over the device time of every operation traced under ``swa_attention``,
``full_attention``, ``cross_attention``."""

from chipbench import roofline


def read(run):
    return roofline.share(run, "stream_attention")
