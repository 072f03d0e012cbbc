"""``rank_pallas`` time in the trace against its roofline (``bench/kernels``)."""

from annbench import roofline


def read(ctx):
    return roofline.share(ctx, "rank_pallas")
