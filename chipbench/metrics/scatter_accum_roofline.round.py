"""scatter_accum_roofline.round: the least time of the scatter_accum kernel's work
(chipbench/counts/kernels.py) over its device time in the trace."""

from chipbench.layer import roofline


def read(ctx):
    return roofline(ctx, "scatter_accum")
