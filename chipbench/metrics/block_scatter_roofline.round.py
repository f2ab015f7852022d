"""block_scatter_roofline.round: the least time of the block_scatter kernel's work
(chipbench/counts/kernels.py) over its device time in the trace."""

from chipbench.layer import roofline


def read(ctx):
    return roofline(ctx, "block_scatter")
