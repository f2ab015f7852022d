"""diff_topk_payload_roofline.train: the least time of the diff_topk_payload kernel's work
(chipbench/counts/kernels.py) over its device time in the trace."""

from chipbench.layer import roofline


def read(ctx):
    return roofline(ctx, "diff_topk_payload")
