"""mfu.train: tokens per second times the model FLOPs of a token
(chipbench/counts/lm.py), as a share of the chips' bf16 peak."""

from chipbench.layer import mfu


def read(ctx):
    return mfu(ctx)
