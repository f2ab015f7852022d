"""mfu.round: FedNL rounds per second times the model FLOPs of a round
(chipbench/counts/fednl.py), as a share of the chips' bf16 peak."""

from chipbench.layer import mfu


def read(ctx):
    return mfu(ctx)
