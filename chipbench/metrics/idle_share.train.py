"""idle_share.train: the share of the traced window in which no op ran on the
device (mean over the chips used)."""

from chipbench.layer import idle_share


def read(ctx):
    return idle_share(ctx)
