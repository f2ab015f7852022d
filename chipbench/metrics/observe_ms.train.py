"""observe_ms.train: device ms a train step spends under the program's
``train.observe`` scope (the curvature refresh's observations), in the
traced window."""

from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "train.observe", per="step")
