"""uplink_ms.train: device ms a train step spends under the program's
``fednl.uplink`` scope (the refresh's diff, select and payload), in the
traced window."""

from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "fednl.uplink", per="step")
