"""server_ms.train: device ms a train step spends under the program's
``fednl.server`` scope (the refresh's payload-space mean and H update),
in the traced window."""

from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "fednl.server", per="step")
