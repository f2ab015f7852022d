"""uplink_ms.round: device ms a FedNL round spends under the program's
``fednl.uplink`` scope (each silo's diff, select and payload), in the
traced window."""

from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "fednl.uplink", per="round")
