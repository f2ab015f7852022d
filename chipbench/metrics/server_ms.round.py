"""server_ms.round: device ms a FedNL round spends under the program's
``fednl.server`` scope (the payload-space mean and the server's H
update), in the traced window."""

from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "fednl.server", per="round")
