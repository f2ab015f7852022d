"""solve_ms.round: device ms a FedNL round spends under the program's
``fednl.solve`` scope (the server's Newton system and its solve), in the
traced window."""

from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "fednl.solve", per="round")
