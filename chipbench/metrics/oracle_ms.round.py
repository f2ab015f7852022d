"""oracle_ms.round: device ms a FedNL round spends under the program's
``fednl.oracle`` scope (the silos' gradients and Hessians), in the
traced window."""

from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "fednl.oracle", per="round")
