"""local_update_ms.round: device ms a FedNL round spends under the
program's ``fednl.local_update`` scope (each silo's S_i and H_i update),
in the traced window."""

from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "fednl.local_update", per="round")
