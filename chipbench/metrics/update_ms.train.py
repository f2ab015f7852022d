"""update_ms.train: device ms a train step spends under the program's
``train.update`` scope (the preconditioned update and the grad norm), in
the traced window."""

from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "train.update", per="step")
