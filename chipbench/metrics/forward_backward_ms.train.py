"""forward_backward_ms.train: device ms a train step spends under the
program's ``train.forward_backward`` scope (the step's forward and
backward pass), in the traced window."""

from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "train.forward_backward", per="step")
