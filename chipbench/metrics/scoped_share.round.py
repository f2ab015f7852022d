"""scoped_share.round: the share of the traced window's op time on the
device that lies under one of the program's phase scopes (``fednl.*``,
``train.*``)."""

from chipbench.scopes import scoped_share


def read(ctx):
    return scoped_share(ctx)
