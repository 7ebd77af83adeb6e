"""Device time per round of the operations the program places in the
server's decode: the ``fedqcs.decode`` scope and its sub-scopes (device
trace, ``bench/scopes.py``)."""

import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "fedqcs.decode")
