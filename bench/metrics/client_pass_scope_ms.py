"""Device time per round of the operations the program places in its
client pass: the ``fedqcs.client`` scope and its sub-scopes (device trace,
``bench/scopes.py``)."""

import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "fedqcs.client")
