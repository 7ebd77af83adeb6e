"""Device time per round of the operations the program places in the
server update: the ``fedqcs.apply`` scope, FedAdam and the residual scatter
(device trace, ``bench/scopes.py``)."""

import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "fedqcs.apply")
