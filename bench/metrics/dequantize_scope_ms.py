"""Device time per round of the decode's ``fedqcs.decode/dequantize``
scope: the decoded codes and their Bussgang-weighted sum (device trace,
``bench/scopes.py``)."""

import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "fedqcs.decode/dequantize")
