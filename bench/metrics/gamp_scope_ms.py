"""Device time per round of the decode's ``fedqcs.decode/gamp`` scope: the
chunked GAMP solve, its kernel, its scan and the scan's copies (device
trace, ``bench/scopes.py``)."""

import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "fedqcs.decode/gamp")
