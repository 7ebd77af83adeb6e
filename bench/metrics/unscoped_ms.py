"""Device time per round of the operations in no layer scope: the eager
glue between the round's programs and the benchmark's data program (device
trace, ``bench/scopes.py``)."""

import scopes


def read(ctx):
    return scopes.layer_ms(ctx, scopes.UNSCOPED)
