"""Host time per round inside the program's ``sync`` spans, where the host
waits on a device value (the profiler trace's host plane,
``bench/scopes.py``)."""

import scopes


def read(ctx):
    return scopes.per_round(ctx, "sync_s", 1e3)
