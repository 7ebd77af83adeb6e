"""Program runs on the device per ``fedqcs_round`` profiler step, over the
traced window (device trace, ``bench/scopes.py``)."""

import scopes


def read(ctx):
    return scopes.per_round(ctx, "module_runs")
