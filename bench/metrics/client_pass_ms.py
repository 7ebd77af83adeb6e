"""Device time per round of the client pass's programs: the staged VJPs of
the segment producer, the per-segment encode and the cohort's data (device
trace, programs placed by bench/phases.json)."""


def read(ctx):
    return ctx.phase_ms("client_pass")
