"""Device time per round of the server's decode program (device trace)."""


def read(ctx):
    return ctx.phase_ms("decode")
