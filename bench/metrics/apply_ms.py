"""Device time per round of the server update: FedAdam and the residual
scatter (device trace)."""


def read(ctx):
    return ctx.phase_ms("apply")
