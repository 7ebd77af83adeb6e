"""Share of the bqcs_encode_fused kernel's roofline: its least time at the chip's peaks
over its summed device time in the traced window (device trace)."""


def read(ctx):
    return ctx.roofline("bqcs_encode_fused")
