"""EA decode, quantized-channel EM-GAMP per client: operations and HBM bytes
per round.  Each of the GAMP iterations takes two (M x N) products per client
block row; the solve reads the row's packed words and scale and writes the
client's estimate (N, f32), once."""

PATTERN = r"qgamp_step_pallas"


def count(shape):
    if shape["method"] != "fedqcs-ea":
        return 0.0, 0.0
    rows = shape["clients"] * shape["rows"]
    n, m, w = shape["n"], shape["m"], shape["words"]
    flops = rows * shape["iters"] * 2 * 2 * m * n
    nbytes = rows * (4 * w + 4 + 4 * n)
    return flops, nbytes
