"""Fused client encoder (error-feedback add, top-S, projection, quantize,
pack): operations and HBM bytes per round.  Per block row the projection is
2*M*N FLOPs; the row reads its gradient and residual blocks and writes the
new residual (f32, N each), its packed words and its scale."""

PATTERN = r"bqcs_encode_fused_pallas"


def count(shape):
    rows = shape["clients"] * shape["rows"]
    n, m, w = shape["n"], shape["m"], shape["words"]
    flops = rows * 2 * m * n
    nbytes = rows * (4 * 3 * n + 4 * w + 4)
    return flops, nbytes
