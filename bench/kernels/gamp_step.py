"""AE decode, EM-GAMP on the Bussgang aggregate: operations and HBM bytes
per round.  Each of the GAMP iterations takes two (M x N) products per block
row (A g and A^T s, 2*M*N FLOPs each); the solve reads the row's aggregate
measurements (M), its noise variance and prior energy, and writes its
estimate (N), all f32, once."""

PATTERN = r"(?<!q)gamp_step_pallas"


def count(shape):
    if shape["method"] != "fedqcs-ae":
        return 0.0, 0.0
    rows, n, m = shape["rows"], shape["n"], shape["m"]
    flops = rows * shape["iters"] * 2 * 2 * m * n
    nbytes = rows * 4 * (m + 2 + n)
    return flops, nbytes
