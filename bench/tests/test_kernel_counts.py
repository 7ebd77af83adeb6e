"""Kernel operation and byte counts against hand counts at a tiny shape."""

import pytest

import readers

SHAPE = {"method": "fedqcs-ae", "clients": 2, "rows": 10, "n": 8, "m": 3, "bits": 3,
         "words": 1, "iters": 5, "components": 3, "tokens": 64, "seq": 32}


def test_encoder():
    flops, nbytes = readers.kernel("bqcs_encode_fused").count(SHAPE)
    # 20 client rows; each: 2*3*8 = 48 FLOPs; reads 8+8 f32, writes 8 f32,
    # 1 word and 1 scale: 4*24 + 4 + 4 = 104 bytes
    assert (flops, nbytes) == (20 * 48, 20 * 104)


def test_ae_gamp():
    flops, nbytes = readers.kernel("gamp_step").count(SHAPE)
    # 10 rows x 5 iterations x 2 products x 2*3*8; per row 3 measurements,
    # noise variance, prior energy in, 8 estimates out: 4*(3+2+8) = 52 bytes
    assert (flops, nbytes) == (10 * 5 * 2 * 48, 10 * 52)
    assert readers.kernel("gamp_step").count(dict(SHAPE, method="fedqcs-ea")) == (0.0, 0.0)


def test_ea_gamp():
    shape = dict(SHAPE, method="fedqcs-ea")
    flops, nbytes = readers.kernel("qgamp_step").count(shape)
    # 20 client rows x 5 x 2 x 48; per row 1 word + 1 scale in, 8 out
    assert (flops, nbytes) == (20 * 5 * 2 * 48, 20 * (4 + 4 + 32))
    assert readers.kernel("qgamp_step").count(SHAPE) == (0.0, 0.0)


def test_patterns_tell_the_gamp_kernels_apart():
    import re

    ae, ea = readers.kernel("gamp_step").PATTERN, readers.kernel("qgamp_step").PATTERN
    assert re.search(ae, "gamp_step_pallas.5") and not re.search(ae, "qgamp_step_pallas.1")
    assert re.search(ea, "qgamp_step_pallas.1") and not re.search(ea, "gamp_step_pallas.5")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        readers.peaks("cpu")
    assert readers.peaks("TPU v5 lite")["flops_per_s"] == 197e12
