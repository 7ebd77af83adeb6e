"""The scalar level lookup (DESIGN.md #Codebooks): every scalar-codebook
dequantize reads ``levels[codes]`` through ``quantizer.select_levels``, a
select chain over the static levels, and must return NumPy's
``levels[codes]`` bit for bit -- in the index domain, straight from packed
words, and inside the Bussgang aggregates -- for both scalar families and
every wire width Q in 1..8, at a ragged M (85 lanes leave the last packed
word partly filled) with rows of all-0 and all-(L-1) codes and zero-alpha
rows."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bussgang
from repro.core.codebook import as_codebook, design_dithered_uniform
from repro.core.compression import decode_packed, pack_codes
from repro.core.quantizer import design_lloyd_max, select_levels

jax.config.update("jax_platform_name", "cpu")

M = 85  # the launcher's lanes (N=256, R=3): no Q in 1..8 divides the word grid
NB = 6
K = 2
FAMILIES = ["lloyd_max", "dithered_uniform"]
BITS = list(range(1, 9))


@functools.lru_cache(maxsize=None)
def _codebook(family, bits):
    if family == "lloyd_max":
        return as_codebook(design_lloyd_max(bits))
    return design_dithered_uniform(bits, M, seed=3)


def _codes(cb, seed=0):
    """(K, NB, M) uint8 codes: row 0 all 0, row 1 all L-1, the rest random
    over [0, L) with both ends forced into every row."""
    rng = np.random.default_rng((seed, cb.bits))
    c = rng.integers(0, cb.n_levels, size=(K, NB, M)).astype(np.uint8)
    c[:, 0] = 0
    c[:, 1] = cb.n_levels - 1
    c[:, 2:, 0] = 0
    c[:, 2:, -1] = cb.n_levels - 1
    return c


def _np_decode(cb, codes):
    """NumPy's ``levels[codes]`` (float32), less the dither where there is one."""
    deq = np.asarray(cb.levels, np.float32)[codes]
    if cb.dither is not None:
        deq = deq - np.asarray(cb.dither, np.float32)[: codes.shape[-1]]
    return deq


def _alphas_rhos(seed=0):
    rng = np.random.default_rng(seed)
    alphas = rng.uniform(0.5, 2.0, size=(K, NB)).astype(np.float32)
    alphas[0, 3] = 0.0  # one client's empty block
    alphas[:, 4] = 0.0  # an empty block on every client
    return jnp.asarray(alphas), jnp.asarray([0.6, 0.4], jnp.float32)


def _np_aggregate(cb, codes, alphas, rhos):
    """eq. 23 on the NumPy lookup, with the program's own Bussgang weights
    and sum (jitted, as the aggregates are)."""

    def weighted_sum(deq, a, r):
        w = bussgang.bussgang_weight(r[:, None], a, cb)
        return jnp.sum(w[..., None] * deq, axis=0)

    return np.asarray(jax.jit(weighted_sum)(_np_decode(cb, codes), alphas, rhos))


def _check(cb, fn):
    codes = _codes(cb)
    words = jax.vmap(lambda c: pack_codes(c, cb.bits))(jnp.asarray(codes))
    alphas, rhos = _alphas_rhos()
    got, want = fn(cb, codes, words, alphas, rhos)
    got = np.asarray(got)
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def _decode_full(cb, codes, words, alphas, rhos):
    return jax.jit(cb.decode)(jnp.asarray(codes)), _np_decode(cb, codes)


def _decode_m(cb, codes, words, alphas, rhos):
    # m cuts the lanes after the dither: decode the 85 lanes, keep 80
    got = jax.jit(lambda c: cb.decode(c, 80))(jnp.asarray(codes))
    return got, _np_decode(cb, codes)[..., :80]


def _decode_packed_fn(cb, codes, words, alphas, rhos):
    got = jax.jit(lambda w: decode_packed(w, cb.bits, M, cb.jnp_levels()))(words)
    return got, np.asarray(cb.levels, np.float32)[codes]


def _codebook_decode_packed(cb, codes, words, alphas, rhos):
    return jax.jit(lambda w: cb.decode_packed(w, M))(words), _np_decode(cb, codes)


def _aggregate_codes(cb, codes, words, alphas, rhos):
    got = jax.jit(lambda c, a, r: bussgang.aggregate_codes(c, a, r, cb))(
        jnp.asarray(codes), alphas, rhos
    )
    return got, _np_aggregate(cb, codes, alphas, rhos)


def _aggregate_packed(cb, codes, words, alphas, rhos):
    got = jax.jit(lambda w, a, r: bussgang.aggregate_packed(w, a, r, cb, M))(
        words, alphas, rhos
    )
    return got, _np_aggregate(cb, codes, alphas, rhos)


FUNCTIONS = {
    "decode": _decode_full,
    "decode_m": _decode_m,
    "decode_packed": _decode_packed_fn,
    "codebook_decode_packed": _codebook_decode_packed,
    "aggregate_codes": _aggregate_codes,
    "aggregate_packed": _aggregate_packed,
}


@pytest.mark.parametrize("fn", list(FUNCTIONS))
@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("family", FAMILIES)
def test_scalar_lookup_matches_numpy(family, bits, fn):
    _check(_codebook(family, bits), FUNCTIONS[fn])


@pytest.mark.parametrize("fn", ["aggregate_codes", "aggregate_packed"])
def test_aggregate_zero_alpha_rows_are_zero(fn):
    """A block with alpha == 0 on every client aggregates to exactly 0."""
    cb = _codebook("lloyd_max", 3)
    codes = _codes(cb)
    words = jax.vmap(lambda c: pack_codes(c, cb.bits))(jnp.asarray(codes))
    alphas, rhos = _alphas_rhos()
    out, _ = FUNCTIONS[fn](cb, codes, words, alphas, rhos)
    out = np.asarray(out)
    assert not out[4].any()
    assert out[3].any()  # the other client's block still counts


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.uint32, jnp.int32])
@pytest.mark.parametrize("n_levels", [2, 8, 256])
def test_select_levels_clamps_high_like_the_gather(n_levels, dtype):
    """Indices at or above L read levels[L-1], as JAX's default gather does;
    every index type the wire produces selects the same level."""
    levels = jnp.asarray(np.linspace(-2.0, 2.0, n_levels), jnp.float32)
    top = 255 if dtype == jnp.uint8 else n_levels + 7
    idx = jnp.arange(top + 1).astype(dtype)
    np.testing.assert_array_equal(
        np.asarray(select_levels(levels, idx)),
        np.asarray(levels[idx.astype(jnp.int32)]),
    )
