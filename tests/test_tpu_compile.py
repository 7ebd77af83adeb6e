"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret-mode runs (every other kernel test) cannot see what Mosaic refuses:
primitives it does not lower, lane-indexed loads, tiles that overflow the
scoped VMEM.  These tests compile each kernel of the federated round for one
chip of a described ``v5e:2x2`` topology -- nothing runs -- at the block
geometries of the launcher (N=256, and N=255 with the same M) and the
library default (N=1024), with the tile rows ``kernels.ops`` picks, and check the
program holds the kernel as a custom call under the kernel's pinned name (the
name a profile's kernel events carry; the benchmark's roofline patterns match
it).  The scalar dequantize is compiled the same way and must hold no gather.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bussgang
from repro.core.compression import FedQCSConfig, decode_packed, packed_width
from repro.core.codebook import make_codebook
from repro.kernels import bqcs_encode_fused as fenc
from repro.kernels import gamp_step as gstep
from repro.kernels import ops
from repro.kernels import qgamp_step as qstep

# (N, R, Q): the launcher's cohort geometries (255 and 256 give the same
# M=85; 255 leaves a ragged lane tile) and the library default
GEOMETRIES = [(255, 3, 3), (256, 3, 3), (1024, 4, 2)]
L = 3  # mixture components
TL = 1 + 3 * L  # packed theta width


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, name, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # pallas_call(name=...) names the custom call's instruction and adds the
    # name to its op_name, whatever transforms wrap the call
    calls = [line for line in text.splitlines() if " custom-call(" in line]
    assert any(re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", line)
               and re.search(rf'op_name="[^"]*/{name}/pallas_call"', line)
               for line in calls), calls


@pytest.mark.parametrize(
    "n,r,q,codebook",
    [g + ("lloyd_max",) for g in GEOMETRIES] + [(1024, 4, 4, "vq")],
)
def test_bqcs_encode_fused_compiles(one_chip, n, r, q, codebook):
    cfg = FedQCSConfig(block_size=n, reduction_ratio=r, bits=q, codebook=codebook)
    cb = make_codebook(cfg)
    m = cfg.m
    per_word = 32 // cb.bits
    if cb.dim > 1:  # centroid table; every measurement lane is real
        mp, tab = m, (cb.n_levels, cb.dim)
    else:  # threshold vector; A^T padded to the word grid
        mp, tab = packed_width(m, cb.bits) * per_word, (cb.n_levels - 1,)
    w = -(-cb.n_codes(m) // per_word)
    tb = ops._encode_tb(4096, n, mp, w)
    nb = 2 * tb
    # vmapped over the cohort, as the engine's per-segment encode calls it
    fn = jax.vmap(lambda g, res, a_t, t: fenc.bqcs_encode_fused_pallas(
        g, res, a_t, t, s=cfg.s, m=m, bits=cb.bits, vq_d=cb.dim, tb=tb
    ), in_axes=(0, 0, None, None))
    _assert_kernel(fn, "bqcs_encode_fused_pallas", _spec(one_chip, (2, nb, n)),
                   _spec(one_chip, (2, nb, n)), _spec(one_chip, (n, mp)),
                   _spec(one_chip, tab))


@pytest.mark.parametrize("n,r,q", GEOMETRIES)
def test_gamp_step_compiles(one_chip, n, r, q):
    m = n // r
    tb = ops._gamp_tb(4096, n, m, m, L)
    nb = 2 * tb
    fn = lambda *xs: gstep.gamp_step_pallas(*xs, n_components=L, tb=tb)
    s = lambda *shape: _spec(one_chip, shape)
    _assert_kernel(fn, "gamp_step_pallas", s(nb, n), s(nb, n), s(nb, m), s(nb, TL),
                   s(nb, m), s(nb, 1), s(m, n))


@pytest.mark.parametrize("n,r,q", GEOMETRIES)
def test_qgamp_step_packed_compiles(one_chip, n, r, q):
    m = n // r
    w = packed_width(m, q)
    tb = ops._gamp_tb(4096, n, m, w, L)
    nb = 2 * tb
    fn = lambda *xs: qstep.qgamp_step_pallas(*xs, n_components=L, tb=tb, bits=q)
    s = lambda *shape: _spec(one_chip, shape)
    _assert_kernel(fn, "qgamp_step_pallas", s(nb, n), s(nb, n), s(nb, m), s(nb, TL),
                   _spec(one_chip, (nb, w), jnp.uint32), s(nb, 1),
                   s(2**q), s(2**q), s(m, n))


# The scalar dequantize is a select chain over the static levels
# (quantizer.select_levels): a table gather on the chip materializes a
# clamped index array and a gathered copy, 2.9 s of estimated time at the
# launcher's 853,544 rows against 1.5 ms for the select.  The compiled
# program must hold no gather, whichever entry point reads the levels.
DEQUANTIZE_ROWS = 4096


def _assert_no_gather(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert " gather(" not in text, [l for l in text.splitlines() if " gather(" in l]


@pytest.mark.parametrize("q", [3, 8])
@pytest.mark.parametrize("entry", ["aggregate_codes", "aggregate_packed", "decode_packed"])
def test_scalar_dequantize_compiles_without_gather(one_chip, entry, q):
    cfg = FedQCSConfig(block_size=256, reduction_ratio=3, bits=q)
    cb = make_codebook(cfg)
    m, k, nb = cfg.m, 2, DEQUANTIZE_ROWS
    w = packed_width(m, q)
    s = lambda *shape: _spec(one_chip, shape)
    alphas_rhos = (s(k, nb), s(k))
    if entry == "aggregate_codes":
        fn = lambda c, a, r: bussgang.aggregate_codes(c, a, r, cb)
        args = (_spec(one_chip, (k, nb, m), jnp.uint8),) + alphas_rhos
    elif entry == "aggregate_packed":
        fn = lambda wd, a, r: bussgang.aggregate_packed(wd, a, r, cb, m)
        args = (_spec(one_chip, (k, nb, w), jnp.uint32),) + alphas_rhos
    else:
        fn = lambda wd: decode_packed(wd, q, m, cb.jnp_levels())
        args = (_spec(one_chip, (k, nb, w), jnp.uint32),)
    _assert_no_gather(fn, *args)
