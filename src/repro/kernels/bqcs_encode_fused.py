"""Pallas TPU kernel: single-pass fused BQCS encoder (paper Sec. III, eqs. 7-10).

One kernel, one VMEM residency per (TB, N) tile, doing the complete
worker-side compressor *including the wire packing*:

    carry  = blocks + residual                 (error feedback, eq. 8)
    sparse = TopS(carry)                       (bisection threshold, eq. 7)
    resid  = carry - sparse                    (new error-feedback state)
    alpha  = sqrt(M) / ||sparse||              (row reduction, eq. 9)
    y      = alpha * (sparse @ A^T)            (MXU GEMM)
    code   = codebook encode                   (broadcast-compare, eq. 10)
    word   = OR_j  code[group j] << (j * Q)    (uint32 packing, the wire)

The codebook table rides in as an operand, so ONE kernel serves every
registered family (core/codebook.py):

  * scalar (Lloyd-Max / dithered-uniform): ``tab`` is the (L-1,) threshold
    vector (in SMEM) and ``code = #{tau_j < y (+ dither)}`` -- one lane-dense
    compare per threshold; the optional shared-seed dither is one extra (Mp,)
    operand added to y before the compare (absent for Lloyd-Max).
  * vq (dim d > 1): ``tab`` is the (L, d) centroid table (in SMEM, with the
    half-norms ||c_l||^2/2 beside it) and the code is the nearest centroid,
    argmax_l <y_g, c_l> - ||c_l||^2/2: d static lane slices (the j-major
    group layout of core.codebook.vq_nearest) times scalar coordinates give
    one (TB, G) score plane per level, and a running strict-greater select
    keeps the first argmax -- no gather, no transpose, no reshape.

The unfused path runs this as two kernels (block_topk, bqcs_encode) plus an
XLA pack pass, which round-trips the (nb, N) carry, sparse, and residual
arrays AND the (nb, M) int32 codes through HBM between stages.  Fusing
removes three full-gradient HBM round trips and emits the Q-bit wire payload
directly, so nothing wider than the true wire format ever leaves the kernel.

Packing layout (the canonical wire format, see DESIGN.md #Wire-format): the
Gp = W * per_word code lanes (per_word = 32 // Q, W = ceil(n_codes /
per_word); scalar: n_codes = M with A^T zero-padded to Gp columns, vq:
n_codes = M // d with the code vector zero-padded to Gp in-register) are
split into per_word contiguous *lane groups* of width W; group j is shifted
by j*Q bits and OR-accumulated into the (TB, W) word tile.  Code lane ``c``
therefore lives in word ``c % W`` at bit offset ``(c // W) * Q`` --
each group gathered by a 0/1 selection matmul (see
:func:`pack_lane_groups`).  ``core.compression.pack_codes`` implements the
identical layout for the XLA path.

Grid: one program per TB-row tile of (nblocks, N).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BISECT_ITERS = 26  # matches block_topk.py (threshold ~1e-7 of dynamic range)


def _fused_kernel(
    *refs, s: int, iters: int, m: int, bits: int, vq_d: int, has_dither: bool,
):
    g_ref, r_ref, at_ref, tab_ref = refs[:4]
    extra = refs[4:-3]  # (cn,) for vq, (dither,) for dithered scalar, or ()
    words_ref, alpha_ref, resid_ref = refs[-3:]
    cn_ref = extra[0] if vq_d > 1 else None
    dith_ref = extra[0] if has_dither else None
    carry = g_ref[...] + r_ref[...]  # (TB, N) error-feedback add

    # -- bisection top-S threshold (same math + trip count as block_topk) --
    mag = jnp.abs(carry)
    hi = jnp.max(mag, axis=1, keepdims=True)  # (TB, 1)
    lo = jnp.zeros_like(hi)

    def body(_, c):
        lo, hi = c
        mid = 0.5 * (lo + hi)
        count = jnp.sum((mag >= mid).astype(jnp.int32), axis=1, keepdims=True)
        too_many = count > s
        return jnp.where(too_many, mid, lo), jnp.where(too_many, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    keep = (mag >= hi) | (mag == jnp.max(mag, axis=1, keepdims=True))
    sparse = jnp.where(keep, carry, 0.0)
    resid_ref[...] = carry - sparse

    # -- norm/scale + MXU projection --
    sq = jnp.sum(sparse * sparse, axis=1, keepdims=True)  # (TB, 1)
    alive = sq > 1e-30
    inv_norm = jax.lax.rsqrt(jnp.where(alive, sq, 1.0))
    alpha = jnp.where(alive, jnp.sqrt(jnp.float32(m)) * inv_norm, 0.0)
    y = jax.lax.dot_general(
        sparse * alpha,
        at_ref[...],  # (N, Mp)
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (TB, Mp)

    if vq_d > 1:
        # -- nearest-centroid encode over d-lane groups (j-major layout) --
        # Centroid coordinates and half-norms are SMEM scalars, so every
        # score is a lane-dense (TB, G) plane: one running first-argmax over
        # the L levels, never a (TB, G, L) tensor indexed by lane.
        n_lev = tab_ref.shape[0]
        g = m // vq_d  # true code-lane count (Mp == M for vq)
        ys = [y[:, j * g : (j + 1) * g] for j in range(vq_d)]

        def score(lvl):
            # Accumulation order matches codebook.vq_nearest exactly: j = 0
            # carries the -||c||^2/2 term, then j = 1..d-1 -- interpret-mode
            # runs are bit-identical to the XLA oracle.
            sc = ys[0] * tab_ref[lvl, 0] - cn_ref[lvl]
            for j in range(1, vq_d):
                sc = sc + ys[j] * tab_ref[lvl, j]
            return sc

        best = score(0)
        codes = jnp.zeros(best.shape, jnp.int32)
        for lvl in range(1, n_lev):
            sc = score(lvl)
            better = sc > best  # strict: ties keep the lowest index
            best = jnp.where(better, sc, best)
            codes = jnp.where(better, lvl, codes)  # (TB, G)
        # Zero-pad the code lanes to the word grid (pure pack-side padding;
        # every measurement lane is real on the vq path).
        per_word = 32 // bits
        gp = -(-g // per_word) * per_word
        if gp != g:
            codes = jnp.concatenate(
                [codes, jnp.zeros((codes.shape[0], gp - g), jnp.int32)], axis=1
            )
    else:
        # -- threshold bucketize: code = #{tau_j < y}, one compare per SMEM
        # threshold scalar (exact integer counts) --
        if has_dither:
            # shared-seed subtractive dither: the encoder quantizes y + u
            # (padded lanes carry u = 0 and are masked below anyway)
            y = y + dith_ref[...][None, :]
        codes = jnp.zeros(y.shape, jnp.int32)
        for j in range(tab_ref.shape[0]):
            codes = codes + (y > tab_ref[j]).astype(jnp.int32)  # (TB, Mp)
        mp = codes.shape[1]
        if mp != m:
            # Zero the measurement lanes added by word-padding A^T so the
            # padded word bits match pack_codes' zero fill bit-exactly.
            lane = jax.lax.broadcasted_iota(jnp.int32, codes.shape, 1)
            codes = jnp.where(lane < m, codes, 0)

    words_ref[...] = pack_lane_groups(codes, bits)
    alpha_ref[...] = alpha


def pack_lane_groups(codes, bits: int):
    """(TB, Gp) int32 codes -> (TB, W) uint32 words, W = Gp // (32 // Q):
    the shift-accumulate over the 32 // Q contiguous lane groups of width W
    (code lane c -> word c % W, bit offset (c // W) * Q).

    Each group is moved to lanes [0, W) by an exact 0/1 selection matmul on
    the MXU, not by a lane slice at offset j * W: on a v5e, Mosaic's
    shift-accumulate of unaligned (TB, W) uint32 lane slices returned wrong
    words at TB >= 128 (right at TB = 8), while the selection is exact for
    any code below 2^24."""
    per_word = 32 // bits
    gp = codes.shape[1]
    w = gp // per_word
    codes = codes.astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (gp, w), 0)
    word = jax.lax.broadcasted_iota(jnp.int32, (gp, w), 1)
    words = None
    for j in range(per_word):
        group = jax.lax.dot_general(
            codes, (lane == word + j * w).astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32).astype(jnp.uint32)  # (TB, W): lanes j*W + [0, W)
        words = group if words is None else words | (group << jnp.uint32(j * bits))
    return words


def unpack_lane_groups(words, bits: int):
    """Inverse of :func:`pack_lane_groups`: (TB, W) uint32 words -> (TB, Gp)
    int32 codes, Gp = W * (32 // Q)."""
    mask = jnp.uint32((1 << bits) - 1)
    return jnp.concatenate(
        [
            ((words >> jnp.uint32(j * bits)) & mask).astype(jnp.int32)
            for j in range(32 // bits)
        ],
        axis=1,
    )


@functools.partial(
    jax.jit,
    static_argnames=("s", "m", "bits", "vq_d", "tb", "iters", "interpret"),
)
def bqcs_encode_fused_pallas(
    blocks: jnp.ndarray,  # (nb, N) f32
    residual: jnp.ndarray,  # (nb, N) f32 error-feedback state
    a_t: jnp.ndarray,  # (N, Mp) f32; scalar: Mp = W * (32 // Q) zero-padded
    tab: jnp.ndarray,  # (L-1,) thresholds (scalar) or (L, d) centroids (vq)
    s: int,
    m: int,  # true measurement count M <= Mp
    bits: int,  # Q: index width on the wire
    vq_d: int = 1,  # codebook dim; > 1 selects nearest-centroid encode
    dither: jnp.ndarray | None = None,  # (Mp,) per-lane dither or None
    tb: int = 8,  # rows per grid step; kernels.ops sizes it to VMEM
    iters: int = BISECT_ITERS,
    interpret: bool = False,
):
    nb, n = blocks.shape
    mp = a_t.shape[1]
    per_word = 32 // bits
    if vq_d > 1:
        assert mp == m and m % vq_d == 0, (mp, m, vq_d)
        w = -(-(m // vq_d) // per_word)
    else:
        assert mp % per_word == 0, (mp, per_word)
        w = mp // per_word
    has_dither = dither is not None
    kernel = functools.partial(
        _fused_kernel, s=s, iters=iters, m=m, bits=bits, vq_d=vq_d,
        has_dither=has_dither,
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        pl.BlockSpec((tb, n), lambda i: (i, 0)),  # gradient tile
        pl.BlockSpec((tb, n), lambda i: (i, 0)),  # residual tile
        pl.BlockSpec((n, mp), lambda i: (0, 0)),  # A^T, resident
        smem,  # codebook table: thresholds (L-1,) or centroids (L, d)
    ]
    operands = [blocks, residual, a_t, tab]
    if vq_d > 1:
        # centroid half-norms, the same expression as codebook.vq_nearest
        in_specs.append(smem)
        operands.append(0.5 * jnp.sum(tab * tab, axis=1))
    if has_dither:
        in_specs.append(pl.BlockSpec((mp,), lambda i: (0,)))
        operands.append(dither)
    words, alpha, resid = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(nb, tb),),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((tb, w), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
            pl.BlockSpec((tb, n), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, w), jnp.uint32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, n), jnp.float32),
        ],
        name="bqcs_encode_fused_pallas",
        interpret=interpret,
    )(*operands)
    return words, alpha[:, 0], resid
