"""Pallas TPU kernel: one fused Q-EM-GAMP iteration (quantized channel, EA path).

This is the PS-side hot loop of the paper's accuracy-optimal strategy
(estimate-and-aggregate, Procedure 2): each worker's code vector is inverted
*individually* over the quantized observation channel, so per GAMP iteration
and per block-row we need

    phat  = alpha * (ghat @ A^T) - nu_p * shat     (MXU GEMM #1, contract N)
    truncated-Gaussian quantized posterior          (VPU, eqs. 12-16)
    rhat  = ghat + nu_r * alpha * (shat' @ A)       (MXU GEMM #2, contract M)
    Bernoulli Gaussian-mixture input channel        (VPU, L components)
    EM hyperparameter refresh                       (row reductions, eq. 17)

The input side (GM posterior + EM) is shared with the AE kernel via
kernels/gm_prior.py; only the output channel differs: instead of the AWGN
Gaussian-product rule, the observation is the Lloyd-Max *code index* and the
posterior is a truncated-normal moment match between the decision thresholds
of the observed bin (with the same far-tail fallback as the pure-XLA
reference in core/gamp.py -- see that module's `_quantized_channel` for the
numerics rationale).

TPU adaptation notes:
  * packed-domain observation (``bits > 0``): the kernel consumes the (TB, W)
    uint32 *wire words* and unpacks the Q-bit indices in VMEM by reversing
    the fused encoder's lane-group shift-accumulate (static slices + shifts,
    DESIGN.md #Wire-format) -- the (nb, M) uint8 code tensor never exists in
    HBM on this path (DESIGN.md #Recon-engine);
  * the per-entry bin edges are fetched without a gather: the (2^Q,) lo/hi
    threshold tables sit in SMEM and the lookup is one lane-dense select per
    level against a scalar edge (a (TB, M, 2^Q) one-hot would pad its minor
    dim to 128 lanes).
  * scalar-variance GAMP (the large-system iid-A approximation, the
    production default -- EXPERIMENTS.md #Perf): nu_p and nu_r are per-row
    scalars, so no |A|^2 GEMMs; unlike the AWGN channel the quantized
    posterior variance *is* per-entry, so nu_s is a (TB, M) tensor reduced
    to the scalar nu_r by a row-sum.
  * alpha (the per-block BQCS scale, transmitted) multiplies both GEMM
    outputs; dead rows (alpha == 0) are fed alpha = 1 by the wrapper and
    zeroed by the driver, exactly like the pure-XLA path.

State per block-row: ghat (N), nu_g (N), shat (M), theta packed as
[lam0 | lam_1..L | mu_1..L | phi_1..L] (1 + 3L floats) -- all kept in VMEM
across both GEMMs and every elementwise stage.

Grid: one program per TB-row tile; A (M, N) and the threshold tables stay
resident across programs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.gamp import trunc_channel_moments  # the shared channel numerics
from repro.kernels import gm_prior
from repro.kernels.bqcs_encode_fused import unpack_lane_groups

_EPS = 1e-12


def _qgamp_step_kernel(
    ghat_ref, nug_ref, shat_ref, theta_ref, codes_ref, alpha_ref,
    lo_ref, hi_ref, a_ref,
    ghat_out, nug_out, shat_out, theta_out, *, n_components: int, em: bool,
    bits: int = 0,
):
    L = n_components
    a = a_ref[...]  # (M, N)
    ghat = ghat_ref[...]  # (TB, N)
    nu_g = nug_ref[...]  # (TB, N)
    shat = shat_ref[...]  # (TB, M)
    th = theta_ref[...]  # (TB, 1 + 3L)
    if bits:
        # Packed-domain observation: codes_ref holds the (TB, W) uint32 wire
        # words; the Q-bit indices are unpacked here, in VMEM, by reversing
        # the fused encoder's shift-accumulate over the 32 // Q lane groups
        # (DESIGN.md #Wire-format: group j = bits [j*Q, (j+1)*Q) of every
        # word = measurements [j*W, (j+1)*W)) -- static lane slices and
        # shifts only, and the uint8 code tensor never exists in HBM.
        codes = unpack_lane_groups(codes_ref[...], bits)[:, : shat.shape[1]]
        # (TB, Mp) -> (TB, M): the word-padding lanes are dropped
    else:
        codes = codes_ref[...]  # (TB, M) int32 in [0, 2^Q)
    alpha = alpha_ref[...]  # (TB, 1) f32, dead rows pre-sanitized to 1.0
    m = codes.shape[1]
    n = ghat.shape[1]
    al2 = alpha * alpha  # (TB, 1)

    theta_parts = gm_prior.unpack_theta(th, L)

    # ---- output side -----------------------------------------------------
    nu_p = jnp.maximum(al2 / m * jnp.sum(nu_g, axis=1, keepdims=True), _EPS)
    phat = (
        alpha
        * jax.lax.dot_general(
            ghat, a, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        - nu_p * shat
    )  # (TB, M)

    # Bin-edge lookup without a gather: one select per level against the
    # SMEM scalar edges -- an exact selection, so it equals lo_tau[codes].
    lo = jnp.zeros(codes.shape, jnp.float32)
    hi = jnp.zeros(codes.shape, jnp.float32)
    for lvl in range(lo_ref.shape[0]):
        hit = codes == lvl
        lo = jnp.where(hit, lo_ref[lvl], lo)  # (TB, M)
        hi = jnp.where(hit, hi_ref[lvl], hi)

    # Truncated-Gaussian moment match (eqs. 12-16) + far-tail fallback --
    # the shared core.gamp numerics, inlined into the kernel body (plain jnp).
    xpost, nu_x = trunc_channel_moments(phat, nu_p, lo, hi)

    shat_new = (xpost - phat) / nu_p  # (TB, M)
    nu_s = jnp.maximum((1.0 - nu_x / nu_p) / nu_p, _EPS)  # (TB, M), per-entry
    nu_r = 1.0 / jnp.maximum(
        al2 / m * jnp.sum(nu_s, axis=1, keepdims=True), _EPS
    )  # (TB, 1)

    # ---- input side ------------------------------------------------------
    rhat = ghat + nu_r * (
        alpha
        * jax.lax.dot_general(
            shat_new, a, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    )  # (TB, N)

    ghat_new, nu_g_new, posterior = gm_prior.gm_input_channel(
        rhat, nu_r, theta_parts
    )
    theta_new = gm_prior.em_refresh(posterior, n) if em else th

    ghat_out[...] = ghat_new
    nug_out[...] = nu_g_new
    shat_out[...] = shat_new
    theta_out[...] = theta_new


@functools.partial(
    jax.jit, static_argnames=("n_components", "em", "tb", "interpret", "bits")
)
def qgamp_step_pallas(
    ghat: jnp.ndarray,  # (nb, N)
    nu_g: jnp.ndarray,  # (nb, N)
    shat: jnp.ndarray,  # (nb, M)
    theta: jnp.ndarray,  # (nb, 1 + 3L)
    codes: jnp.ndarray,  # (nb, M) int32 -- or (nb, W) uint32 words if bits
    alpha: jnp.ndarray,  # (nb, 1) f32, strictly positive (sanitized)
    lo_tau: jnp.ndarray,  # (2^Q,)
    hi_tau: jnp.ndarray,  # (2^Q,)
    a: jnp.ndarray,  # (M, N)
    n_components: int = 3,
    em: bool = True,
    tb: int = 8,  # rows per grid step; kernels.ops sizes it to VMEM
    interpret: bool = False,
    bits: int = 0,  # 0 = unpacked int32 codes; Q = packed uint32 wire words
):
    nb, n = ghat.shape
    m = shat.shape[1]
    tl = theta.shape[1]
    obs_w = codes.shape[1]  # M unpacked, W = ceil(M / (32//Q)) packed
    kernel = functools.partial(
        _qgamp_step_kernel, n_components=n_components, em=em, bits=bits
    )
    row = lambda i: (i, 0)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    outs = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(nb, tb),),
        in_specs=[
            pl.BlockSpec((tb, n), row),
            pl.BlockSpec((tb, n), row),
            pl.BlockSpec((tb, m), row),
            pl.BlockSpec((tb, tl), row),
            pl.BlockSpec((tb, obs_w), row),
            pl.BlockSpec((tb, 1), row),
            smem,  # (2^Q,) lower bin edges (sentinel at index 0)
            smem,  # (2^Q,) upper bin edges (sentinel at index -1)
            pl.BlockSpec((m, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, n), row),
            pl.BlockSpec((tb, n), row),
            pl.BlockSpec((tb, m), row),
            pl.BlockSpec((tb, tl), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, n), jnp.float32),
            jax.ShapeDtypeStruct((nb, n), jnp.float32),
            jax.ShapeDtypeStruct((nb, m), jnp.float32),
            jax.ShapeDtypeStruct((nb, tl), jnp.float32),
        ],
        name="qgamp_step_pallas",
        interpret=interpret,
    )(ghat, nu_g, shat, theta, codes, alpha, lo_tau, hi_tau, a)
    return outs
