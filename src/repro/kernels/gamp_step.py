"""Pallas TPU kernel: one fused EM-GAMP iteration (AWGN channel, AE path).

This is the PS-side hot loop of the paper's production strategy
(aggregate-and-estimate, Sec. IV-B): per GAMP iteration and per block we need

    phat  = ghat @ A^T - nu_p * shat          (MXU GEMM #1, contract N)
    AWGN posterior + Onsager terms            (VPU elementwise)
    rhat  = ghat + nu_r * (shat' @ A)         (MXU GEMM #2, contract M)
    Bernoulli Gaussian-mixture input channel  (VPU, L components)
    EM hyperparameter refresh                 (row reductions)

A naive XLA lowering round-trips every intermediate through HBM; the fused
kernel keeps the whole per-tile state (ghat, nu_g, shat, theta, y) in VMEM
across both GEMMs and all elementwise stages.  Scalar-variance GAMP (the
large-system iid-A approximation) is used, so no |A|^2 GEMMs are needed.

State is carried per block-row:  ghat (N), nu_g (N), shat (M), theta packed
as [lam0 | lam_1..L | mu_1..L | phi_1..L]  (1 + 3L floats).

Grid: one program per TB-row tile; A (M, N) stays resident across programs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import gm_prior

_EPS = 1e-12


def _gamp_step_kernel(
    ghat_ref, nug_ref, shat_ref, theta_ref, y_ref, nud_ref, a_ref,
    ghat_out, nug_out, shat_out, theta_out, *, n_components: int, em: bool,
):
    L = n_components
    a = a_ref[...]  # (M, N)
    ghat = ghat_ref[...]  # (TB, N)
    nu_g = nug_ref[...]  # (TB, N)
    shat = shat_ref[...]  # (TB, M)
    th = theta_ref[...]  # (TB, 1 + 3L)
    y = y_ref[...]  # (TB, M)
    nu_d = jnp.maximum(nud_ref[...], _EPS)  # (TB, 1)
    m = y.shape[1]
    n = ghat.shape[1]

    theta_parts = gm_prior.unpack_theta(th, L)

    # ---- output side -----------------------------------------------------
    nu_p = jnp.maximum(jnp.sum(nu_g, axis=1, keepdims=True) / m, _EPS)  # (TB,1)
    phat = (
        jax.lax.dot_general(
            ghat, a, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        - nu_p * shat
    )  # (TB, M)
    xpost = (phat * nu_d + y * nu_p) / (nu_p + nu_d)
    nu_x = nu_p * nu_d / (nu_p + nu_d)  # (TB, 1)
    shat_new = (xpost - phat) / nu_p  # (TB, M)
    nu_s = jnp.maximum((1.0 - nu_x / nu_p) / nu_p, _EPS)  # (TB, 1)
    nu_r = 1.0 / nu_s  # scalar-variance identity: (1/m)*sum_M nu_s = nu_s

    # ---- input side ------------------------------------------------------
    rhat = ghat + nu_r * jax.lax.dot_general(
        shat_new, a, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (TB, N)

    ghat_new, nu_g_new, posterior = gm_prior.gm_input_channel(
        rhat, nu_r, theta_parts
    )

    # ---- EM refresh (eq. 17) ----------------------------------------------
    theta_new = gm_prior.em_refresh(posterior, n) if em else th

    ghat_out[...] = ghat_new
    nug_out[...] = nu_g_new
    shat_out[...] = shat_new
    theta_out[...] = theta_new


@functools.partial(jax.jit, static_argnames=("n_components", "em", "tb", "interpret"))
def gamp_step_pallas(
    ghat: jnp.ndarray,  # (nb, N)
    nu_g: jnp.ndarray,  # (nb, N)
    shat: jnp.ndarray,  # (nb, M)
    theta: jnp.ndarray,  # (nb, 1 + 3L)
    y: jnp.ndarray,  # (nb, M)
    nu_d: jnp.ndarray,  # (nb, 1)
    a: jnp.ndarray,  # (M, N)
    n_components: int = 3,
    em: bool = True,
    tb: int = 8,  # rows per grid step; kernels.ops sizes it to VMEM
    interpret: bool = False,
):
    nb, n = ghat.shape
    m = shat.shape[1]
    tl = theta.shape[1]
    kernel = functools.partial(_gamp_step_kernel, n_components=n_components, em=em)
    row = lambda i: (i, 0)
    outs = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(nb, tb),),
        in_specs=[
            pl.BlockSpec((tb, n), row),
            pl.BlockSpec((tb, n), row),
            pl.BlockSpec((tb, m), row),
            pl.BlockSpec((tb, tl), row),
            pl.BlockSpec((tb, m), row),
            pl.BlockSpec((tb, 1), row),
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
        name="gamp_step_pallas",
        interpret=interpret,
    )(ghat, nu_g, shat, theta, y, nu_d, a)
    return outs
