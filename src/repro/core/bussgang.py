"""Bussgang linearization and aggregate-and-estimate combining (Sec. IV-B).

Proposition 1: for a codebook designed for the standard normal (MMSE
condition) and x ~ N(0, I),

    Q(x) = gamma_Q * x + d,   E[d] = 0,  cov(d) = (psi_Q - gamma_Q^2) I,
    d uncorrelated with x.

Therefore the weighted sum of *dequantized* codes

    q_tilde = sum_k rho_k / (gamma_Q alpha_k) * q_k
            = A (sum_k rho_k g_k) + d_tilde                      (eq. 23)

is a *linear* AWGN observation of the aggregated gradient with

    nu = (psi_Q - gamma_Q^2)/gamma_Q^2 * sum_k (rho_k/alpha_k)^2  (eq. 24).

Everything here is generic over the codebook family (core/codebook.py): the
paper proves Prop. 1 for Lloyd-Max, but the derivation only needs the
codebook's (gamma, psi) moments, which every family computes at design time
-- for the d-dim vq codebook the per-entry moments follow from the isotropy
of N(0, I_d) (gamma = E[<Q(x), x>]/d).  This generic linearization is also
what the EA decoder falls back to for codebooks without scalar cells.

The linearity is what makes the cross-pod collective a plain sum: on hardware,
`q_tilde` is produced by a `psum` over the pod axis of locally-scaled
dequantized codes (see runtime/collectives.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.codebook import as_codebook

__all__ = [
    "bussgang_weight",
    "aggregate_codes",
    "aggregate_packed",
    "effective_noise_var",
    "signal_energy",
]


def bussgang_weight(rho: jnp.ndarray, alpha: jnp.ndarray, quantizer):
    """Per-(worker, block) combining weight rho_k / (gamma_Q alpha_{k,b}).

    ``quantizer``: any Codebook (or legacy LloydMaxQuantizer).
    alpha == 0 (empty block) contributes weight 0.
    """
    safe = jnp.where(alpha > 0, alpha, 1.0)
    w = rho / (as_codebook(quantizer).gamma * safe)
    return jnp.where(alpha > 0, w, 0.0)


def aggregate_codes(
    codes: jnp.ndarray,  # (K, nb, n_codes) uint8 codes from K workers
    alphas: jnp.ndarray,  # (K, nb)
    rhos: jnp.ndarray,  # (K,)
    quantizer,  # Codebook or legacy LloydMaxQuantizer
) -> jnp.ndarray:
    """q_tilde (nb, M): the Bussgang-weighted aggregate of eq. 23 (under
    the ``dequantize`` scope)."""
    cb = as_codebook(quantizer)
    with jax.named_scope("dequantize"):
        deq = cb.decode(codes)  # (K, nb, M)
        w = bussgang_weight(rhos[:, None], alphas, cb)  # (K, nb)
        return jnp.sum(w[..., None] * deq, axis=0)


def aggregate_packed(
    words: jnp.ndarray,  # (K, nb, W) uint32 packed wire words from K workers
    alphas: jnp.ndarray,  # (K, nb)
    rhos: jnp.ndarray,  # (K,)
    quantizer,  # Codebook or legacy LloydMaxQuantizer
    m: int,
) -> jnp.ndarray:
    """q_tilde (nb, M) straight from the packed wire payload: the scalar
    families index reconstruction levels through the shift/masked lane
    groups (compression.decode_packed) so the (K, nb, M) uint8 code view
    never materializes at the PS boundary; vq unpacks indices and reads
    centroids.  The index width is the codebook's own ``bits``.
    Numerically identical to ``aggregate_codes(unpack_codes(words), ...)``
    (and, like it, under the ``dequantize`` scope)."""
    cb = as_codebook(quantizer)
    with jax.named_scope("dequantize"):
        deq = cb.decode_packed(words, m)  # (K, nb, M)
        w = bussgang_weight(rhos[:, None], alphas, cb)  # (K, nb)
        return jnp.sum(w[..., None] * deq, axis=0)


def effective_noise_var(
    alphas: jnp.ndarray,  # (K, nb)
    rhos: jnp.ndarray,  # (K,)
    quantizer,  # Codebook or legacy LloydMaxQuantizer
) -> jnp.ndarray:
    """nu_{g,b} (nb,): AWGN variance of the effective distortion (eq. 24)."""
    safe = jnp.where(alphas > 0, alphas, 1.0)
    terms = jnp.where(alphas > 0, (rhos[:, None] / safe) ** 2, 0.0)
    return as_codebook(quantizer).kappa * jnp.sum(terms, axis=0)


def signal_energy(alphas: jnp.ndarray, rhos: jnp.ndarray, m: int, n: int) -> jnp.ndarray:
    """Per-entry energy of the aggregated block, used for GAMP init:
    E[(g_sum)_n^2] ~= sum_k rho_k^2 ||g_k||^2 / N = sum_k rho_k^2 M/alpha_k^2 / N.
    (Cross terms vanish in expectation for independent worker gradients.)
    """
    safe = jnp.where(alphas > 0, alphas, 1.0)
    terms = jnp.where(alphas > 0, (rhos[:, None] ** 2) * m / jnp.square(safe), 0.0)
    return jnp.sum(terms, axis=0) / n
