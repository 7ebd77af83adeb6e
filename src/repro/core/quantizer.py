"""Lloyd-Max scalar quantizer optimized for N(0,1) (paper Sec. III-A).

The quantizer is designed *once* (numpy, at config time) for the standard
normal distribution and shared by every device/pod and the PS -- exactly the
property the paper exploits to avoid per-step signalling: the BQCS scaling
``alpha = sqrt(M)/||g||`` makes every projected entry ~ N(0,1), so a single
codebook serves all (k, b, t).

Also computes the Bussgang constants of Proposition 1:

    gamma_Q = E[Q(X) X]   (eq. 21)   -- linear gain
    psi_Q   = E[Q(X)^2]   (eq. 22)   -- second moment
    kappa_Q = (psi_Q - gamma_Q^2) / gamma_Q^2   -- normalized distortion power
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

__all__ = [
    "LloydMaxQuantizer",
    "design_lloyd_max",
    "encode",
    "decode",
    "quantize",
    "select_levels",
]

_SQRT2 = math.sqrt(2.0)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)
# Vectorized erf built ONCE at import: np.vectorize re-walks its dispatch
# machinery on every construction, and _Phi sits inside the design_lloyd_max
# fixed-point loop (hundreds of iterations per design; the VQ/dither designs
# of core/codebook.py make config-time design hotter still).
_ERF = np.vectorize(math.erf)


def _phi(x: np.ndarray) -> np.ndarray:
    """Standard normal pdf (numpy, design-time only)."""
    return _INV_SQRT_2PI * np.exp(-0.5 * np.square(x))


def _Phi(x: np.ndarray) -> np.ndarray:
    """Standard normal cdf (numpy, design-time only)."""
    return 0.5 * (1.0 + _ERF(np.asarray(x, dtype=np.float64) / _SQRT2))


@dataclasses.dataclass(frozen=True)
class LloydMaxQuantizer:
    """An optimal (MMSE) scalar quantizer for N(0,1).

    Attributes:
      bits: Q, number of bits; 2**Q output levels.
      levels: (2**Q,) reconstruction points q_i, ascending.
      thresholds: (2**Q - 1,) interior decision thresholds tau_1..tau_{2^Q-1}
        (tau_0 = -inf and tau_{2^Q} = +inf are implicit).
      gamma: Bussgang gain gamma_Q (eq. 21).
      psi: output second moment psi_Q (eq. 22).
    """

    bits: int
    levels: np.ndarray
    thresholds: np.ndarray
    gamma: float
    psi: float

    @property
    def n_levels(self) -> int:
        return 1 << self.bits

    @property
    def kappa(self) -> float:
        """kappa_Q = (psi - gamma^2)/gamma^2, the distortion-to-signal ratio
        after Bussgang normalization (appears in Thm 1 / eq. 24)."""
        return (self.psi - self.gamma**2) / (self.gamma**2)

    @property
    def distortion(self) -> float:
        """MSE of the quantizer for a unit-variance Gaussian input:
        E[(Q(X)-X)^2] = 1 - 2 gamma + psi; equals psi - gamma^2... for the
        Lloyd-Max fixed point gamma == psi so this is 1 - gamma."""
        return 1.0 - 2.0 * self.gamma + self.psi

    def jnp_levels(self, dtype=jnp.float32) -> jnp.ndarray:
        return jnp.asarray(self.levels, dtype=dtype)

    def jnp_thresholds(self, dtype=jnp.float32) -> jnp.ndarray:
        return jnp.asarray(self.thresholds, dtype=dtype)


def design_lloyd_max(bits: int, iters: int = 0, tol: float = 1e-12) -> LloydMaxQuantizer:
    """Designs the Lloyd-Max quantizer for N(0,1) via fixed-point iteration.

    Alternates the two optimality conditions until convergence:
      tau_i = (q_i + q_{i+1}) / 2                       (nearest-neighbor)
      q_i   = E[X | tau_{i-1} < X <= tau_i]             (centroid)
            = (phi(tau_{i-1}) - phi(tau_i)) / (Phi(tau_i) - Phi(tau_{i-1}))
    """
    if not (1 <= bits <= 8):
        raise ValueError(f"bits must be in [1, 8], got {bits}")
    n = 1 << bits
    if not iters:
        iters = 300 * n  # fixed-point convergence slows with level count
    # Initialize levels at Gaussian quantiles (good starting point).
    probs = (np.arange(n, dtype=np.float64) + 0.5) / n
    # Inverse normal CDF via binary search (no scipy available).
    levels = np.array([_norm_ppf(p) for p in probs], dtype=np.float64)
    prev = levels.copy()
    for _ in range(iters):
        taus = 0.5 * (levels[:-1] + levels[1:])
        lo = np.concatenate([[-np.inf], taus])
        hi = np.concatenate([taus, [np.inf]])
        num = _phi(np.where(np.isfinite(lo), lo, 0.0)) * np.isfinite(lo) - _phi(
            np.where(np.isfinite(hi), hi, 0.0)
        ) * np.isfinite(hi)
        den = _Phi(hi) - _Phi(lo)
        levels = num / np.maximum(den, 1e-300)
        if np.max(np.abs(levels - prev)) < tol:
            break
        prev = levels.copy()
    taus = 0.5 * (levels[:-1] + levels[1:])

    # Bussgang constants (eqs. 21, 22) with tau_0=-inf, tau_{2^Q}=+inf.
    lo = np.concatenate([[-np.inf], taus])
    hi = np.concatenate([taus, [np.inf]])
    phi_lo = np.where(np.isfinite(lo), _phi(np.where(np.isfinite(lo), lo, 0.0)), 0.0)
    phi_hi = np.where(np.isfinite(hi), _phi(np.where(np.isfinite(hi), hi, 0.0)), 0.0)
    gamma = float(np.sum(levels * (phi_lo - phi_hi)))
    psi = float(np.sum(np.square(levels) * (_Phi(hi) - _Phi(lo))))
    return LloydMaxQuantizer(
        bits=bits,
        levels=levels.astype(np.float64),
        thresholds=taus.astype(np.float64),
        gamma=gamma,
        psi=psi,
    )


def _norm_ppf(p: float, lo: float = -12.0, hi: float = 12.0) -> float:
    """Inverse standard normal CDF by bisection (design-time only)."""
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if _Phi(np.array(mid)) < p:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def encode(x: jnp.ndarray, quantizer: LloydMaxQuantizer) -> jnp.ndarray:
    """Maps real inputs to code indices in [0, 2**Q).  Shape-preserving."""
    taus = quantizer.jnp_thresholds(jnp.result_type(x, jnp.float32))
    # searchsorted: index i such that taus[i-1] < x <= taus[i].
    return jnp.searchsorted(taus, x, side="left").astype(jnp.uint8)


def select_levels(levels: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``levels[idx]`` as an elementwise select chain over the static level
    count L: exact for every idx in [0, L), and an index at or above L reads
    ``levels[L-1]`` (the clamp of JAX's default gather).  On a TPU a table
    gather lowers to a materialized index array and a gathered copy; the
    chain is one loop fusion with whatever consumes it (DESIGN.md
    #Codebooks)."""
    n = levels.shape[0]
    out = jnp.broadcast_to(levels[n - 1], idx.shape)
    for l in range(n - 2, -1, -1):
        out = jnp.where(idx == l, levels[l], out)
    return out


def decode(codes: jnp.ndarray, quantizer: LloydMaxQuantizer, dtype=jnp.float32) -> jnp.ndarray:
    """Maps code indices back to reconstruction levels q_i."""
    return select_levels(quantizer.jnp_levels(dtype), codes)


def quantize(x: jnp.ndarray, quantizer: LloydMaxQuantizer) -> jnp.ndarray:
    """Q(x): quantize-dequantize in one go (used by baselines/analysis)."""
    return decode(encode(x, quantizer), quantizer, dtype=x.dtype)
