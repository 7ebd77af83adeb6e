"""Pluggable quantizer codebooks (the paper's Sec. III-A lifted to an axis).

The paper fixes ONE codebook -- the Lloyd-Max scalar quantizer designed for
N(0,1) -- and exploits that the BQCS scaling ``alpha = sqrt(M)/||g||`` makes
every projected entry ~ N(0,1), so a single config-time design serves every
(worker, block, round) with zero signalling.  That property is a property of
the *scaling*, not of Lloyd-Max: any codebook designed once for the standard
normal inherits it.  This module makes the codebook a protocol axis:

  * ``lloyd_max``         -- the paper's quantizer (core/quantizer.py) behind
                             the interface with zero behavior change: same
                             searchsorted encode, same thresholds, same
                             Bussgang constants, bit-identical wire.
  * ``dithered_uniform``  -- shared-seed subtractive-dither uniform quantizer
                             (the QCS-Dither [23] family promoted from a
                             baseline into the real BQCS wire path).  The
                             per-lane dither is a protocol constant derived
                             from the config seed, so -- unlike the paper's
                             criticism of QCS-Dither -- nothing extra crosses
                             the wire.
  * ``vq``                -- FedVQCS-style (arXiv:2204.07692) d-dimensional
                             vector codebook: k-means on N(0,1)^d at config
                             time; one code indexes d measurements, so the
                             wire drops to ceil(log2 L)/d bits/measurement.

Every implementation duck-types the ``LloydMaxQuantizer`` surface the rest of
the repo already consumes (``bits``/``gamma``/``psi``/``kappa``/
``jnp_levels``/``jnp_thresholds``) and adds the generic codec surface
(``encode``/``decode``/``decode_packed``/``quantize``/``n_codes``) plus the
channel hooks the PS needs: scalar families expose cell boundaries (so the
exact truncated-Gaussian Q-EM-GAMP channel of eqs. 12-16 applies, with the
dither as a per-lane edge shift); ``vq`` reports
``supports_exact_channel = False`` and the EA solver falls back to the
Bussgang-linearized AWGN channel, which ``bussgang.py`` already derives
generically from (gamma, psi).

Wire accounting: a codebook packs ``n_codes(M) = M / dim`` indices of width
``bits = ceil(log2 n_levels)`` each -- ``core.compression.pack_codes`` is
already generic over both, so the packed layout is one definition for every
family.

Designs are numpy at config time (like design_lloyd_max); the jnp tables they
produce are what crosses into jit.  New families register via
:func:`register_codebook_family` and become available to every layer
(codec, kernels, GAMP channel, collectives, fed engine) without touching any
of them.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.quantizer import (
    LloydMaxQuantizer,
    _phi,
    design_lloyd_max,
    select_levels,
)

__all__ = [
    "Codebook",
    "ScalarCodebook",
    "VectorCodebook",
    "make_codebook",
    "register_codebook_family",
    "as_codebook",
    "vq_nearest",
    "design_dithered_uniform",
    "design_vq",
    "index_bits",
]

CODEBOOK_FAMILIES: Dict[str, Callable] = {}


def register_codebook_family(name: str, builder: Callable) -> None:
    """Registers ``builder(cfg) -> Codebook`` under ``cfg.codebook == name``.
    This is the plugin point: a trained/adaptive/entropy-coded codebook lands
    as one builder function, and every layer downstream picks it up."""
    CODEBOOK_FAMILIES[name] = builder


def index_bits(n_levels: int) -> int:
    """Wire width of one code index: ceil(log2 n_levels), >= 1."""
    return max(1, (n_levels - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class Codebook:
    """Common protocol surface; see module docstring for the contract.

    Attributes:
      family: registry name ("lloyd_max" / "dithered_uniform" / "vq" / ...).
      bits: wire width of one code index, ceil(log2 n_levels).
      dim: measurements per code (1 = scalar; d for vector quantization).
      n_levels: codebook size L (NOT necessarily 2**bits).
      gamma: Bussgang gain E[<Q(x), x>]/dim for x ~ N(0, I_dim)  (eq. 21).
      psi: output second moment E[||Q(x)||^2]/dim                (eq. 22).
    """

    family: str
    bits: int
    dim: int
    n_levels: int
    gamma: float
    psi: float

    @property
    def kappa(self) -> float:
        """(psi - gamma^2)/gamma^2: normalized distortion power (Thm 1)."""
        return (self.psi - self.gamma**2) / (self.gamma**2)

    @property
    def bits_per_entry(self) -> float:
        """Index bits per *measurement* on the wire (excl. word slack)."""
        return self.bits / self.dim

    @property
    def supports_exact_channel(self) -> bool:
        """True iff the EA decoder can run the exact truncated-posterior
        quantized channel (scalar cells); False -> Bussgang AWGN fallback."""
        return self.dim == 1

    def n_codes(self, m: int) -> int:
        """Code lanes for m measurements (m must divide by dim)."""
        if m % self.dim:
            raise ValueError(
                f"codebook dim {self.dim} must divide the measurement count {m}"
            )
        return m // self.dim

    # subclasses implement: encode / decode / decode_packed
    def quantize(self, x: jnp.ndarray) -> jnp.ndarray:
        """Q(x): quantize-dequantize (used by QIHT and analysis)."""
        return self.decode(self.encode(x), x.shape[-1])


# ---------------------------------------------------------------------------
# Scalar families (dim = 1): threshold encode, level-table decode.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScalarCodebook(Codebook):
    """Scalar codebook: L levels, L-1 interior decision thresholds, and an
    optional per-measurement-lane subtractive dither (protocol constant).

    Encode: ``searchsorted(thresholds, y + dither)`` -- identical to the
    pre-refactor quantizer.encode when dither is None.
    Decode: ``levels[code] - dither``, the lookup through
    ``quantizer.select_levels`` (a select chain, not a table gather).
    """

    levels: np.ndarray = None  # (L,) ascending reconstruction points
    thresholds: np.ndarray = None  # (L - 1,) interior decision thresholds
    dither: Optional[np.ndarray] = None  # (m,) per-lane dither or None

    def jnp_levels(self, dtype=jnp.float32) -> jnp.ndarray:
        return jnp.asarray(self.levels, dtype=dtype)

    def jnp_thresholds(self, dtype=jnp.float32) -> jnp.ndarray:
        return jnp.asarray(self.thresholds, dtype=dtype)

    def jnp_dither(self, dtype=jnp.float32) -> Optional[jnp.ndarray]:
        if self.dither is None:
            return None
        return jnp.asarray(self.dither, dtype=dtype)

    def encode(self, y: jnp.ndarray) -> jnp.ndarray:
        taus = self.jnp_thresholds(jnp.result_type(y, jnp.float32))
        if self.dither is not None:
            y = y + self.jnp_dither(taus.dtype)
        return jnp.searchsorted(taus, y, side="left").astype(jnp.uint8)

    def decode(self, codes: jnp.ndarray, m: Optional[int] = None, dtype=jnp.float32):
        deq = select_levels(self.jnp_levels(dtype), codes)
        if self.dither is not None:
            deq = deq - self.jnp_dither(dtype)
        return deq if m is None else deq[..., :m]

    def decode_packed(self, words: jnp.ndarray, m: int, dtype=jnp.float32):
        """Dequantize straight from packed wire words (the lane-group level
        lookup of compression.decode_packed); the index view never
        materializes."""
        from repro.core.compression import decode_packed  # deferred: layering

        deq = decode_packed(words, self.bits, m, self.jnp_levels(dtype))
        if self.dither is not None:
            deq = deq - self.jnp_dither(dtype)[:m]
        return deq


# ---------------------------------------------------------------------------
# Vector family (dim = d > 1): nearest-centroid encode, table decode.
# ---------------------------------------------------------------------------


def vq_nearest(y: jnp.ndarray, centroids: jnp.ndarray) -> jnp.ndarray:
    """Nearest-centroid indices for grouped measurements.

    ``y`` is (..., M) with M = d * G in the **j-major lane layout**:
    measurement lane ``j*G + g`` is dimension j of group g (G = M // d) --
    contiguous per-dimension lane slices, the same static-slice idiom as the
    packed wire's lane groups, so the fused encoder kernel computes the
    identical scores with ``y[:, j*G:(j+1)*G]`` slices and no transpose.

    Scoring: argmax_l <y_g, c_l> - ||c_l||^2 / 2 (equivalent to min
    distance); ties break to the LOWEST index, and the accumulation order
    (j = 0 carries the -||c||^2/2 term, then j = 1..d-1) is the single
    definition both the XLA path and the kernel follow, so interpret-mode
    kernel runs are bit-identical to this function.
    """
    n_lev, d = centroids.shape
    g = y.shape[-1] // d
    y3 = y.reshape(y.shape[:-1] + (d, g))
    cn = 0.5 * jnp.sum(centroids * centroids, axis=1)  # (L,)
    sc = y3[..., 0, :, None] * centroids[:, 0] - cn  # (..., G, L)
    for j in range(1, d):
        sc = sc + y3[..., j, :, None] * centroids[:, j]
    mx = jnp.max(sc, axis=-1, keepdims=True)
    lvl = jnp.arange(n_lev, dtype=jnp.int32)
    return jnp.min(jnp.where(sc == mx, lvl, n_lev), axis=-1)


@dataclasses.dataclass(frozen=True)
class VectorCodebook(Codebook):
    """FedVQCS-style d-dim vector codebook over N(0,1)^d.

    One code indexes ``dim`` measurements (j-major lane layout, see
    :func:`vq_nearest`), so the wire carries ``bits/dim`` bits per
    measurement.  No exact scalar-cell channel exists (the cells are
    d-dimensional Voronoi regions); the EA decoder falls back to the
    Bussgang-linearized AWGN channel built from (gamma, psi).
    """

    centroids: np.ndarray = None  # (L, d)

    def jnp_centroids(self, dtype=jnp.float32) -> jnp.ndarray:
        return jnp.asarray(self.centroids, dtype=dtype)

    def encode(self, y: jnp.ndarray) -> jnp.ndarray:
        codes = vq_nearest(y, self.jnp_centroids(jnp.result_type(y, jnp.float32)))
        return codes.astype(jnp.uint8 if self.n_levels <= 256 else jnp.int32)

    def decode(self, codes: jnp.ndarray, m: Optional[int] = None, dtype=jnp.float32):
        c = self.jnp_centroids(dtype)
        deq = c[codes.astype(jnp.int32)]  # (..., G, d)
        deq = jnp.swapaxes(deq, -1, -2)  # (..., d, G): j-major lane layout
        deq = deq.reshape(codes.shape[:-1] + (codes.shape[-1] * self.dim,))
        return deq if m is None else deq[..., :m]

    def decode_packed(self, words: jnp.ndarray, m: int, dtype=jnp.float32):
        from repro.core.compression import unpack_codes  # deferred: layering

        return self.decode(unpack_codes(words, self.bits, self.n_codes(m)), m, dtype)


# ---------------------------------------------------------------------------
# Designs (numpy, config time -- shared protocol constants, like
# design_lloyd_max).
# ---------------------------------------------------------------------------


def _as_lloyd_max_codebook(q: LloydMaxQuantizer) -> ScalarCodebook:
    return ScalarCodebook(
        family="lloyd_max",
        bits=q.bits,
        dim=1,
        n_levels=q.n_levels,
        gamma=q.gamma,
        psi=q.psi,
        levels=q.levels,
        thresholds=q.thresholds,
        dither=None,
    )


def design_dithered_uniform(
    bits: int, m: int, seed: int, clip: float = 4.0
) -> ScalarCodebook:
    """Uniform mid-rise quantizer over [-clip, clip] with shared-seed
    subtractive dither u ~ Unif(-delta/2, delta/2) per measurement lane.

    The dither decorrelates the quantization error from the signal (the
    classical dithered-quantization property QCS-Dither [23] relies on);
    regenerating it from the protocol seed on both sides removes the
    signalling overhead the paper criticizes.  Bussgang constants are
    computed by numerical integration over (x ~ N(0,1), u) at design time.
    """
    if not (1 <= bits <= 8):
        raise ValueError(f"bits must be in [1, 8], got {bits}")
    n = 1 << bits
    delta = 2.0 * clip / n
    levels = -clip + delta * (np.arange(n, dtype=np.float64) + 0.5)
    thresholds = -clip + delta * np.arange(1, n, dtype=np.float64)

    # gamma = E[(q(x+u) - u) x], psi = E[(q(x+u) - u)^2]: trapezoid over a
    # fine x-grid weighted by the standard normal pdf, averaged over a
    # midpoint u-grid (exact in the u-average limit; the grids are design-
    # time numpy and the constants are protocol-stable).
    xs = np.linspace(-9.0, 9.0, 6001)
    wx = _phi(xs)
    wx /= np.sum(wx)
    us = (np.arange(33, dtype=np.float64) + 0.5) / 33.0 * delta - 0.5 * delta
    v = xs[:, None] + us[None, :]
    idx = np.clip(np.floor((v + clip) / delta), 0, n - 1).astype(np.int64)
    qxu = levels[idx] - us[None, :]
    q_mean = np.mean(qxu, axis=1)
    gamma = float(np.sum(wx * xs * q_mean))
    psi = float(np.sum(wx * np.mean(np.square(qxu), axis=1)))

    rng = np.random.default_rng((int(seed), 0xD17E))
    dither = rng.uniform(-0.5 * delta, 0.5 * delta, size=m)
    return ScalarCodebook(
        family="dithered_uniform",
        bits=bits,
        dim=1,
        n_levels=n,
        gamma=gamma,
        psi=psi,
        levels=levels,
        thresholds=thresholds,
        dither=dither.astype(np.float64),
    )


def design_vq(
    n_levels: int,
    dim: int,
    seed: int,
    n_samples: int = 1 << 16,
    iters: int = 60,
) -> VectorCodebook:
    """k-means (Lloyd's algorithm) codebook for N(0, I_dim), deterministic in
    the seed.  Empty cells reseed to the sample farthest from its centroid.
    Bussgang constants come from a fresh held-out sample (in-sample moments
    would be optimistically biased toward gamma == psi)."""
    if dim < 2:
        raise ValueError(f"vq dim must be >= 2 (use a scalar family for d=1), got {dim}")
    if not (2 <= n_levels <= 256):
        raise ValueError(f"vq levels must be in [2, 256], got {n_levels}")
    rng = np.random.default_rng((int(seed), 0x7ECB))
    x = rng.standard_normal((n_samples, dim))
    c = x[rng.choice(n_samples, n_levels, replace=False)].copy()
    for _ in range(iters):
        d2 = np.sum(np.square(x[:, None, :] - c[None, :, :]), axis=-1)  # (S, L)
        assign = np.argmin(d2, axis=1)
        counts = np.bincount(assign, minlength=n_levels)
        for j in range(dim):
            sums = np.bincount(assign, weights=x[:, j], minlength=n_levels)
            c[:, j] = np.where(counts > 0, sums / np.maximum(counts, 1), c[:, j])
        if (counts == 0).any():
            worst = np.argsort(-d2[np.arange(n_samples), assign])
            for i, l in enumerate(np.flatnonzero(counts == 0)):
                c[l] = x[worst[i]]
    # Held-out Bussgang moments.
    xh = rng.standard_normal((n_samples, dim))
    d2 = np.sum(np.square(xh[:, None, :] - c[None, :, :]), axis=-1)
    q = c[np.argmin(d2, axis=1)]
    gamma = float(np.mean(np.sum(q * xh, axis=1)) / dim)
    psi = float(np.mean(np.sum(np.square(q), axis=1)) / dim)
    return VectorCodebook(
        family="vq",
        bits=index_bits(n_levels),
        dim=dim,
        n_levels=n_levels,
        gamma=gamma,
        psi=psi,
        centroids=c,
    )


# ---------------------------------------------------------------------------
# Registry + config entry point.
# ---------------------------------------------------------------------------


def _build_lloyd_max(cfg) -> ScalarCodebook:
    return _as_lloyd_max_codebook(design_lloyd_max(cfg.bits))


def _build_dithered_uniform(cfg) -> ScalarCodebook:
    return design_dithered_uniform(cfg.bits, cfg.m, cfg.seed)


def _build_vq(cfg) -> VectorCodebook:
    n_levels = cfg.vq_levels or (1 << cfg.bits)
    if cfg.m % cfg.vq_dim:
        raise ValueError(
            f"vq_dim={cfg.vq_dim} must divide M={cfg.m} "
            f"(block_size // reduction_ratio)"
        )
    return design_vq(n_levels, cfg.vq_dim, cfg.seed)


register_codebook_family("lloyd_max", _build_lloyd_max)
register_codebook_family("dithered_uniform", _build_dithered_uniform)
register_codebook_family("vq", _build_vq)


def make_codebook(cfg) -> Codebook:
    """Builds the protocol codebook named by ``cfg.codebook`` (FedQCSConfig).
    Deterministic in the config, so every pod and the PS derive the same
    tables independently -- no table ever crosses the wire."""
    try:
        builder = CODEBOOK_FAMILIES[cfg.codebook]
    except KeyError:
        raise ValueError(
            f"unknown codebook {cfg.codebook!r} "
            f"(registered: {sorted(CODEBOOK_FAMILIES)})"
        ) from None
    return builder(cfg)


def as_codebook(obj) -> Codebook:
    """Adapts legacy LloydMaxQuantizer instances (tests, benchmarks, external
    callers) to the Codebook surface; Codebooks pass through."""
    if isinstance(obj, Codebook):
        return obj
    if isinstance(obj, LloydMaxQuantizer):
        return _as_lloyd_max_codebook(obj)
    raise TypeError(f"not a codebook or quantizer: {type(obj)!r}")
