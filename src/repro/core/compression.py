"""BQCS end-to-end gradient codec over pytrees (paper Sec. III).

Pipeline per step, per worker/pod:

    grads (pytree) --flatten+pad--> (nblocks, N) blocks
      + residual (error feedback, eq. 8)
      -> block top-S sparsify (residual out, eq. 7)
      -> project with shared A, scale alpha = sqrt(M)/||.||  (eq. 9)
      -> codebook encode (eq. 10; Lloyd-Max / dithered-uniform / vq, see
         core/codebook.py -- the config's ``codebook`` axis)
      -> bit-pack codes into uint32 words (the wire payload)

Wire cost per step per worker: nblocks * (W*32 bits + 32 bits for alpha),
  W = ceil(n_codes / (32//Q)) packed words over n_codes = M / codebook.dim
  index lanes of width Q = ceil(log2 levels) -- ~= Q/(dim*R) bits per
  gradient entry (Sec. III-B), exactly M*Q bits for the scalar families
  whenever Q divides 32 (CompressedGradient.wire_bits derives this from the
  actual word count).

The codec is stateless except for the error-feedback residual, which the
caller owns (it lives in the TrainState so it is checkpointed).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sensing, sparsify
from repro.core.codebook import Codebook, index_bits, make_codebook
from repro.core.layout import GradientLayout
from repro.core.quantizer import select_levels

__all__ = [
    "FedQCSConfig",
    "BQCSCodec",
    "CompressedGradient",
    "GradientLayout",
    "flatten_to_blocks",
    "blocks_to_tree",
    "pack_codes",
    "unpack_codes",
    "decode_packed",
    "packed_width",
]


@dataclasses.dataclass(frozen=True)
class FedQCSConfig:
    """Protocol parameters shared by every worker and the PS."""

    block_size: int = 1024  # N
    reduction_ratio: int = 4  # R = N / M
    bits: int = 2  # Q: index bits per code (scalar: per measurement)
    # Quantizer codebook family (core/codebook.py): "lloyd_max" (the paper's
    # Sec. III-A scalar quantizer), "dithered_uniform" (shared-seed dither),
    # or "vq" (FedVQCS-style vq_dim-dimensional vector codebook, one Q-bit
    # code per vq_dim measurements -> Q/vq_dim bits per measurement).
    codebook: str = "lloyd_max"
    vq_dim: int = 2  # d (vq only); must divide M
    vq_levels: int = 0  # vq codebook size L; 0 = 2**bits
    s_ratio: float = 0.1  # S = floor(s_ratio * N) kept per block
    gamp_iters: int = 25
    gamp_components: int = 3  # L
    gamp_variance_mode: str = "exact"
    # "topk" = exact lax.top_k; "bisect" = fixed-iteration threshold search
    # (compares/reductions only).  Use "bisect" in distributed steps: XLA
    # partitions top_k's sort by REPLICATING the operand across the mesh
    # (measured: 30.5 GB/step cross-pod for qwen2-7b -- EXPERIMENTS.md #Perf
    # iteration 3c), while bisect partitions trivially.
    sparsifier: str = "topk"
    seed: int = 1234  # sensing-matrix seed (protocol constant)
    use_kernels: bool = False  # route hot paths through Pallas kernels
    wire_mode: str = "gather_codes"  # or "psum_dequant" (see DESIGN.md)
    # PS reconstruction strategy inside the distributed collectives:
    # "ae" (aggregate-and-estimate, Bussgang combine then one GAMP) or
    # "ea" (estimate-and-aggregate, per-worker Q-EM-GAMP then rho-sum).
    # "ea" needs the per-worker codes, i.e. wire_mode="gather_codes".
    recon_mode: str = "ae"
    # PS-side EA decode chunking (DESIGN.md #Recon-engine): the K*nb block
    # problems stream through a lax.scan in chunks of this many rows, so the
    # GAMP state (and, on the XLA path, the unpacked code view) never
    # materializes for more than one chunk at a time.  0 = monolithic batch.
    recon_chunk: int = 0

    def validate(self) -> "FedQCSConfig":
        """Raises ValueError on incoherent knob combinations, with the fix
        named in the message.  Called by ``BQCSCodec`` (so ``make_codec``
        rejects a bad protocol at construction, not rounds later inside a
        collective); returns self so it chains.  Note R need not divide N --
        M = floor(N / R) is the paper's own Sec. VI blocking (1591 // 3)."""
        if self.block_size < 1 or self.reduction_ratio < 1:
            raise ValueError(
                f"block_size={self.block_size} and reduction_ratio="
                f"{self.reduction_ratio} must both be >= 1"
            )
        if self.m < 1:
            raise ValueError(
                f"reduction_ratio={self.reduction_ratio} leaves no measurements "
                f"(M = {self.block_size} // {self.reduction_ratio} = 0); use "
                f"reduction_ratio <= block_size"
            )
        if not (1 <= self.bits <= 8):
            raise ValueError(f"bits must be in [1, 8], got {self.bits}")
        if not (0.0 < self.s_ratio <= 1.0):
            raise ValueError(f"s_ratio must be in (0, 1], got {self.s_ratio}")
        if self.wire_mode not in ("gather_codes", "psum_dequant"):
            raise ValueError(
                f"unknown wire_mode {self.wire_mode!r} "
                "(choose 'gather_codes' or 'psum_dequant')"
            )
        if self.recon_mode not in ("ae", "ea"):
            raise ValueError(
                f"unknown recon_mode {self.recon_mode!r} (choose 'ae' or 'ea')"
            )
        if self.recon_mode == "ea" and self.wire_mode != "gather_codes":
            raise ValueError(
                "recon_mode='ea' needs the per-worker codes on the PS side, "
                "i.e. wire_mode='gather_codes' (see DESIGN.md); "
                f"got wire_mode={self.wire_mode!r}"
            )
        if self.recon_chunk < 0:
            raise ValueError(f"recon_chunk must be >= 0, got {self.recon_chunk}")
        if self.gamp_variance_mode not in ("exact", "scalar"):
            raise ValueError(
                f"unknown gamp_variance_mode {self.gamp_variance_mode!r} "
                "(choose 'exact' or 'scalar')"
            )
        if self.codebook == "vq" and self.m % self.vq_dim:
            raise ValueError(
                f"vq_dim={self.vq_dim} must divide M={self.m} "
                f"(= block_size // reduction_ratio); pick a vq_dim that "
                f"divides {self.m} or adjust the blocking"
            )
        return self

    @property
    def m(self) -> int:
        return self.block_size // self.reduction_ratio

    @property
    def s(self) -> int:
        return max(1, int(self.s_ratio * self.block_size))

    @property
    def bits_per_entry(self) -> float:
        """Wire index bits per gradient entry (excl. the negligible alphas):
        Q/R for the scalar families, ceil(log2 L)/(d*R) for vq."""
        if self.codebook == "vq":
            width = index_bits(self.vq_levels or (1 << self.bits))
            return width / (self.vq_dim * self.reduction_ratio)
        return self.bits / self.reduction_ratio


@dataclasses.dataclass
class CompressedGradient:
    """The wire payload of one worker for one step.

    ``codes`` is *packed*: uint32 words holding Q-bit codebook indices in
    the canonical lane-group layout (see :func:`pack_codes`), not the uint8
    index view -- what crosses the wire is what this object carries.  The
    words cover ``n_codes = M / codebook.dim`` index lanes of width
    ``bits = ceil(log2 levels)`` each (scalar families: n_codes == M).
    """

    codes: jnp.ndarray  # (nblocks, W) uint32 words, W = packed_width(n_codes, Q)
    alpha: jnp.ndarray  # (nblocks,) f32 scales
    nbar: int  # original flat length (for unpadding)
    m: int  # measurements per block
    bits: int  # Q: index width on the wire

    def wire_bits(self) -> int:
        """Actual bits on the wire, derived from the true packed word count:
        nb * (W * 32 + 32 for alpha).  Counting ``M * Q`` instead would be
        wrong whenever Q does not divide 32 -- Q=3 packs 10 codes per word,
        so each word carries 2 slack bits that still cross the wire."""
        nb, w = self.codes.shape[:2]
        return nb * (w * 32 + 32)


# ---------------------------------------------------------------------------
# pytree <-> blocks plumbing
# ---------------------------------------------------------------------------


def flatten_to_blocks(tree: Any, n: int, row_multiple: int = 1) -> Tuple[jnp.ndarray, Any, int]:
    """Concatenates all leaves into one vector, zero-pads to a multiple of N,
    reshapes to (nblocks, N).  ``row_multiple`` additionally pads nblocks up
    to a multiple (so the (data, model) sharding of the block view is even).
    Returns (blocks, spec, nbar) where the spec is now a monolithic
    :class:`~repro.core.layout.GradientLayout` (bit-identical block output;
    geometry -- sizes, offsets, nbar -- computed in Python ints, see
    core/layout.py for the int32 guard)."""
    layout = GradientLayout.monolithic(tree, n, row_multiple=row_multiple)
    return layout.to_blocks(tree), layout, layout.nbar


def flatten_to_blocks_batched(tree: Any, n: int, row_multiple: int = 1):
    """Batched variant: every leaf carries a leading ``pods`` axis; returns
    (pods, nblocks, N) blocks plus the UNBATCHED spec (for blocks_to_tree on
    the aggregated result)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple((tuple(l.shape[1:]), l.dtype) for l in leaves)
    layout = GradientLayout.from_shapes(treedef, shapes, n, row_multiple=row_multiple)
    return layout.to_blocks_batched(tree), layout, layout.nbar


def blocks_to_tree(blocks: jnp.ndarray, spec: Any, nbar: int | None = None) -> Any:
    """Inverse of :func:`flatten_to_blocks`.  ``spec`` is a
    :class:`~repro.core.layout.GradientLayout` (the ``nbar`` argument is then
    redundant and ignored -- the layout knows its own unpadding) or the
    legacy ``(treedef, shapes)`` tuple."""
    if isinstance(spec, GradientLayout):
        return spec.tree_from_blocks(blocks)
    treedef, shapes = spec
    flat = blocks.reshape(-1)[:nbar]
    leaves = []
    off = 0
    for shape, dtype in shapes:
        size = int(np.prod([int(d) for d in shape], dtype=object)) if shape else 1
        leaves.append(flat[off : off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# bit packing (wire format)
# ---------------------------------------------------------------------------


def packed_width(m: int, bits: int) -> int:
    """uint32 words per block row on the wire: W = ceil(lanes / (32 // Q)).
    ``m`` counts *code lanes* -- measurements for the scalar families,
    M / d vector-codebook indices for vq -- and ``bits`` is the per-code
    index width ceil(log2 levels)."""
    return -(-m // (32 // bits))


def pack_codes(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Packs Q-bit indices into uint32 words -- the canonical wire layout.

    Lane-group interleaved (DESIGN.md #Wire-format): with per_word = 32 //
    bits and W = ceil(M / per_word), measurement ``m`` lives in word
    ``m % W`` at bit offset ``(m // W) * bits``, i.e. word ``w`` holds
    measurements ``{w, W + w, 2W + w, ...}``.  This is the layout the fused
    encoder kernel emits with contiguous static lane-group shifts (a
    consecutive-codes-per-word layout would need an in-kernel transpose).

    (nb, M) uint8 -> (nb, W) uint32.
    """
    per_word = 32 // bits
    nb, m = codes.shape
    w = packed_width(m, bits)
    pad = w * per_word - m
    if pad:
        codes = jnp.concatenate([codes, jnp.zeros((nb, pad), codes.dtype)], axis=1)
    grouped = codes.reshape(nb, per_word, w).astype(jnp.uint32)
    shifts = (jnp.arange(per_word, dtype=jnp.uint32) * bits)[None, :, None]
    # Disjoint bit ranges per group, so the OR-accumulate is a plain sum.
    return jnp.sum(grouped << shifts, axis=1).astype(jnp.uint32)


def _unpack_groups(words: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(..., W) uint32 -> (..., per_word, W) uint32 lane groups (shift/mask)."""
    per_word = 32 // bits
    shifts = (jnp.arange(per_word, dtype=jnp.uint32) * bits).reshape(
        (1,) * (words.ndim - 1) + (per_word, 1)
    )
    mask = jnp.uint32((1 << bits) - 1)
    return (words[..., None, :] >> shifts) & mask


def unpack_codes(words: jnp.ndarray, bits: int, m: int) -> jnp.ndarray:
    """Inverse of :func:`pack_codes`: (..., W) uint32 -> (..., m) uint8.
    Leading batch dims pass through (so stacked (K, nb, W) payloads unpack
    without a vmap)."""
    out = _unpack_groups(words, bits).astype(jnp.uint8)
    return out.reshape(words.shape[:-1] + (-1,))[..., :m]


def decode_packed(
    words: jnp.ndarray, bits: int, m: int, levels: jnp.ndarray
) -> jnp.ndarray:
    """Dequantize straight from the packed wire words: (..., W) uint32 ->
    (..., m) f32 reconstruction levels.  The level lookup
    (:func:`~repro.core.quantizer.select_levels`) reads the shift/masked
    lane groups directly, so no (..., M) uint8 index view is built."""
    deq = select_levels(levels, _unpack_groups(words, bits))  # (..., per_word, W)
    return deq.reshape(words.shape[:-1] + (-1,))[..., :m]


# ---------------------------------------------------------------------------
# The codec
# ---------------------------------------------------------------------------


_KERNEL_BYPASS_WARNED = False


def _warn_kernel_bypass_once(cfg: FedQCSConfig) -> None:
    """use_kernels=True with gamp_variance_mode='exact' (the default) keeps
    the GAMP solves on the XLA path -- the fused kernels implement
    scalar-variance GAMP only -- which used to happen silently.  Name the
    conflict once per process; the encode kernels are unaffected."""
    global _KERNEL_BYPASS_WARNED
    if _KERNEL_BYPASS_WARNED:
        return
    if cfg.use_kernels and cfg.gamp_variance_mode == "exact":
        _KERNEL_BYPASS_WARNED = True
        warnings.warn(
            "FedQCSConfig(use_kernels=True, gamp_variance_mode='exact'): the "
            "fused GAMP kernels implement scalar-variance GAMP, so every GAMP "
            "reconstruction will keep the pure-XLA path despite "
            "use_kernels=True (the fused encoder still runs).  Set "
            "gamp_variance_mode='scalar' to route reconstruction through the "
            "kernels (see DESIGN.md #Kernels).",
            UserWarning,
            stacklevel=3,
        )


class BQCSCodec:
    """Stateless BQCS encoder/decoder bound to a FedQCSConfig.

    The sensing matrix and quantizer codebook are derived deterministically
    from the config, so constructing the same codec on every pod yields the
    same protocol -- no matrix or table ever crosses the wire.
    """

    def __init__(self, cfg: FedQCSConfig):
        self.cfg = cfg.validate()
        _warn_kernel_bypass_once(cfg)
        self.codebook: Codebook = make_codebook(cfg)
        key = jax.random.PRNGKey(cfg.seed)
        self._a = sensing.sensing_matrix(key, cfg.m, cfg.block_size)

    @property
    def a(self) -> jnp.ndarray:
        return self._a

    @property
    def quantizer(self) -> Codebook:
        """Back-compat alias: the codebook duck-types the old
        LloydMaxQuantizer surface (bits/gamma/psi/kappa/jnp_levels/
        jnp_thresholds for scalar families)."""
        return self.codebook

    @property
    def n_codes(self) -> int:
        """Index lanes per block on the wire: M / codebook.dim."""
        return self.codebook.n_codes(self.cfg.m)

    # -- encode ------------------------------------------------------------
    def compress_blocks_packed(
        self, blocks: jnp.ndarray, residual: jnp.ndarray, s: int | None = None
    ):
        """(blocks + residual) -> (words, alpha, new_residual).  Eqs. 7-10
        plus the wire packing: ``words`` is the (nb, W) uint32 payload in the
        canonical :func:`pack_codes` layout -- this is what crosses the wire.

        With ``use_kernels`` the whole pipeline (error-feedback add, top-S,
        projection, quantization, packing) is ONE fused Pallas pass; the XLA
        path composes the stage functions and packs last.  ``s`` overrides
        the config's global top-S budget (per-segment sparsity budgets of a
        :class:`GradientLayout`); every stage is per-block, so any row
        partition of ``blocks`` encodes bit-identically to the whole.
        """
        cfg = self.cfg
        if cfg.use_kernels:
            from repro.kernels import ops as kops

            return kops.bqcs_encode_fused(
                blocks, residual, self._a, self.codebook, cfg.s if s is None else s
            )
        codes, alpha, new_residual = self._compress_blocks_xla(blocks, residual, s)
        return pack_codes(codes, self.codebook.bits), alpha, new_residual

    def compress_blocks(
        self, blocks: jnp.ndarray, residual: jnp.ndarray, s: int | None = None
    ):
        """(blocks + residual) -> (codes, alpha, new_residual).  Eqs. 7-10.

        Unpacked uint8-index view of :meth:`compress_blocks_packed` for
        PS-side math and analysis; the kernel route still runs the fused
        single-pass encoder and unpacks the words it emits.
        """
        cfg = self.cfg
        if cfg.use_kernels:
            words, alpha, new_residual = self.compress_blocks_packed(blocks, residual, s)
            return self.unpack(words), alpha, new_residual
        return self._compress_blocks_xla(blocks, residual, s)

    def _compress_blocks_xla(
        self, blocks: jnp.ndarray, residual: jnp.ndarray, s: int | None = None
    ):
        cfg = self.cfg
        s = cfg.s if s is None else s
        carry = blocks + residual
        if cfg.sparsifier == "bisect":
            sparse, new_residual = sparsify.block_sparsify_threshold(carry, s)
        else:
            sparse, new_residual = sparsify.block_sparsify(carry, s)
        x, alpha = sensing.project_blocks(sparse, self._a.T)
        return self.codebook.encode(x), alpha, new_residual

    def layout_for(self, grads_like: Any, per_tensor: bool = False, **kwargs) -> GradientLayout:
        """Builds this codec's block layout for a gradient tree: monolithic
        (the default wire geometry, bit-identical to the pre-layout flatten)
        or per-tensor (independently padded leaf segments -- the streaming
        geometry; ``kwargs`` forward to :meth:`GradientLayout.per_tensor`)."""
        n = self.cfg.block_size
        if per_tensor:
            return GradientLayout.per_tensor(grads_like, n, **kwargs)
        return GradientLayout.monolithic(grads_like, n, **kwargs)

    def compress_tree(
        self, grads: Any, residual_blocks: jnp.ndarray,
        layout: GradientLayout | None = None,
    ):
        """Whole-tree encode: blocks per ``layout`` (default: monolithic --
        the pre-layout wire, bit-identical), one encoder pass over the full
        grid.  Per-tensor layouts with uniform sparsity also take this path;
        per-segment ``s`` budgets force the segment loop (same wire bits,
        see :meth:`compress_tree_streamed`)."""
        cfg = self.cfg
        if layout is None:
            layout = GradientLayout.monolithic(grads, cfg.block_size)
        seg_s = layout.segment_s(cfg.s)
        if any(s != cfg.s for s in seg_s):
            return self.compress_tree_streamed(grads, residual_blocks, layout)
        words, alpha, new_res = self.compress_blocks_packed(
            layout.to_blocks(grads), residual_blocks
        )
        payload = CompressedGradient(
            words, alpha, layout.nbar, cfg.m, self.codebook.bits
        )
        return payload, layout, new_res

    def compress_tree_streamed(
        self, grads: Any, residual_blocks: jnp.ndarray, layout: GradientLayout
    ):
        """Segment-streamed encode: drives the (fused) encoder one layout
        segment at a time -- build segment i's blocks from its own leaves,
        encode (with its own top-S budget), carry its error-feedback residual
        rows, discard -- so peak live encoder memory is bounded by the
        LARGEST segment's blocks, not the whole model
        (``layout.encoder_live_bytes``).  Every encoder stage is per-block,
        so the concatenated wire output is BIT-IDENTICAL to the one-pass
        :meth:`compress_tree` over the same layout.

        Returns the same ``(CompressedGradient, layout, new_residual)``
        triple; ``residual_blocks`` is the full ``(rows, N)`` grid and comes
        back the same shape."""
        cfg = self.cfg
        words_parts, alpha_parts, res_parts = [], [], []
        for seg, seg_blocks in layout.iter_segment_blocks(grads):
            w, al, res = self.compress_blocks_packed(
                seg_blocks,
                residual_blocks[seg.row_slice],
                s=seg.s if seg.s is not None else cfg.s,
            )
            words_parts.append(w)
            alpha_parts.append(al)
            res_parts.append(res)
        words = jnp.concatenate(words_parts, axis=0)
        alpha = jnp.concatenate(alpha_parts, axis=0)
        new_res = jnp.concatenate(res_parts, axis=0)
        payload = CompressedGradient(
            words, alpha, layout.nbar, cfg.m, self.codebook.bits
        )
        return payload, layout, new_res

    def zero_residual(self, grads_like: Any, layout: GradientLayout | None = None) -> jnp.ndarray:
        if layout is None:
            layout = GradientLayout.monolithic(grads_like, self.cfg.block_size)
        return jnp.zeros((layout.rows, layout.n), jnp.float32)

    # -- wire --------------------------------------------------------------
    def pack(self, codes: jnp.ndarray) -> jnp.ndarray:
        return pack_codes(codes, self.codebook.bits)

    def unpack(self, words: jnp.ndarray) -> jnp.ndarray:
        """(..., W) words -> (..., n_codes) index view (n_codes = M / dim)."""
        return unpack_codes(words, self.codebook.bits, self.n_codes)

    # -- decode helpers ------------------------------------------------------
    def dequantize(self, codes: jnp.ndarray) -> jnp.ndarray:
        return self.codebook.decode(codes, self.cfg.m)

    def dequantize_packed(self, words: jnp.ndarray) -> jnp.ndarray:
        """Reconstruction values straight from packed wire words (..., W) --
        the index view never materializes on the scalar families (see
        :func:`decode_packed`); vq unpacks indices then reads centroids."""
        return self.codebook.decode_packed(words, self.cfg.m)

    # -- decode health -------------------------------------------------------
    def clip_saturation(self, codes_or_words: jnp.ndarray, packed: bool = True):
        """Fraction of code lanes pinned at an extreme codebook level --
        the quantizer clip-saturation rate (repro.obs decode health).

        Scalar families order their levels, so index 0 / L-1 means the input
        overshot the quantizer's support: a rising rate flags an alpha
        scaling (or Lloyd-Max fit) losing the gradient's tails.  Vector
        codebooks have no level order, so vq reports a constant 0.  Jit-safe
        scalar; padding lanes in packed words are excluded by the unpack
        slice."""
        q = self.codebook
        if q.dim != 1:
            return jnp.zeros(())
        idx = self.unpack(codes_or_words) if packed else codes_or_words
        extreme = (idx == 0) | (idx == q.n_levels - 1)
        return jnp.mean(extreme.astype(jnp.float32))
