"""Chunked / sharded packed-domain PS reconstruction engine
(DESIGN.md #Recon-engine).

PR 3 scaled the client side to 1000-client cohorts; this module makes the PS
decode scale the same way.  The EA strategy (the paper's best-NMSE mode,
Procedure 2) is one independent Q-EM-GAMP inversion per (worker, block) --
``K * nb`` problems sharing one sensing matrix.  The monolithic batch solve
(`reconstruction.estimate_and_aggregate` at chunk=0) materializes the whole
``(K*nb, N)`` GAMP state, plus on the XLA path the full ``(K, nb, M)`` uint8
code view, and iterates every problem until the *globally* slowest block
converges.  The engine fixes all three scale terms:

  * **chunking** -- the flat problem batch streams through a ``lax.scan`` in
    fixed-size chunks (``FedQCSConfig.recon_chunk`` rows), so live GAMP state
    is O(chunk * N) regardless of cohort size;
  * **packed-domain decode** -- chunks carry the uint32 wire words straight
    from the collective; the fused kernel unpacks per lane group in VMEM and
    the XLA path unpacks one chunk at a time, so the ``(K, nb, M)`` uint8
    tensor never exists (``qem_gamp_packed``);
  * **early-stop per chunk** -- each chunk's GAMP loop exits when *its own*
    slowest block froze (``GampConfig.early_stop``), converting the
    early-freeze carry into wall-clock instead of masked no-op iterations;
  * **sharding** -- chunks optionally spread over a mesh axis via
    ``jax.shard_map``: the chunk axis is partitioned into CONTIGUOUS
    blocks of nch/ndev chunks per device (PartitionSpec semantics), so the
    dead-row pad chunks appended at the end all land on the last device --
    cheap, since dead rows freeze at iteration 0 and an early-stop chunk of
    only dead rows exits after one iteration.  Every device scans only its
    own chunks.  Do NOT nest this under the 'pod' manual collective -- the
    in-step decode is already sharded by the outer mesh.

The two-phase sweep (`ea_decode_two_phase`) adds a quality mode: a cheap
scalar-variance pass everywhere, then exact-variance GAMP (Procedure 2's
per-entry variances) re-solves only the blocks whose converged flag is still
false.  Phase 2's survivor gather is host-side (data-dependent shapes), so
the two-phase entry point is a host orchestrator around jitted solves -- use
it from drivers (benchmarks, offline decode), not inside a train step.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import BQCSCodec
from repro.core.gamp import (
    GampConfig,
    GampInfo,
    _qem_gamp_xla,
    em_gamp,
    qem_gamp,
    qem_gamp_packed,
)

__all__ = [
    "ReconSpec",
    "chunked_rows",
    "chunked_solve",
    "ea_solve_flat",
    "ea_decode",
    "ea_decode_segments",
    "ea_decode_two_phase",
    "decode_from_stats",
]


@dataclasses.dataclass(frozen=True)
class ReconSpec:
    """One value describing HOW the PS reconstructs a round.

    Consolidates the stringly knobs that had accreted across
    ``api.reconstruct`` / the collectives / the recon engine (positional
    ``mode``, ``groups``, per-call chunk overrides) into a single spec every
    entry point accepts:

      mode: "ae" (aggregate-and-estimate: Bussgang combine, one EM-GAMP) or
        "ea" (estimate-and-aggregate: per-worker Q-EM-GAMP, rho-sum).
      groups: AE grouping G (ideal uplink only; eq. 25 grouping).
      chunk: recon-engine row chunking; None defers to the codec's
        ``cfg.recon_chunk``.
      use_pallas: fused-kernel routing; None defers to ``cfg.use_kernels``.
      channel: optional received multiple-access observation in place of the
        per-payload codes: a ``(y_eff (nb, M), nu_eff (nb,))`` pair as
        produced by a channel family's ``combine`` hook (fed/channel.py --
        typed loosely here: core stays fed-agnostic).  AE only; the payloads
        then contribute alphas (quantization noise + GAMP init), not codes.
      return_info: also return the solver's decode-health aux (per-block
        converged flags + live-iteration counts, :class:`~repro.core.gamp.
        GampInfo`) instead of discarding it -- ``api.reconstruct`` then
        returns ``(tree, info)``.  Kernel routes report the static
        placeholder info (fixed trip count, no freeze signal).
    """

    mode: str = "ae"
    groups: int = 1
    chunk: Optional[int] = None
    use_pallas: Optional[bool] = None
    channel: Any = None
    return_info: bool = False

    def __post_init__(self):
        if self.mode not in ("ae", "ea"):
            raise ValueError(f"unknown recon mode {self.mode!r} (want 'ea' or 'ae')")
        if self.groups < 1:
            raise ValueError(f"groups must be >= 1, got {self.groups}")
        if self.mode == "ea" and self.channel is not None:
            raise ValueError(
                "a superimposed multiple-access reception has no per-client "
                "codes, so recon mode 'ea' cannot consume a channel "
                "observation (use mode='ae')"
            )
        if self.channel is not None and self.groups != 1:
            raise ValueError("groups != 1 is only defined for exact-code AE")

    def resolve(self, cfg) -> "ReconSpec":
        """Fills the defer-to-codec fields from a FedQCSConfig."""
        return dataclasses.replace(
            self,
            chunk=cfg.recon_chunk if self.chunk is None else self.chunk,
            use_pallas=cfg.use_kernels if self.use_pallas is None else self.use_pallas,
        )


def _pad_rows_zero(arrays, rows: int, target: int):
    """Zero-pads every array's leading axis from ``rows`` to ``target``.
    Zero rows are dead blocks (alpha == 0): the solver freezes them from
    iteration 0 and emits exact zeros, so padding is output-invariant."""
    pad = target - rows
    if pad == 0:
        return arrays
    return tuple(
        jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]) for x in arrays
    )


def chunked_rows(
    solve,
    inputs: Tuple[jnp.ndarray, ...],
    chunk: int,
    out_width: int,
    mesh=None,
    axis_name: str = "recon",
):
    """Streams row-aligned ``inputs`` through ``solve`` in fixed-size chunks.

    ``solve(*chunk_inputs) -> (chunk, out_width)`` runs under a ``lax.scan``
    over ``ceil(rows / chunk)`` chunks (rows zero-padded to the chunk grid --
    dead-block padding, see `_pad_rows_zero`).  With a ``mesh``, the chunk
    axis is additionally sharded over ``axis_name`` via
    ``jax.shard_map``: the chunk count is padded to the axis size and
    each device scans its local chunks; everything ``solve`` closes over
    (sensing matrix, threshold tables) is replicated.

    ``chunk <= 0`` or a chunk covering all rows degrades to one direct call.
    Either way the solve, its padding and its scan run under the ``gamp``
    scope (obs/trace.py).
    """
    with jax.named_scope("gamp"):
        rows = inputs[0].shape[0]
        if chunk <= 0 or (chunk >= rows and mesh is None):
            return solve(*inputs)
        nch = -(-rows // chunk)
        if mesh is not None:
            ndev = mesh.shape[axis_name]
            nch = -(-nch // ndev) * ndev
        padded = _pad_rows_zero(inputs, rows, nch * chunk)
        chunked = tuple(x.reshape((nch, chunk) + x.shape[1:]) for x in padded)

        def scan_chunks(*xs):
            _, out = jax.lax.scan(lambda _, c: (None, solve(*c)), None, xs)
            return out

        if mesh is None:
            out = scan_chunks(*chunked)
        else:
            from jax.sharding import PartitionSpec as P

            spec = P(axis_name)
            out = jax.shard_map(
                scan_chunks,
                mesh=mesh,
                in_specs=(spec,) * len(chunked),
                out_specs=spec,
                axis_names={axis_name},
                check_vma=False,
            )(*chunked)
        return out.reshape(nch * chunk, out_width)[:rows]


def ea_solve_flat(
    codec: BQCSCodec,
    obs: jnp.ndarray,  # (rows, M) codes or (rows, W) packed uint32 words
    alpha: jnp.ndarray,  # (rows,)
    gamp: GampConfig,
    *,
    packed: bool,
    use_pallas: bool = False,
    chunk: int = 0,
    mesh=None,
    axis_name: str = "recon",
    with_info: bool = False,
) -> jnp.ndarray:
    """Solves a flat batch of per-(worker, block) Q-EM-GAMP problems ->
    (rows, N) block estimates.  The chunk solver is `qem_gamp_packed` when
    ``packed`` (wire words in, in-VMEM/in-chunk unpack) else `qem_gamp`.

    ``with_info`` returns ``(estimates, GampInfo)`` instead (see
    :func:`chunked_solve`).
    """
    n = codec.cfg.block_size
    if packed:
        base = lambda o, al: qem_gamp_packed(
            o, al, codec.a, codec.codebook, gamp, codec.cfg.m,
            use_pallas=use_pallas, with_info=with_info,
        )
    else:
        base = lambda o, al: qem_gamp(
            o, al, codec.a, codec.codebook, gamp,
            use_pallas=use_pallas, with_info=with_info,
        )
    return chunked_solve(
        base, (obs, alpha), chunk, n,
        with_info=with_info, mesh=mesh, axis_name=axis_name,
    )


def chunked_solve(
    base,
    inputs: Tuple[jnp.ndarray, ...],
    chunk: int,
    n: int,
    *,
    with_info: bool = False,
    mesh=None,
    axis_name: str = "recon",
):
    """:func:`chunked_rows` for a row-wise GAMP solve ``base(*inputs)`` ->
    (rows, n) estimates, or ``(estimates, GampInfo)`` with ``with_info``:
    the per-row converged flags and live-iteration counts then ride the
    chunk scan as two extra output columns (the same trick as
    `ea_decode_two_phase`'s flag column), so the info costs nothing beyond
    the columns themselves."""
    if not with_info:
        return chunked_rows(base, inputs, chunk, n, mesh=mesh, axis_name=axis_name)

    def solve(*xs):
        gh, info = base(*xs)
        return jnp.concatenate(
            [
                gh,
                info.converged.astype(jnp.float32)[:, None],
                info.iters.astype(jnp.float32)[:, None],
            ],
            axis=1,
        )

    stacked = chunked_rows(solve, inputs, chunk, n + 2, mesh=mesh, axis_name=axis_name)
    info = GampInfo(stacked[:, n] > 0.5, stacked[:, n + 1].astype(jnp.int32))
    return stacked[:, :n], info


def ea_decode(
    codec: BQCSCodec,
    obs: jnp.ndarray,  # (K, nb, M) uint8 codes or (K, nb, W) uint32 words
    alphas: jnp.ndarray,  # (K, nb)
    rhos: jnp.ndarray,  # (K,)
    gamp: Optional[GampConfig] = None,
    *,
    packed: bool,
    use_pallas: bool = False,
    chunk: int = 0,
    mesh=None,
    axis_name: str = "recon",
    spec: Optional[ReconSpec] = None,
    with_info: bool = False,
) -> jnp.ndarray:
    """FedQCS-EA decode through the engine: flatten the (K, nb) problem grid,
    chunk/shard-solve, rho-weight and sum -> (nb, N) aggregated blocks.

    Jit-safe (the chunk stream is a ``lax.scan``); this is what
    `reconstruction.estimate_and_aggregate` / ``_packed`` delegate to.
    A ``spec`` (ReconSpec) overrides the chunk/use_pallas knobs in one value
    (its ``return_info`` implies ``with_info``); with info requested the
    return is ``(blocks, GampInfo)`` whose aux arrays are (K, nb)-shaped.
    """
    from repro.core.reconstruction import gamp_config_from  # deferred: layering

    if spec is not None:
        spec = spec.resolve(codec.cfg)
        chunk, use_pallas = spec.chunk, spec.use_pallas
        with_info = with_info or spec.return_info
    gamp = gamp or gamp_config_from(codec)
    k, nb = obs.shape[:2]
    flat = ea_solve_flat(
        codec,
        obs.reshape((k * nb,) + obs.shape[2:]),
        alphas.reshape(k * nb),
        gamp,
        packed=packed,
        use_pallas=use_pallas,
        chunk=chunk,
        mesh=mesh,
        axis_name=axis_name,
        with_info=with_info,
    )
    if with_info:
        flat, info = flat
        agg = jnp.einsum("k,kbn->bn", rhos, flat.reshape(k, nb, -1))
        return agg, GampInfo(
            info.converged.reshape(k, nb), info.iters.reshape(k, nb)
        )
    return jnp.einsum("k,kbn->bn", rhos, flat.reshape(k, nb, -1))


def ea_decode_segments(
    codec: BQCSCodec,
    obs: jnp.ndarray,  # (K, nb, M) uint8 codes or (K, nb, W) uint32 words
    alphas: jnp.ndarray,  # (K, nb)
    rhos: jnp.ndarray,  # (K,)
    layout,  # core.layout.GradientLayout (the round's block geometry)
    gamp: Optional[GampConfig] = None,
    *,
    packed: bool,
    use_pallas: bool = False,
    chunk: int = 0,
    emit=None,  # callback(segment, {leaf id: array}) per decoded segment
) -> jnp.ndarray:
    """Segment-local FedQCS-EA decode: each layout segment's ``(K, rows)``
    block problems solve and aggregate independently, so per-tensor decode
    starts -- and ``emit(segment, leaves)`` fires with that segment's decoded
    leaves -- as soon as its rows arrive, without waiting for the rest of the
    model (a streaming PS receiving segments in backward order updates the
    last layers first).

    Chunk boundaries align to layout segments by construction here: every
    segment is its own chunked solve, so no ``lax.scan`` chunk ever straddles
    two tensors (build per-tensor layouts with ``row_multiple=chunk`` to keep
    those per-segment chunks full).  Each GAMP problem is per-(worker, block)
    row, so the concatenated output matches :func:`ea_decode` over the whole
    grid up to float reassociation (XLA compiles different reduction orders
    for different batch shapes, and GAMP iterates on them -- expect ~1e-4
    relative, not bit-identity).  Host loop over segments around jitted solves --
    call from drivers/PS ingest, not inside jit.  Returns the aggregated
    ``(nb, N)`` block grid.
    """
    if layout.rows != obs.shape[1]:
        raise ValueError(
            f"layout has {layout.rows} block rows, payloads have {obs.shape[1]}"
        )
    parts = []
    for seg in layout.segments:
        agg = ea_decode(
            codec,
            obs[:, seg.row_slice],
            alphas[:, seg.row_slice],
            rhos,
            gamp,
            packed=packed,
            use_pallas=use_pallas,
            chunk=chunk,
        )
        if emit is not None:
            emit(seg, layout.segment_leaves(seg.index, agg))
        parts.append(agg)
    return jnp.concatenate(parts, axis=0)


def ea_decode_two_phase(
    codec: BQCSCodec,
    obs: jnp.ndarray,  # (K, nb, M) uint8 codes or (K, nb, W) uint32 words
    alphas: jnp.ndarray,  # (K, nb)
    rhos: jnp.ndarray,  # (K,)
    gamp: Optional[GampConfig] = None,
    *,
    packed: bool,
    chunk: int = 0,
    refine_iters: Optional[int] = None,
    mesh=None,
    axis_name: str = "recon",
) -> Tuple[jnp.ndarray, dict]:
    """Two-phase EA sweep: scalar-variance GAMP everywhere (cheap: 2 GEMMs
    per iteration), then exact-variance GAMP (4 GEMMs, Procedure 2's
    per-entry variances) re-solves ONLY the blocks whose early-freeze flag
    is still false after phase 1.

    Host orchestrator (phase 2 gathers a data-dependent survivor set), so
    call it from drivers, not inside jit.  Returns (aggregated (nb, N)
    blocks, stats dict with phase-2 counts).
    """
    from repro.core.reconstruction import gamp_config_from  # deferred: layering

    gamp = gamp or gamp_config_from(codec)
    k, nb = obs.shape[:2]
    rows = k * nb
    n = codec.cfg.block_size
    flat_obs = obs.reshape((rows,) + obs.shape[2:])
    flat_alpha = alphas.reshape(rows)

    # Phase 1: scalar-variance sweep over every problem, converged flags out.
    # The flags come from _gamp_run's early-freeze carry, so the XLA solver
    # runs phase 1 (the kernel's fixed-trip scan has no freeze signal).
    p1 = dataclasses.replace(gamp, variance_mode="scalar")
    codes_of = (
        (lambda o: codec.unpack(o)) if packed else (lambda o: o)
    )
    def solve_flags(o, al):
        gh, fl, it = _qem_gamp_xla(codes_of(o), al, codec.a, codec.codebook, p1)
        # converged flag + live-iteration count ride as extra output columns
        return jnp.concatenate(
            [gh, fl.astype(jnp.float32)[:, None], it.astype(jnp.float32)[:, None]],
            axis=1,
        )

    stacked = chunked_rows(
        solve_flags, (flat_obs, flat_alpha), chunk, n + 2,
        mesh=mesh, axis_name=axis_name,
    )
    ghat = stacked[:, :n]
    converged = np.asarray(stacked[:, n]) > 0.5
    iters1 = np.asarray(stacked[:, n + 1])

    # Phase 2: exact-variance refinement of the survivors only.
    survivors = np.flatnonzero(~converged)
    if survivors.size:
        p2 = dataclasses.replace(
            gamp,
            variance_mode="exact",
            iters=refine_iters if refine_iters is not None else gamp.iters,
            early_stop=False,
        )
        idx = jnp.asarray(survivors)
        refined, _, _ = jax.jit(
            lambda o, al: _qem_gamp_xla(codes_of(o), al, codec.a, codec.codebook, p2)
        )(flat_obs[idx], flat_alpha[idx])
        ghat = ghat.at[idx].set(refined)
    stats = {
        "rows": rows,
        "phase2_rows": int(survivors.size),
        "phase2_frac": float(survivors.size) / max(rows, 1),
        # decode-health counters (repro.obs): phase-1 effort + the
        # unconverged-survivor count IS phase2_rows, recorded explicitly
        # under the counter's name so run logs stay self-describing.
        "phase1_iters_mean": float(iters1.mean()) if rows else 0.0,
        "unconverged_survivors": int(survivors.size),
    }
    agg = jnp.einsum("k,kbn->bn", rhos, ghat.reshape(k, nb, n))
    return agg, stats


def decode_from_stats(
    codec: BQCSCodec,
    stats,  # core.aggregator.PartialStats (the folded round total)
    gamp: Optional[GampConfig] = None,
    *,
    use_pallas: bool = False,
    with_info: bool = False,
) -> jnp.ndarray:
    """Finalizes a streamed round straight from folded partial sufficient
    statistics (core/aggregator.py; DESIGN.md #Streaming-PS) -> (nb, N)
    aggregated blocks.  ``with_info`` returns ``(blocks, GampInfo | None)``:
    the finalize EM-GAMP's decode health on the "ae" path, None on "ea"
    (whose GAMP ran per ingest batch -- StreamingPS accumulates that).

    "ea" stats already hold the raw-weighted sum of per-client GAMP
    estimates, so finalization is just the 1/W renormalization.  "ae" stats
    hold the Bussgang aggregate's (y, nu, energy) accumulated with RAW
    weights; after the 1/W (linear) and 1/W^2 (quadratic) rescale this is
    bit-for-bit the barrier AE observation up to f32 reassociation of the
    client sums, and one EM-GAMP inversion finishes the decode exactly like
    `reconstruction.aggregate_and_estimate`.  Jit-safe.
    """
    from repro.core.aggregator import normalized_stats  # deferred: layering
    from repro.core.reconstruction import gamp_config_from  # deferred: layering

    y, nu, energy = normalized_stats(stats)
    if stats.mode == "ea":
        return (y, None) if with_info else y
    gamp = gamp or gamp_config_from(codec)
    return em_gamp(
        y, nu, codec.a, gamp, init_var=energy,
        use_pallas=use_pallas, with_info=with_info,
    )
