"""The cohort round engine (DESIGN.md #Fed-engine).

Runs full FL rounds over *any* model exposed as ``grad_fn(params, batch)`` at
thousands-of-clients scale.  One round is two passes:

  * **client pass** — every cohort member's gradient + BQCS encode, batched
    through ``jax.vmap`` in one device pass (optionally ``lax.scan``-chunked
    so the per-client gradient trees never all materialize at once).  A
    bit-identical Python-loop oracle (``impl="loop"``) dispatches the
    per-client codec path one client at a time — the pre-engine
    ``paper/mlp.py`` dispatch pattern — and is the benchmark baseline.  Both
    impls share the batched gradient pass (the gradient is the model's work,
    and per-client GEMM lowerings are not ulp-deterministic across batch
    shapes on every backend), so loop-vs-vmap equality is exact by
    construction for any model.
  * **PS pass** — reconstruction once per round from the stacked payloads:
    the method dispatch (fedqcs-ae / fedqcs-ea / qcs-qiht / qcs-dither /
    signsgd / none) reuses ``core/reconstruction.py`` + ``core/baselines.py``
    unchanged; the wireless channel's effective noise variance threads into
    ``em_gamp``'s ``noise_var`` next to the Bussgang quantization distortion
    (eq. 24 + channel term).

The quantizer codebook is a scenario axis like the partition or channel:
``FedQCSConfig.codebook`` ("lloyd_max" / "dithered_uniform" / "vq") selects
the wire family for every fedqcs/qiht method, and the PS dispatch picks the
matching channel automatically (exact truncated-posterior cells for scalar
families, the Bussgang-linearized fallback for vq -- DESIGN.md #Codebooks);
``examples/federated_mnist.py --compare`` sweeps EA/AE across the families.

Participation contract (shared with ``runtime/collectives.py``): a cohort
slot with ``rho_k = 0`` — scheduler dropout or channel outage — contributes
exactly zero to the aggregate, and its error-feedback residual carries the
*full* gradient forward (``blocks + residual``), so a straggler's work is
deferred, not lost.  Clients outside the cohort are untouched.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines, bussgang
from repro.core.compression import (
    BQCSCodec,
    FedQCSConfig,
    blocks_to_tree,
    packed_width,
)
from repro.core.layout import GradientLayout
from repro.core.gamp import em_gamp, gamp_health
from repro.core.reconstruction import (
    aggregate_and_estimate,
    estimate_and_aggregate_packed,
    gamp_config_from,
)
from repro.obs import NULL_RECORDER
from repro.obs.trace import (
    APPLY,
    CLIENT,
    DECODE,
    SUB_PHASES,
    UPLINK,
    SpanCollector,
    round_step,
    scoped,
    span,
)
from repro.fed.channel import (
    CHANNEL_FAMILIES,
    ChannelConfig,
    get_channel_family,
    mimo_tx_gain,
    realize_uplink,
)
from repro.fed.scheduler import SchedulerConfig, SchedulerState, select_cohort
from repro.fed.server_opt import ServerOptConfig, init_server_state, server_update
from repro.fed.stream import (
    StreamConfig,
    StreamingPS,
    batch_arrivals,
    late_discount,
    simulate_arrivals,
    stream_decode,
)

__all__ = [
    "CohortConfig",
    "CohortEngine",
    "ArrayClientData",
    "TokenClientData",
    "make_interleaved_segments",
]

EF_METHODS = ("fedqcs-ae", "fedqcs-ea", "qcs-qiht")
METHODS = EF_METHODS + ("qcs-dither", "signsgd", "none")


@dataclasses.dataclass(frozen=True)
class CohortConfig:
    """Engine-level knobs (protocol knobs live in FedQCSConfig)."""

    method: str = "fedqcs-ae"
    chunk: int = 0  # clients per scan chunk in the vmapped pass; 0 = all at once
    groups: int = 1  # AE grouping (G), ideal channel only
    impl: str = "vmap"  # vmap | loop  (loop = per-client oracle/baseline)
    dither_n: int = 2048  # qcs-dither re-blocking size (power of 2)
    record_nmse: bool = True
    seed: int = 0
    # Block layout of the gradient wire (core/layout.py): "monolithic" (the
    # paper's whole-model flatten, bit-identical to the pre-layout engine) or
    # "per_tensor" (independently padded leaf segments -- the streaming
    # geometry).  An explicit GradientLayout passed to CohortEngine(layout=)
    # wins over this string.
    layout: str = "monolithic"
    # Segment-streamed client encode (per_tensor layouts only): the encode
    # pass consumes the gradient one layout segment at a time, so peak live
    # encoder memory is bounded by the largest segment's blocks instead of
    # the whole model (DESIGN.md #Layout).
    encode_stream: bool = False
    # Microbatch count for the default gradient hook under encode_stream
    # (client batch split into grad_accum equal microbatches, gradients
    # averaged) -- bounds per-client activation memory next to the encoder's
    # segment bound.
    grad_accum: int = 1


# ---------------------------------------------------------------------------
# Client data sources
# ---------------------------------------------------------------------------


class ArrayClientData:
    """Labeled-array federation: clients are index sets (from
    ``fed.partition``) into one (x, y) array pair.  Batches are drawn
    host-side, deterministic in (seed, round, client id) so a client's draw
    does not depend on who else is in the cohort."""

    def __init__(self, x, y, parts: List[np.ndarray], batch_size: int = 1, seed: int = 0):
        self.x, self.y = np.asarray(x), np.asarray(y)
        self.parts = [np.asarray(p, np.int64) for p in parts]
        self.counts = np.array([len(p) for p in self.parts], np.int64)
        if (self.counts == 0).any():
            raise ValueError("every client needs at least one sample")
        self.batch_size = batch_size
        self.seed = seed
        # Padded (K, maxlen) index matrix: one vectorized gather per round.
        maxlen = int(self.counts.max())
        self._idx = np.zeros((len(parts), maxlen), np.int64)
        for k, p in enumerate(self.parts):
            self._idx[k, : len(p)] = p
            self._idx[k, len(p) :] = p[0]  # padding never drawn (pos < len)

    def cohort_batch(self, round_idx: int, ids: np.ndarray) -> Dict[str, jnp.ndarray]:
        # One vectorized draw over ALL K clients, rows indexed by global
        # client id: client k's minibatch is a pure function of
        # (seed, round, k), independent of who else is in the cohort (the
        # 0xDA7A tag keeps this stream disjoint from the scheduler's).
        rng = np.random.default_rng((self.seed, 0xDA7A, round_idx))
        u = rng.random((len(self.counts), self.batch_size))[ids]  # (C, b)
        pos = (u * self.counts[ids][:, None]).astype(np.int64)
        sel = self._idx[ids[:, None], pos]  # (C, b)
        return {"x": jnp.asarray(self.x[sel]), "y": jnp.asarray(self.y[sel])}


class TokenClientData:
    """Synthetic-language federation for the registry models: each client
    holds its own stream of ``data/synthetic.py``-style affine-rule sequences.
    Heterogeneity: clients mix ``n_dialects`` rule variants (the additive
    constant shifts per dialect) with per-client mixture weights drawn from
    Dir(alpha) — alpha -> 0 gives one-dialect clients, alpha -> inf IID."""

    def __init__(
        self,
        vocab_size: int,
        batch: int,
        seq: int,
        clients: int,
        alpha: float = 0.0,  # 0 = homogeneous (no dialect skew)
        n_dialects: int = 10,
        noise: float = 0.2,
        seed: int = 0,
    ):
        self.vocab_size, self.batch, self.seq = vocab_size, batch, seq
        self.noise, self.seed = noise, seed
        self.counts = np.ones(clients, np.int64)
        rng = np.random.default_rng((seed, 0xD1A1))
        if alpha > 0:
            self._p = rng.dirichlet(np.full(n_dialects, alpha), size=clients)
        else:
            self._p = np.full((clients, n_dialects), 1.0 / n_dialects)
        self._make = jax.jit(jax.vmap(self._make_one))

    def _make_one(self, key, p):
        from repro.data.synthetic import affine_rule_batch

        k1, k2, k3, k4 = jax.random.split(key, 4)
        dialect = jax.random.categorical(k4, jnp.log(p + 1e-9), shape=(self.batch, 1))
        # dialect shifts the affine rule's additive constant
        return affine_rule_batch(
            k1, k2, k3, self.batch, self.seq, self.vocab_size, self.noise,
            c=17 + 5 * dialect,
        )

    def cohort_batch(self, round_idx: int, ids: np.ndarray) -> Dict[str, jnp.ndarray]:
        base = jax.random.fold_in(jax.random.PRNGKey(self.seed), round_idx)
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.asarray(ids))
        return self._make(keys, jnp.asarray(self._p[ids], jnp.float32))


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class CohortEngine:
    """Stateful driver: owns params, per-client residuals, server-opt and
    scheduler state; each :meth:`run_round` is one federated round.

    ``obs`` is a :class:`repro.obs.MetricsRecorder` (default: the null
    recorder).  Its ``active`` flag is read ONCE here and treated as static:
    an active recorder makes the jitted PS pass return the decode-health
    auxiliaries (GAMP iters/convergence, clip saturation, combiner health)
    and wraps each round phase in a blocking span; the null recorder builds
    the exact pre-telemetry graphs, so it costs nothing (pinned by the
    ``obs`` bench).  Recording itself happens on the host, once per round.
    """

    def __init__(
        self,
        params: Any,
        grad_fn: Callable[[Any, Any], Any],
        data: Any,  # ArrayClientData / TokenClientData duck type
        fed_cfg: Optional[FedQCSConfig] = None,
        cohort: CohortConfig = CohortConfig(),
        sched: SchedulerConfig = SchedulerConfig(),
        chan: ChannelConfig = ChannelConfig(),
        server: ServerOptConfig = ServerOptConfig(),
        stream: Optional[StreamConfig] = None,
        obs: Any = None,
        layout: Optional[GradientLayout] = None,
        grad_segments_fn: Optional[Callable[[Any, Any, GradientLayout], Any]] = None,
    ):
        if cohort.method not in METHODS:
            raise ValueError(f"unknown method {cohort.method!r} (choose from {METHODS})")
        if cohort.layout not in ("monolithic", "per_tensor"):
            raise ValueError(
                f"unknown layout {cohort.layout!r} (choose 'monolithic' or "
                "'per_tensor', or pass an explicit GradientLayout)"
            )
        if cohort.encode_stream and cohort.method not in EF_METHODS:
            raise ValueError(
                "encode_stream drives the BQCS encoder one layout segment at a "
                f"time, which only the error-feedback codec methods {EF_METHODS} "
                f"use; got {cohort.method!r}"
            )
        if cohort.encode_stream and cohort.impl == "loop":
            raise ValueError(
                "encode_stream is a vmapped-encode path; the per-client loop "
                "oracle encodes whole block grids (impl='vmap')"
            )
        if cohort.grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {cohort.grad_accum}")
        if cohort.grad_accum > 1 and not cohort.encode_stream:
            raise ValueError(
                "grad_accum microbatching is the encode_stream gradient hook's "
                "knob (DESIGN.md #Layout); set encode_stream=True"
            )
        if grad_segments_fn is not None and not cohort.encode_stream:
            raise ValueError(
                "grad_segments_fn feeds the segment-streamed encode "
                "(DESIGN.md #Interleave); set encode_stream=True"
            )
        if stream is not None and cohort.method not in ("fedqcs-ae", "fedqcs-ea"):
            raise ValueError(
                f"streaming rounds fold Bussgang/EA sufficient statistics, which "
                f"only the fedqcs methods produce; got {cohort.method!r}"
            )
        if stream is not None and cohort.groups != 1:
            raise ValueError("streaming fedqcs-ae has no group structure (groups must be 1)")
        # Channel gating is by family TRAITS, not kind strings: the registry
        # (fed/channel.py) is the only place a kind resolves to behavior.
        fam = get_channel_family(chan.kind)
        if not fam.exact_codes and cohort.method != "fedqcs-ae":
            raise ValueError(
                f"method {cohort.method!r} needs the exact codes at the PS, which "
                "only an ideal (error-free digital) uplink provides; noisy "
                "channels are supported by 'fedqcs-ae' (Bussgang + channel "
                "variance into em_gamp noise_var, DESIGN.md #Fed-engine)"
            )
        if cohort.groups != 1 and (cohort.method != "fedqcs-ae" or not fam.exact_codes):
            raise ValueError("groups != 1 is only defined for fedqcs-ae over an ideal uplink")
        self._chan_family = fam
        self.cohort, self.sched, self.chan, self.server = cohort, sched, chan, server
        self.stream = stream
        self.obs = obs if obs is not None else NULL_RECORDER
        self._collect = bool(self.obs.active)  # static: fixes the jitted graphs
        self._spans = SpanCollector() if self._collect else None
        self.fed_cfg = fed_cfg or FedQCSConfig()
        self.grad_fn = grad_fn
        self.data = data
        self.params = params

        # The block layout is hoisted ONCE here and shared by every pass (the
        # constructor used to flatten params for the spec and the vmapped
        # client pass re-derived and discarded it each call).  The layout IS
        # the spec: blocks_to_tree takes it directly.
        n = self.fed_cfg.block_size
        if layout is not None:
            if layout.n != n:
                raise ValueError(
                    f"explicit layout has block size {layout.n}, "
                    f"FedQCSConfig.block_size is {n}"
                )
            self.layout = layout
        elif cohort.layout == "per_tensor":
            self.layout = GradientLayout.per_tensor(params, n)
        else:
            self.layout = GradientLayout.monolithic(params, n)
        if self.layout.kind == "per_tensor" and cohort.method == "qcs-dither":
            raise ValueError(
                "qcs-dither re-blocks the monolithic flat vector; a per-tensor "
                "layout interleaves per-segment padding into that vector, so "
                "its geometry does not apply (use the monolithic layout)"
            )
        if not cohort.encode_stream and any(
            seg.s is not None for seg in self.layout.segments
        ):
            raise ValueError(
                "per-segment sparsity budgets only take effect on the "
                "segment-streamed encode; set encode_stream=True"
            )
        self.spec = self.layout
        self.nbar = self.layout.nbar
        self.nb, self.n = self.layout.rows, n
        self._grad_segments_fn = grad_segments_fn
        self.clients = len(data.counts)
        self.codec = BQCSCodec(self.fed_cfg) if cohort.method in EF_METHODS else None
        self.gamp = gamp_config_from(self.codec) if self.codec else None
        self._dither = (
            baselines.DitherCodec(
                n=cohort.dither_n,
                m=cohort.dither_n // self.fed_cfg.reduction_ratio,
                bits=self.fed_cfg.bits,
            )
            if cohort.method == "qcs-dither"
            else None
        )
        self.residuals = jnp.zeros((self.clients, self.nb, self.n), jnp.float32)
        self.server_state = init_server_state(server, params)
        self.sched_state = SchedulerState.init(self.clients)
        self.round = 0
        self.key = jax.random.PRNGKey(cohort.seed)
        # Each program of the round runs under its layer's scope
        # (obs/trace.py); the scopes leave the programs' names as they were.
        self._grads_jit = jax.jit(scoped(self._grad_blocks_fn, CLIENT))
        # loop-oracle unit
        self._encode_jit = jax.jit(scoped(self._encode_fn, CLIENT, "encode"))
        # the cohort residual rows arrive as a fresh gather (residuals[jids])
        # consumed only by the encode, so the new residual writes in place
        self._encode_vmap_jit = jax.jit(
            scoped(jax.vmap(self._encode_fn), CLIENT, "encode"), donate_argnums=(1,)
        )
        if cohort.encode_stream:
            # Per-segment units of the streamed client pass: the batched
            # gradient tree (hook default), one segment's (C, rows, N) block
            # view, the vmapped per-segment encode (top-S budget static so a
            # layout's per-segment s values each get their own graph), and
            # the running true-sum fold for nmse bookkeeping.
            self._grads_tree_jit = jax.jit(scoped(self._grads_tree_fn, CLIENT))
            self._seg_blocks_jit = jax.jit(
                scoped(self.layout.segment_blocks_batched, CLIENT), static_argnums=(1,)
            )
            # segment residual rows are a fresh slice (residuals[:, rows]):
            # donated so each segment's new residual reuses that buffer
            self._encode_seg_jit = jax.jit(
                scoped(jax.vmap(self._encode_segment_fn, in_axes=(0, 0, 0, None)),
                       CLIENT, "encode"),
                static_argnums=(3,),
                donate_argnums=(1,),
            )
            self._seg_true_sum_jit = jax.jit(scoped(
                lambda rhos, blocks: jnp.einsum("k,kbn->bn", rhos, blocks), CLIENT
            ))
        self._ps_jit = jax.jit(scoped(self._ps_fn, DECODE))
        self._uplink_jit = jax.jit(
            scoped(lambda key, c, nb: realize_uplink(self.chan, key, c, nb), UPLINK),
            static_argnums=(1, 2),
        )
        # per-round prep (effective rhos + per-client keys) in one dispatch
        self._prep_jit = jax.jit(scoped(self._prep_fn, CLIENT))
        if stream is not None:
            # One StreamingPS reused across rounds (owns the jitted folds).
            self._stream_ps = StreamingPS(
                self.codec,
                mode="ae" if cohort.method == "fedqcs-ae" else "ea",
                gamp=self.gamp,
                stream=stream,
                use_pallas=self.fed_cfg.use_kernels,
                recon_chunk=self.fed_cfg.recon_chunk,
                chan=self.chan if fam.multiple_access else None,
                collect_health=self._collect,
            )
            self._noise_keys_jit = jax.jit(
                lambda jids, k: jax.vmap(lambda i: jax.random.fold_in(k, i))(jids)
            )
            self._nmse_jit = jax.jit(
                lambda ghat, blocks, rhos: (
                    jnp.sum(jnp.square(ghat - jnp.einsum("k,kbn->bn", rhos, blocks)))
                    / (jnp.sum(jnp.square(jnp.einsum("k,kbn->bn", rhos, blocks))) + 1e-30)
                )
            )
            # encode_stream folds the reference sum during the client pass
            # (payloads carry (nb, N) true_sum, not (C, nb, N) blocks)
            self._nmse_true_jit = jax.jit(
                lambda ghat, ts: jnp.sum(jnp.square(ghat - ts))
                / (jnp.sum(jnp.square(ts)) + 1e-30)
            )
        # blocks -> tree -> server update in one jitted apply (the per-round
        # fixed cost would otherwise be tens of eager dispatches and dominate
        # small cohorts).
        self._apply_jit = jax.jit(scoped(
            lambda ghat_blocks, params, sstate, step: server_update(
                self.server,
                blocks_to_tree(ghat_blocks, self.spec, self.nbar),
                sstate,
                params,
                step,
            ),
            APPLY,
        ))
        # the cohort's new residual rows written back (a copy, not donated)
        self._scatter_jit = jax.jit(scoped(_scatter_residuals, APPLY))
        if self._collect:
            # one fused reduction per round: update + param global l2 norms
            self._norms_jit = jax.jit(
                lambda blocks, params: (
                    jnp.sqrt(jnp.sum(jnp.square(blocks))),
                    jnp.sqrt(
                        sum(
                            jnp.sum(jnp.square(x))
                            for x in jax.tree_util.tree_leaves(params)
                        )
                    ),
                )
            )
            self._sat_jit = (
                jax.jit(self.codec.clip_saturation) if self.codec is not None else None
            )

    def _prep_fn(self, rho0, mask, jids, kr):
        r = rho0 * mask
        total = jnp.sum(r)
        rhos_eff = jnp.where(total > 0, r / jnp.maximum(total, 1e-12), 0.0)
        keys = jax.vmap(lambda i: jax.random.fold_in(kr, i))(jids)
        return rhos_eff, keys

    # -- client side --------------------------------------------------------

    def _grad_blocks_fn(self, params, batch):
        """(C, ...) cohort batch -> (C, nb, N) gradient blocks, one vmapped
        device pass, ``lax.scan``-chunked when ``cohort.chunk`` bounds how
        many per-client gradient trees materialize at once.  Both impls share
        this pass — the gradient is the *model's* work; the engine's claim
        (and the loop oracle) is about the per-client codec path."""
        vm = jax.vmap(
            lambda b: self.layout.to_blocks(self.grad_fn(params, b))
        )
        leaves = jax.tree_util.tree_leaves(batch)
        c = leaves[0].shape[0]
        chunk = self.cohort.chunk
        if chunk <= 0 or chunk >= c:
            return vm(batch)
        nch = -(-c // chunk)
        pad = nch * chunk - c

        def chunked(x):  # padded slots replay client 0; outputs sliced off
            xp = jnp.concatenate([x, jnp.repeat(x[:1], pad, axis=0)]) if pad else x
            return xp.reshape((nch, chunk) + x.shape[1:])

        _, blocks = jax.lax.scan(
            lambda _, b: (None, vm(b)), None, jax.tree_util.tree_map(chunked, batch)
        )
        return blocks.reshape((nch * chunk, self.nb, self.n))[:c]

    def _grads_tree_fn(self, params, batch):
        """(C, ...) cohort batch -> batched gradient TREE (leaves keep their
        model shapes under a leading client axis) -- the streamed pass slices
        layout segments out of this instead of one monolithic block grid.
        ``cohort.grad_accum`` > 1 splits each client's samples into that many
        microbatches and averages the gradients through a ``lax.scan``, so
        per-client activation memory is bounded alongside the encoder's
        segment bound."""
        vg = jax.vmap(lambda b: self.grad_fn(params, b))
        acc = self.cohort.grad_accum
        if acc <= 1:
            return vg(batch)
        leaves = jax.tree_util.tree_leaves(batch)
        c, bsz = leaves[0].shape[0], leaves[0].shape[1]
        if bsz % acc:
            raise ValueError(
                f"grad_accum={acc} must divide the per-client batch size {bsz}"
            )
        mb = bsz // acc

        def split(x):  # (C, b, ...) -> (acc, C, b/acc, ...)
            return x.reshape((c, acc, mb) + x.shape[2:]).swapaxes(0, 1)

        mbatches = jax.tree_util.tree_map(split, batch)
        first = jax.tree_util.tree_map(lambda x: x[0], mbatches)
        rest = jax.tree_util.tree_map(lambda x: x[1:], mbatches)
        gsum, _ = jax.lax.scan(
            lambda carry, b: (
                jax.tree_util.tree_map(jnp.add, carry, vg(b)),
                None,
            ),
            vg(first),
            rest,
        )
        return jax.tree_util.tree_map(lambda g: g / acc, gsum)

    def _grad_segments(self, params, batch):
        """Segment source for the streamed client pass: yields
        ``(segment index, (C, rows, N) blocks)`` in any order.  The default
        runs one batched gradient pass (grad_accum-microbatched) and slices
        each layout segment out of the gradient tree; a custom
        ``grad_segments_fn(params, batch, layout)`` can instead yield
        segments as the backward pass produces them -- encode of layer L
        overlapping backprop of layer L-1 -- which is the interleave hook the
        LLM-scale pipeline plugs into."""
        if self._grad_segments_fn is not None:
            yield from self._grad_segments_fn(params, batch, self.layout)
            return
        grads = self._grads_tree_jit(params, batch)
        for seg in self.layout.segments:
            yield seg.index, self._seg_blocks_jit(grads, seg.index)

    def _encode_segment_fn(self, blocks, residual, rho, s):
        """One client's codec path for ONE layout segment: (rows, N) blocks
        + matching residual rows -> wire payload rows.  Every codec stage is
        per-block, so the segment outputs concatenate bit-identically to the
        whole-grid encode; ``s`` is the segment's static top-S budget."""
        if self.cohort.method == "fedqcs-ea" or (
            self.cohort.method == "fedqcs-ae" and self.stream is not None
        ):
            words, alpha, enc_res = self.codec.compress_blocks_packed(
                blocks, residual, s=s
            )
            payload = {"words": words, "alpha": alpha}
        else:  # fedqcs-ae / qcs-qiht barrier rounds consume the index view
            codes, alpha, enc_res = self.codec.compress_blocks(blocks, residual, s=s)
            payload = {"codes": codes, "alpha": alpha}
        new_res = jnp.where(rho > 0, enc_res, blocks + residual)
        return payload, new_res

    def _client_pass_streamed(self, params, batch, residuals, rhos, rhos_nmse):
        """Segment-streamed client pass (``cohort.encode_stream``): consumes
        the gradient one layout segment at a time, so the encoder's live
        block state is one segment's ``(C, rows, N)`` -- bounded by the
        largest segment -- never the whole ``(C, nb, N)`` grid.  Wire output
        is bit-identical to the one-pass encode (pinned by test).  nmse
        bookkeeping folds into a running ``(nb, N)`` true_sum instead of
        carrying every client's full blocks to the PS."""
        nseg = len(self.layout.segments)
        pay: List[Any] = [None] * nseg
        res: List[Any] = [None] * nseg
        tsum: List[Any] = [None] * nseg
        seg_s = self.layout.segment_s(self.fed_cfg.s)
        # Spans here are host wall-clock around ASYNC dispatch: "backward" is
        # the time the producer spends inside next() (for an interleaved
        # grad_segments_fn, one stage's VJP dispatch), "encode_overlap" the
        # encode dispatch riding on top of it -- the overlap the interleave
        # buys shows up as encode_overlap << a blocking encode would be.
        it = self._grad_segments(params, batch)
        while True:
            with span("backward", self._spans):
                nxt = next(it, None)
            if nxt is None:
                break
            idx, seg_blocks = nxt
            if not 0 <= idx < nseg:
                raise ValueError(
                    f"grad_segments_fn yielded segment index {idx}, layout "
                    f"has {nseg} segments"
                )
            if pay[idx] is not None:
                raise ValueError(
                    f"grad_segments_fn yielded segment {idx} "
                    f"({self.layout.segments[idx].name!r}) twice -- a second "
                    "payload would silently drop the first from the wire"
                )
            seg = self.layout.segments[idx]
            with span("encode_overlap", self._spans):
                pay[idx], res[idx] = self._encode_seg_jit(
                    seg_blocks, residuals[:, seg.row_slice], rhos, seg_s[idx]
                )
                if self.cohort.record_nmse:
                    tsum[idx] = self._seg_true_sum_jit(rhos_nmse, seg_blocks)
        missing = [i for i, p in enumerate(pay) if p is None]
        if missing:
            raise ValueError(f"grad_segments_fn never yielded segments {missing}")
        payloads = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=1), *pay
        )
        new_res = jnp.concatenate(res, axis=1)
        if self.cohort.record_nmse:
            payloads = dict(payloads, true_sum=jnp.concatenate(tsum, axis=0))
        return payloads, new_res

    def _encode_fn(self, blocks, residual, rho, key):
        """One client's codec path: (nb, N) blocks -> method payload.

        ``rho`` is the client's effective weight (0 = dropped/outage: the
        error-feedback residual then absorbs the full carry so nothing is
        lost).  ``key`` seeds per-client randomness (dither)."""
        payload: Dict[str, jnp.ndarray] = {}
        method = self.cohort.method
        if method == "fedqcs-ea" or (method == "fedqcs-ae" and self.stream is not None):
            # EA -- and every streaming round -- consumes the wire words
            # directly (packed reconstruction engine / streaming ingest,
            # DESIGN.md #Recon-engine, #Streaming-PS): the payload carries
            # what crosses the wire and the uint8 index view never
            # materializes.
            words, alpha, enc_res = self.codec.compress_blocks_packed(blocks, residual)
            payload["words"], payload["alpha"] = words, alpha
            new_res = jnp.where(rho > 0, enc_res, blocks + residual)
        elif method in EF_METHODS:
            codes, alpha, enc_res = self.codec.compress_blocks(blocks, residual)
            payload["codes"], payload["alpha"] = codes, alpha
            new_res = jnp.where(rho > 0, enc_res, blocks + residual)
        elif method == "qcs-dither":
            dn = self.cohort.dither_n
            nb2 = -(-self.nbar // dn)
            flat = blocks.reshape(-1)[: self.nbar]
            carry = jnp.pad(flat, (0, nb2 * dn - self.nbar)).reshape(nb2, dn)
            q, delta, dith = self._dither.compress(carry, key)
            recon = self._dither.reconstruct(q, delta, dith).reshape(-1)[: self.nbar]
            payload["recon"] = jnp.pad(
                recon, (0, self.nb * self.n - self.nbar)
            ).reshape(self.nb, self.n)
            new_res = residual
        elif method == "signsgd":
            payload["signs"] = baselines.signsgd_compress(blocks)
            new_res = residual
        else:  # none
            new_res = residual
        return payload, new_res

    def _client_pass(self, params, batch, residuals, rhos, keys, rhos_nmse=None):
        """Gradients (always batched) + encode (vmapped, or the per-client
        Python-loop oracle).  The two impls are bit-identical: they share the
        gradient pass, and the per-client encode touches only its own row.
        ``rhos_nmse`` is the normalized weighting the nmse reference uses
        when it differs from ``rhos`` (streaming rounds pass raw weights)."""
        if self.cohort.encode_stream:
            return self._client_pass_streamed(
                params, batch, residuals, rhos,
                rhos_nmse if rhos_nmse is not None else rhos,
            )
        blocks = self._grads_jit(params, batch)
        if self.cohort.impl == "loop":
            outs = [
                self._encode_jit(blocks[i], residuals[i], rhos[i], keys[i])
                for i in range(int(rhos.shape[0]))
            ]
            payloads = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[o[0] for o in outs]
            )
            new_res = jnp.stack([o[1] for o in outs])
        else:
            payloads, new_res = self._encode_vmap_jit(blocks, residuals, rhos, keys)
        method = self.cohort.method
        if self.cohort.record_nmse or method in ("none", "signsgd"):
            payloads = dict(payloads, blocks=blocks)
        return payloads, new_res

    # -- PS side ------------------------------------------------------------

    def _ps_fn(self, payloads, rhos_eff, chan, key):
        """Reconstruction once per round from the stacked cohort payloads.
        ``chan`` is the round's full ChannelRealization; for fedqcs-ae over a
        per-client noisy uplink its effective variance threads into em_gamp's
        noise_var next to the Bussgang term and the received measurements get
        a matching noise draw (faithful simulation, not just a variance
        hint); over a multiple-access uplink the PS sees only the
        superimposed ``fam.transmit`` output and joint-estimates the
        aggregate through ``fam.combine``."""
        method = self.cohort.method
        stats: Dict[str, jnp.ndarray] = {}
        if self._collect and self.codec is not None:
            # quantizer clip-saturation rate off the wire payload (scalar
            # families; vq reports 0 -- see BQCSCodec.clip_saturation)
            if "words" in payloads:
                stats["clip_saturation"] = self.codec.clip_saturation(payloads["words"])
            elif "codes" in payloads:
                stats["clip_saturation"] = self.codec.clip_saturation(
                    payloads["codes"], packed=False
                )
        true_sum = None
        if "true_sum" in payloads:  # encode_stream folded it per segment
            true_sum = payloads["true_sum"]
        elif "blocks" in payloads:
            true_sum = jnp.einsum("k,kbn->bn", rhos_eff, payloads["blocks"])
        if method == "none":
            ghat = true_sum
        elif method == "signsgd":
            # unweighted majority vote (the baseline's defining semantics);
            # rho_k = 0 clients abstain (their signs are zeroed out)
            alive = (rhos_eff > 0).astype(jnp.int8)[:, None, None]
            scale = jnp.mean(jnp.abs(true_sum))
            ghat = baselines.signsgd_aggregate(payloads["signs"] * alive, lr_scale=scale)
        elif method == "qcs-dither":
            ghat = jnp.einsum("k,kbn->bn", rhos_eff, payloads["recon"])
        elif method == "qcs-qiht":
            codes, alphas = payloads["codes"], payloads["alpha"]
            c, nb, m = codes.shape
            parts = baselines.qiht_reconstruct(
                codes.reshape(c * nb, m), alphas.reshape(-1),
                self.codec.a, self.codec.codebook, self.fed_cfg.s,
            )
            ghat = jnp.einsum("k,kbn->bn", rhos_eff, parts.reshape(c, nb, -1))
        elif method == "fedqcs-ea":
            # Packed-domain chunked EA decode (words straight from the client
            # pass; chunking per FedQCSConfig.recon_chunk).
            ghat = estimate_and_aggregate_packed(
                self.codec, payloads["words"], payloads["alpha"], rhos_eff,
                self.gamp, with_info=self._collect,
            )
            if self._collect:
                ghat, ginfo = ghat
                stats.update(gamp_health(ginfo, live=payloads["alpha"] > 0))
        else:  # fedqcs-ae
            codes, alphas = payloads["codes"], payloads["alpha"]
            q = self.codec.codebook
            fam = self._chan_family
            nu_q = bussgang.effective_noise_var(alphas, rhos_eff, q)
            stats["nu_quant"] = jnp.mean(nu_q)
            if fam.exact_codes:
                stats["nu_channel"] = jnp.zeros(())
                ghat = aggregate_and_estimate(
                    self.codec, codes, alphas, rhos_eff,
                    groups=self.cohort.groups, gamp=self.gamp,
                    with_info=self._collect,
                )
                if self._collect:
                    ghat, ginfo = ghat
                    stats.update(gamp_health(ginfo))
            else:
                m = self.fed_cfg.m
                with jax.named_scope("dequantize"):
                    deq = self.codec.dequantize(codes)  # (C, nb, M)
                w = bussgang.bussgang_weight(rhos_eff[:, None], alphas, q)  # (C, nb)
                if fam.multiple_access:
                    # Over-the-air joint estimation: every client pre-scales
                    # by its Bussgang weight (rho is PS-broadcast, alpha is
                    # client-local -- no per-client side channel) times the
                    # round's broadcast power-control scalar (mimo_tx_gain:
                    # unit average power on the air) and transmits
                    # SIMULTANEOUSLY; non-participants carry w = 0 rows.
                    # The PS spatially combines the one superimposed
                    # reception into the aggregate observation + its
                    # effective noise.
                    active = (rhos_eff > 0).astype(jnp.float32)
                    eta = mimo_tx_gain(w, active)
                    x = (eta * w)[..., None] * deq  # (C, nb, M) transmit rows
                    y_rx = fam.transmit(self.chan, chan, x, key)
                    if self._collect:
                        # combiner-health aux (CSI mismatch, ||f||^2) is part
                        # of the combine hook protocol -- no kind dispatch
                        y, nu_ch, ch_aux = fam.combine(
                            self.chan, chan, y_rx, w, active,
                            psi=q.psi, tx_gain=eta, with_aux=True,
                        )
                        stats.update(ch_aux)
                    else:
                        y, nu_ch = fam.combine(self.chan, chan, y_rx, w, active,
                                               psi=q.psi, tx_gain=eta)
                else:
                    # Per-client reception: equalized rows + their effective
                    # variance, Bussgang-combined at the PS (eq. 23/24 +
                    # channel term).
                    nu_chan = fam.effective_noise(chan)
                    y_rx = fam.transmit(self.chan, chan, deq, key)
                    y = jnp.sum(w[..., None] * y_rx, axis=0)
                    nu_ch = jnp.sum(jnp.square(w) * nu_chan, axis=0)  # (nb,)
                stats["nu_channel"] = jnp.mean(nu_ch)
                energy = bussgang.signal_energy(alphas, rhos_eff, m, self.n)
                with jax.named_scope("gamp"):
                    ghat = em_gamp(
                        y, nu_q + nu_ch, self.codec.a, self.gamp,
                        init_var=energy, use_pallas=self.fed_cfg.use_kernels,
                        with_info=self._collect,
                    )
                if self._collect:
                    ghat, ginfo = ghat
                    stats.update(gamp_health(ginfo))
        if self.cohort.record_nmse and true_sum is not None and method != "none":
            num = jnp.sum(jnp.square(ghat - true_sum))
            den = jnp.sum(jnp.square(true_sum)) + 1e-30
            stats["nmse"] = num / den
        return ghat, stats

    # -- round loop ---------------------------------------------------------

    def _staleness(self, prev_sched, ids, t) -> np.ndarray:
        """Cohort staleness at selection time: rounds since each member's
        last successful participation (0 for never-participated), mirroring
        the async scheduler's discount input."""
        last = prev_sched.last_round[ids]
        return np.where(last < 0, 0, t - 1 - last)

    def _wire_up_bytes(self, participating: float):
        """Uplink wire cost this round: what the participants' payloads cost
        on the canonical wire (packed words + one f32 alpha per block for the
        fedqcs/qiht families, 1 bit/entry for signsgd; None where the method
        has no defined wire format)."""
        method = self.cohort.method
        if self.codec is not None and method in EF_METHODS:
            q = self.codec.codebook
            w = packed_width(q.n_codes(self.fed_cfg.m), q.bits)
            return participating * self.nb * (w * 32 + 32) / 8.0
        if method == "signsgd":
            return participating * self.nb * self.n / 8.0
        return None

    def _record_round(self, t, out, staleness, ghat_blocks) -> None:
        """Assembles and records the round event (host side, once per round).
        The event is a superset of the returned stats dict: envelope fields
        come from the recorder, wire/norm/staleness/phase timings here."""
        event: Dict[str, Any] = dict(out)
        event["round"] = t
        event["staleness_mean"] = float(np.mean(staleness)) if len(staleness) else 0.0
        wire = self._wire_up_bytes(out["participating"])
        if wire is not None:
            event["wire_up_bytes"] = wire
            if self.codec is not None and len(self.layout.segments) > 1:
                # per-tensor wire accounting: each layout segment's share of
                # the uplink (rows scale the same packed-words-per-row cost;
                # pad rows are wire overhead the monolithic layout wouldn't
                # pay, so they're itemized per segment)
                q = self.codec.codebook
                w = packed_width(q.n_codes(self.fed_cfg.m), q.bits)
                event["wire_segments"] = [
                    {
                        "name": seg.name,
                        "rows": seg.rows,
                        "pad": seg.pad,
                        "bytes": out["participating"] * seg.rows * (w * 32 + 32) / 8.0,
                    }
                    for seg in self.layout.segments
                ]
        # model broadcast: every cohort member pulls the nbar f32 params
        event["wire_down_bytes"] = float(out["cohort"]) * self.nbar * 4.0
        un, pn = self._norms_jit(ghat_blocks, self.params)
        event["update_norm"], event["param_norm"] = float(un), float(pn)
        phase = self._spans.drain()
        event["phase_ms"] = phase
        # backward/encode_overlap nest inside client_pass: don't double-count
        event["round_ms"] = sum(
            v for k, v in phase.items() if k not in SUB_PHASES
        )
        self.obs.record("round", event)

    def run_round(self, inspect: Optional[Callable[[Any, Any], None]] = None) -> Dict[str, float]:
        """One federated round; advances params/residuals/server state and
        returns the round's stats (python floats).  ``inspect``, when given,
        is called with the round's stacked cohort payloads and the decoded
        aggregate blocks (nb, N), before the server update applies them."""
        with round_step(self.round):
            if self.stream is not None:
                return self._run_round_streaming(inspect)
            return self._run_round_barrier(inspect)

    def _run_round_barrier(self, inspect=None) -> Dict[str, float]:
        t = self.round
        with span("select", self._spans):
            prev_sched = self.sched_state
            ids, rho0, new_sched = select_cohort(
                self.sched, prev_sched, t, self.data.counts
            )
            stale = self._staleness(prev_sched, ids, t) if self._collect else ()
            kr = jax.random.fold_in(self.key, t)
            k_chan, k_noise = jax.random.split(kr)
        with span("uplink", self._spans):
            chan = self._uplink_jit(k_chan, len(ids), self.nb)
            if self._collect:
                jax.block_until_ready(chan)
        # Channel outage is a failed participation: un-stamp those clients so
        # the async staleness discount sees their true last *successful*
        # round (their residual carries the full gradient meanwhile).
        with span("sync", self._spans):
            mask = np.asarray(chan.mask)
        dead = ids[mask == 0]
        if len(dead):
            new_sched.last_round[dead] = prev_sched.last_round[dead]
        self.sched_state = new_sched
        with span("select", self._spans):
            jids = jnp.asarray(ids)
            rhos_eff, keys = self._prep_jit(jnp.asarray(rho0), chan.mask, jids, kr)

        with span("client_pass", self._spans):
            batch = self.data.cohort_batch(t, ids)
            res_c = self.residuals[jids]
            payloads, new_res = self._client_pass(
                self.params, batch, res_c, rhos_eff, keys
            )
            del res_c  # the cohort's residual rows are consumed: free them
            if self._collect:
                jax.block_until_ready(payloads)
        with span("decode", self._spans):
            ghat_blocks, stats = self._ps_jit(payloads, rhos_eff, chan, k_noise)
            if self._collect:
                jax.block_until_ready(ghat_blocks)
        if inspect is not None:
            inspect(payloads, ghat_blocks)
        del payloads  # wire + nmse reference: decoded, no longer needed

        with span("apply", self._spans):
            self.residuals = self._scatter_jit(self.residuals, jids, new_res)
            del new_res
            self.params, self.server_state = self._apply_jit(
                ghat_blocks, self.params, self.server_state, t
            )
            if self._collect:
                jax.block_until_ready(self.params)
        self.round = t + 1
        with span("sync", self._spans):
            out = {k: float(v) for k, v in stats.items()}
            out["participating"] = float(jnp.sum(rhos_eff > 0))
        out["cohort"] = len(ids)
        if self._collect:
            self._record_round(t, out, stale, ghat_blocks)
        return out

    def _run_round_streaming(self, inspect=None) -> Dict[str, float]:
        """Streaming round mode (DESIGN.md #Streaming-PS): same client pass,
        but the PS folds arrival-ordered sub-cohort payload batches through
        the bounded ingest buffer into partial sufficient statistics instead
        of one barrier decode.  Missed-deadline clients are non-participants:
        weight 0 (full residual carry) and un-stamped, exactly like channel
        outage."""
        t = self.round
        with span("select", self._spans):
            prev_sched = self.sched_state
            ids, rho0, new_sched = select_cohort(
                self.sched, prev_sched, t, self.data.counts
            )
            stale = self._staleness(prev_sched, ids, t) if self._collect else ()
            kr = jax.random.fold_in(self.key, t)
            k_chan, k_noise = jax.random.split(kr)
        with span("uplink", self._spans):
            chan = self._uplink_jit(k_chan, len(ids), self.nb)
            if self._collect:
                jax.block_until_ready(chan)
        with span("sync", self._spans):
            mask = np.asarray(chan.mask)
        alive = (np.asarray(rho0) > 0) & (mask > 0)
        times = simulate_arrivals(self.stream, t, len(ids), alive)
        arrived = times <= self.stream.deadline
        # Raw (unnormalized) weights: scheduler rho x channel mask x arrival
        # x lateness discount; normalization happens at finalize (1/W).
        w_raw = (
            np.asarray(rho0, np.float64) * mask * arrived * late_discount(self.stream, times)
        ).astype(np.float32)
        # Channel outage OR missed deadline = failed participation: un-stamp.
        dead = ids[(mask == 0) | ~arrived]
        if len(dead):
            new_sched.last_round[dead] = prev_sched.last_round[dead]
        self.sched_state = new_sched
        with span("select", self._spans):
            jids = jnp.asarray(ids)
            jw = jnp.asarray(w_raw)
            # _prep_fn's normalization of the raw weights is exactly the nmse
            # reference weighting; the mask is already folded into w_raw.
            rhos_eff, keys = self._prep_jit(jw, jnp.ones_like(jw), jids, kr)

        with span("client_pass", self._spans):
            batch = self.data.cohort_batch(t, ids)
            res_c = self.residuals[jids]
            payloads, new_res = self._client_pass(
                self.params, batch, res_c, jw, keys, rhos_nmse=rhos_eff
            )
            del res_c
            if self._collect:
                jax.block_until_ready(payloads)

        fam = self._chan_family
        nu_chan = noise_keys = chan_real = chan_key = None
        if fam.multiple_access:
            # Each arrival batch is one superimposed sub-cohort reception
            # over this round's H (the aggregator-tree tiers fold exactly
            # that); the receiver noise key is per admitted batch.
            chan_real, chan_key = chan, k_noise
        elif not fam.exact_codes:
            nu_chan = fam.effective_noise(chan)
            noise_keys = self._noise_keys_jit(jids, k_noise)
        batches = batch_arrivals(times, self.stream.deadline, self.stream.batch_clients)
        with span("fold", self._spans):
            ghat_blocks, sinfo = stream_decode(
                self.codec, payloads["words"], payloads["alpha"], w_raw, batches,
                nu_chan=nu_chan, noise_keys=noise_keys,
                chan_real=chan_real, chan_key=chan_key, ps=self._stream_ps,
            )
            if self._collect:
                jax.block_until_ready(ghat_blocks)
        if inspect is not None:
            inspect(payloads, ghat_blocks)

        with span("apply", self._spans):
            self.residuals = self._scatter_jit(self.residuals, jids, new_res)
            del new_res
            self.params, self.server_state = self._apply_jit(
                ghat_blocks, self.params, self.server_state, t
            )
            if self._collect:
                jax.block_until_ready(self.params)
        self.round = t + 1
        with span("sync", self._spans):
            out = {
                k: float(v)
                for k, v in sinfo.items()
                if k not in ("participating",)  # recomputed below for parity
            }
            if self.cohort.record_nmse:
                if "true_sum" in payloads:
                    out["nmse"] = float(
                        self._nmse_true_jit(ghat_blocks, payloads["true_sum"])
                    )
                else:
                    out["nmse"] = float(
                        self._nmse_jit(ghat_blocks, payloads["blocks"], rhos_eff)
                    )
            if self._collect and self._sat_jit is not None:
                out["clip_saturation"] = float(self._sat_jit(payloads["words"]))
        out["cohort"] = len(ids)
        out["participating"] = float(np.sum(w_raw > 0))
        out["arrived"] = float(np.sum(arrived))
        if self._collect:
            self._record_round(t, out, stale, ghat_blocks)
        return out

    def run(self, rounds: int) -> List[Dict[str, float]]:
        return [self.run_round() for _ in range(rounds)]


def _scatter_residuals(residuals, jids, rows):
    """Writes the cohort's new residual rows back into the registry."""
    return residuals.at[jids].set(rows)


# ---------------------------------------------------------------------------
# Interleaved producer factory
# ---------------------------------------------------------------------------


def make_interleaved_segments(
    model_cfg: Any,
    layout: GradientLayout,
    grad_accum: int = 1,
    layer_chunks: int = 1,
):
    """``grad_segments_fn`` that interleaves encode with backprop
    (DESIGN.md #Interleave): yields each layout segment's ``(C, rows, N)``
    blocks as the corresponding layer cotangents are produced -- backward
    order -- so encode of layer L dispatches while L-1 backprops and the
    full gradient pytree never materializes.  Works for every staged
    registry family (transformer/moe/vlm/ssm/hybrid); build ``layout``
    with :func:`repro.models.segment_tap.interleaved_layout` (same
    ``layer_chunks``) and pass BOTH it and the returned producer to
    :class:`CohortEngine` with ``encode_stream=True``.  ``grad_accum``
    must mirror ``CohortConfig.grad_accum`` -- the producer microbatches
    each stage exactly like the one-pass tree fn.  The returned object
    also exposes ``grads_fn``/``peak_live_grad_bytes`` (the bit-identity
    oracle and the live-bytes bound the interleave bench records)."""
    from repro.models.segment_tap import InterleavedSegments

    return InterleavedSegments(
        model_cfg, layout, grad_accum=grad_accum, layer_chunks=layer_chunks
    )


# ---------------------------------------------------------------------------
# Smoke entry point (CI minimal-deps leg): a tiny synthetic cohort end to end.
#     PYTHONPATH=src python -m repro.fed.engine --clients 8 --rounds 2
# ---------------------------------------------------------------------------


def _smoke_main(argv=None):
    import argparse

    from repro.fed.partition import PartitionConfig, partition_indices
    from repro.fed.toy import toy_classification, toy_loss, toy_params

    ap = argparse.ArgumentParser(description="cohort engine smoke")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--sample-frac", type=float, default=1.0)
    ap.add_argument("--alpha", type=float, default=0.3)
    ap.add_argument("--snr-db", type=float, default=None)
    ap.add_argument(
        "--channel", default=None, choices=sorted(CHANNEL_FAMILIES),
        help="uplink family (default: awgn when --snr-db is set, else ideal)",
    )
    ap.add_argument("--n-rx", type=int, default=8, help="mimo_mac receive antennas")
    ap.add_argument("--csi-error", type=float, default=0.0,
                    help="mimo_mac CSI estimate error variance")
    ap.add_argument("--method", default="fedqcs-ae", choices=METHODS)
    ap.add_argument("--chunk", type=int, default=0)
    ap.add_argument(
        "--layout", default="monolithic", choices=("monolithic", "per_tensor"),
        help="gradient block layout (per_tensor = independently padded leaf segments)",
    )
    ap.add_argument(
        "--encode-stream", action="store_true",
        help="stream the client encode one layout segment at a time",
    )
    ap.add_argument(
        "--grad-accum", type=int, default=1,
        help="microbatches for the encode-stream gradient hook",
    )
    ap.add_argument(
        "--stream", type=int, default=0, metavar="BATCH",
        help="streaming PS mode: sub-cohort ingest batch size (0 = barrier round)",
    )
    ap.add_argument("--deadline", type=float, default=8.0)
    ap.add_argument(
        "--record", default=None, metavar="RUN_DIR",
        help="write events.jsonl + meta.json to this run dir (repro.obs)",
    )
    args = ap.parse_args(argv)

    recorder = None
    if args.record:
        from repro.obs import JsonlRecorder

        recorder = JsonlRecorder(args.record, config=vars(args))

    x, y = toy_classification()
    parts = partition_indices(
        y, args.clients, PartitionConfig(kind="dirichlet", alpha=args.alpha, min_size=4)
    )
    engine = CohortEngine(
        toy_params(),
        jax.grad(toy_loss),
        ArrayClientData(x, y, parts, batch_size=4),
        fed_cfg=FedQCSConfig(block_size=64, reduction_ratio=2, bits=3, gamp_iters=10),
        cohort=CohortConfig(
            method=args.method, chunk=args.chunk, layout=args.layout,
            encode_stream=args.encode_stream, grad_accum=args.grad_accum,
        ),
        sched=SchedulerConfig(
            kind="uniform" if args.sample_frac < 1.0 else "full",
            sample_frac=args.sample_frac,
        ),
        chan=ChannelConfig(
            kind=args.channel
            or ("awgn" if args.snr_db is not None else "ideal"),
            snr_db=args.snr_db if args.snr_db is not None else 20.0,
            n_rx=args.n_rx,
            csi_error=args.csi_error,
        ),
        server=ServerOptConfig(kind="fedadam", lr=0.01),
        stream=StreamConfig(batch_clients=args.stream, deadline=args.deadline)
        if args.stream > 0
        else None,
        obs=recorder,
    )
    for i, stats in enumerate(engine.run(args.rounds)):
        print("round", i, stats)
        assert all(np.isfinite(v) for v in stats.values()), stats
    if recorder is not None:
        recorder.close()
        print("recorded:", recorder.run_dir)
    print("smoke ok:", args.clients, "clients,", args.rounds, "rounds")


if __name__ == "__main__":
    _smoke_main()
