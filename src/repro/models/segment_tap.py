"""Backward-interleaved gradient segments (DESIGN.md #Interleave).

The engine's default streamed producer (``CohortEngine._grad_segments``)
materializes the whole batched gradient tree before the first layout segment
reaches the encoder: peak client memory carries every gradient leaf plus the
encoder state.  Nothing forces that -- reverse-mode AD produces cotangents
LAYER BY LAYER, last layer first.  This module taps that order:

  * Every registry train_loss is (since this module landed) a composition of
    **stage functions** -- ``embed_stage -> stack_stage* -> head_stage`` --
    with signature ``(params-subtree, carry, ctx) -> carry'`` (see the
    per-family modules).  ``train_loss`` itself calls them, so the staged
    forward traces the same ops as the monolithic one.
  * :class:`InterleavedSegments` replays those stages under per-stage
    ``jax.vjp``: one forward sweep saves the stage-boundary activations, then
    the backward sweep walks the stages in reverse, emitting each stage's
    parameter gradients the moment its cotangents exist.  A static **plan**
    maps stage gradients onto layout-segment slots (a stacked layer chunk ->
    its sliced segment; the tied embedding -> a SUM of the embed and head
    stage contributions), and a segment is yielded as soon as its last
    contribution arrives -- backward order, i.e. out-of-order w.r.t. the
    layout, which the engine's ``grad_segments_fn`` contract already accepts.
    Encode of stage k's segments is dispatched (JAX async dispatch) while
    stage k-1's VJP runs; the full gradient pytree never exists.
  * Stage-boundary carries and cotangents are **donated** through the
    backward jits -- each is consumed exactly once -- so the live set at any
    instant is: the remaining boundary activations, one stage's gradients,
    the pending cross-stage accumulators (tied embeddings), and the
    in-flight encode buffers.  :meth:`peak_live_grad_bytes` computes that
    bound from the plan; the ``--only interleave`` bench measures against
    it.

**Bit-identity contract.** The wire produced through this producer is
bit-identical to the one-pass encode *of the gradients this producer
computes* (:meth:`grads_fn` -- same stage VJPs, tree materialized then
sliced): every segment's blocks are assembled from literally the same piece
arrays in both paths, and concat/slice/cast/pad are value-exact.  Staged
VJPs are NOT bitwise equal to the monolithic ``jax.jit(jax.grad(loss))`` --
XLA fuses the two programs differently, giving ~1e-8 relative differences --
so equivalence to the default engine path is pinned at allclose, and wire
bit-identity is pinned against :meth:`grads_fn` (same style as the PR-9
streamed-vs-one-pass test, which held the gradients fixed).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.layout import GradientLayout, _leaf_size
from repro.obs.trace import CLIENT, scoped

__all__ = [
    "Stage",
    "build_stages",
    "interleaved_layout",
    "InterleavedSegments",
]


@dataclasses.dataclass(frozen=True)
class Stage:
    """One link of the staged train loss.

    ``select(params)`` picks the parameter subtree this stage's forward
    reads; ``fwd(sp, carry, ctx)`` advances the activation carry (``carry``
    is ignored when ``has_carry`` is False -- the embed stage).  ``ranges``
    aligns ``jax.tree_util.tree_leaves(select(params))`` with the FULL
    parameter tree: entry i says stage-gradient leaf i is the flat scalar
    span ``[lo, hi)`` of the full-tree leaf named ``name`` (keystr path).
    A layer-chunk stage's spans cover only its chunk's rows; shared leaves
    (tied embedding) appear in several stages' ranges with identical spans
    and their gradients SUM.
    """

    name: str
    select: Callable[[Any], Any]
    fwd: Callable[[Any, Any, Dict[str, Any]], Any]
    ranges: Tuple[Tuple[str, int, int], ...]
    has_carry: bool = True


def _chunk_bounds(n_layers: int, chunks: int) -> List[Tuple[int, int]]:
    """Near-even [lo, hi) partition of the stacked layer axis."""
    chunks = max(1, min(int(chunks), n_layers))
    base, rem = divmod(n_layers, chunks)
    bounds, lo = [], 0
    for i in range(chunks):
        hi = lo + base + (1 if i < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def _abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct parameter tree -- geometry without allocating."""
    from repro.models import model as model_api

    return jax.eval_shape(lambda: model_api.init_params(cfg, jax.random.PRNGKey(0)))


def _subtree_ranges(
    subtree: Any,
    rename: Callable[[str], str],
    lo_hi: Optional[Tuple[int, int]] = None,
) -> Tuple[Tuple[str, int, int], ...]:
    """Ranges aligned with ``tree_leaves(subtree)``.  With ``lo_hi`` the
    subtree is the FULL stacked tree and each leaf's span is its
    ``[lo, hi)`` axis-0 slice."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(subtree)[0]:
        name = rename(jax.tree_util.keystr(path))
        size = _leaf_size(leaf.shape)
        if lo_hi is None:
            out.append((name, 0, size))
        else:
            lo, hi = lo_hi
            stride = size // leaf.shape[0]
            out.append((name, lo * stride, hi * stride))
    return tuple(out)


def _stack_chunk_stages(
    aparams_stack: Any,
    key: str,
    fwd_of_chunk: Callable[..., Any],
    layer_chunks: int,
) -> List[Stage]:
    """Per-chunk stages over one stacked (L, ...) parameter subtree."""
    n_layers = jax.tree_util.tree_leaves(aparams_stack)[0].shape[0]
    stages = []
    for lo, hi in _chunk_bounds(n_layers, layer_chunks):
        stages.append(
            Stage(
                name=f"{key}[{lo}:{hi}]",
                select=lambda p, lo=lo, hi=hi: jax.tree_util.tree_map(
                    lambda v: v[lo:hi], p[key]
                ),
                fwd=fwd_of_chunk,
                ranges=_subtree_ranges(
                    aparams_stack, lambda s: f"['{key}']" + s, (lo, hi)
                ),
            )
        )
    return stages


def build_stages(
    cfg: ModelConfig, aparams: Any, layer_chunks: int = 1
) -> Tuple[List[Stage], Callable[[Any, ModelConfig], Dict[str, Any]]]:
    """(forward-order stages, train_ctx fn) for one registry family.

    ``layer_chunks`` splits the main stacked run into that many stages so
    gradients stream out mid-stack; the hybrid family's weight-shared
    attention block ties every group together, so its stack is always ONE
    stage (chunking would re-associate the shared block's gradient sum and
    break bit-identity with train_loss).
    """
    fam = cfg.family
    embed_size = _leaf_size(aparams["tok"]["embed"].shape)
    if fam in ("dense", "moe", "vlm"):
        from repro.models import transformer as tf

        stages = [
            Stage(
                name="embed",
                select=lambda p: {"embed": p["tok"]["embed"]},
                fwd=lambda sp, x, ctx: tf.embed_stage(sp, ctx, cfg),
                ranges=(("['tok']['embed']", 0, embed_size),),
                has_carry=False,
            )
        ]
        if "layers_dense" in aparams:
            stages.append(
                Stage(
                    name="layers_dense",
                    select=lambda p: p["layers_dense"],
                    fwd=lambda sp, x, ctx: tf.stack_stage(sp, x, ctx, cfg, moe=False),
                    ranges=_subtree_ranges(
                        aparams["layers_dense"], lambda s: "['layers_dense']" + s
                    ),
                )
            )
        stages += _stack_chunk_stages(
            aparams["layers"],
            "layers",
            lambda sp, x, ctx: tf.stack_stage(sp, x, ctx, cfg, moe=cfg.is_moe),
            layer_chunks,
        )
        stages.append(
            Stage(
                name="head",
                select=lambda p: tf.head_params(p, cfg),
                fwd=lambda sp, x, ctx: tf.head_stage(sp, x, ctx, cfg),
                ranges=_subtree_ranges(tf.head_params(aparams, cfg), lambda s: s),
            )
        )
        return stages, tf.train_ctx
    if fam == "ssm":
        from repro.models import ssm_lm as sm
        from repro.models.common import head_loss, head_loss_params

        stages = [
            Stage(
                name="embed",
                select=lambda p: {"embed": p["tok"]["embed"]},
                fwd=lambda sp, x, ctx: sm.embed_stage(sp, ctx, cfg),
                ranges=(("['tok']['embed']", 0, embed_size),),
                has_carry=False,
            )
        ]
        stages += _stack_chunk_stages(
            aparams["layers"],
            "layers",
            lambda sp, x, ctx: sm.stack_stage(sp, x, ctx, cfg),
            layer_chunks,
        )
        stages.append(
            Stage(
                name="head",
                select=lambda p: head_loss_params(p, cfg),
                fwd=lambda sp, x, ctx: head_loss(sp, x, ctx, cfg),
                ranges=_subtree_ranges(head_loss_params(aparams, cfg), lambda s: s),
            )
        )
        return stages, sm.train_ctx
    if fam == "hybrid":
        if layer_chunks > 1:
            raise ValueError(
                "hybrid stacks cannot be chunked: the weight-shared attention "
                "block ties every group, so chunking would re-associate its "
                "gradient sum (layer_chunks must be 1)"
            )
        from repro.models import hybrid as hy
        from repro.models.common import head_loss, head_loss_params

        stages = [
            Stage(
                name="embed",
                select=lambda p: {"embed": p["tok"]["embed"]},
                fwd=lambda sp, x, ctx: hy.embed_stage(sp, ctx, cfg),
                ranges=(("['tok']['embed']", 0, embed_size),),
                has_carry=False,
            ),
            Stage(
                name="stack",
                select=lambda p: {
                    "mamba_layers": p["mamba_layers"], "shared": p["shared"]
                },
                fwd=lambda sp, x, ctx: hy.stack_stage(sp, x, ctx, cfg),
                ranges=_subtree_ranges(
                    {"mamba_layers": aparams["mamba_layers"],
                     "shared": aparams["shared"]},
                    lambda s: s,
                ),
            ),
            Stage(
                name="head",
                select=lambda p: head_loss_params(p, cfg),
                fwd=lambda sp, x, ctx: head_loss(sp, x, ctx, cfg),
                ranges=_subtree_ranges(head_loss_params(aparams, cfg), lambda s: s),
            ),
        ]
        return stages, hy.train_ctx
    raise NotImplementedError(
        f"no interleaved stage decomposition for family {fam!r} "
        "(the encoder-decoder audio family has no staged train loss)"
    )


# Largest segment an interleaved layout keeps whole, in scalars.  A leaf (or
# a layer-chunk part of a stacked leaf) above it is split along axis 0, so
# the streamed encoder's live state stays bounded whatever the vocabulary or
# width (qwen3-0.6b's tied embedding alone is 155.6M scalars).
MAX_SEGMENT_SCALARS = 1 << 24


def interleaved_layout(
    cfg: ModelConfig,
    n: int,
    layer_chunks: int = 1,
    row_multiple: int = 1,
    s_ratio: Optional[Callable[[str, Tuple[int, ...]], Optional[float]]] = None,
    group_scalars: int = 0,
) -> GradientLayout:
    """Per-tensor layout whose stacked-layer leaves are split at the
    producer's chunk boundaries, so every chunk stage completes whole
    segments (an unsplit (L, ...) leaf's single segment would only finish
    when the LAST chunk backprops, killing the interleave); any part larger
    than ``MAX_SEGMENT_SCALARS`` is split further along axis 0."""
    aparams = _abstract_params(cfg)
    bounds: List[Tuple[int, int]] = []
    if layer_chunks > 1 and cfg.family in ("dense", "moe", "vlm", "ssm"):
        n_layers = jax.tree_util.tree_leaves(aparams["layers"])[0].shape[0]
        bounds = _chunk_bounds(n_layers, layer_chunks)
    parts = [hi - lo for lo, hi in bounds]

    def split(name: str, shape: Tuple[int, ...]):
        # every leaf under the main stack ("['layers']['attn']['wq']", ...);
        # "['layers_dense']..." does not share the prefix
        base = parts if parts and name.startswith("['layers']") else [shape[0]] if shape else []
        row = math.prod(shape[1:])
        out = []
        for p in base:
            k = -(-p * row // MAX_SEGMENT_SCALARS)
            out += [hi - lo for lo, hi in _chunk_bounds(p, k)]
        return out if len(out) > 1 else None

    leaves_with_path = jax.tree_util.tree_flatten_with_path(aparams)[0]
    treedef = jax.tree_util.tree_structure(aparams)
    shapes = tuple((tuple(l.shape), l.dtype) for _, l in leaves_with_path)
    names = [jax.tree_util.keystr(p) for p, _ in leaves_with_path]
    return GradientLayout.from_shapes_per_tensor(
        treedef, shapes, n, row_multiple=row_multiple, names=names,
        s_ratio=s_ratio, group_scalars=group_scalars,
        split=split,
    )


# ---------------------------------------------------------------------------
# The producer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Contrib:
    """One stage-gradient fragment -> segment-slot destination."""

    gleaf: int  # index into tree_leaves(stage gradients)
    a: int  # slice [a, b) within the stage leaf's flat span
    b: int
    seg: int  # destination segment index
    slot: int  # position within the segment (leaf slot j)
    dst: int  # offset within the slot


class InterleavedSegments:
    """``grad_segments_fn`` that yields layout segments in backward order.

    Engine hook signature: ``producer(params, batch, layout)`` yields
    ``(segment index, (C, rows, N) blocks)``.  ``grads_fn(params, batch)``
    materializes the matching batched gradient TREE from the same stage
    gradients -- the one-pass reference the wire bit-identity tests pin
    against.  Construct via :func:`repro.fed.engine.make_interleaved_segments`.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        layout: GradientLayout,
        grad_accum: int = 1,
        layer_chunks: int = 1,
    ):
        if grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        if grad_accum > 1 and cfg.family == "vlm":
            raise ValueError(
                "grad_accum microbatching splits the per-client sample axis, "
                "which the VLM batch's positions tensor does not carry "
                "(use grad_accum=1)"
            )
        self.cfg = cfg
        self.layout = layout
        self.grad_accum = int(grad_accum)
        self._aparams = _abstract_params(cfg)
        self.stages, self._ctx_fn = build_stages(cfg, self._aparams, layer_chunks)
        self._check_layout(layout)
        self._build_plan()
        self._build_jits()

    # -- construction --------------------------------------------------------

    def _check_layout(self, layout: GradientLayout) -> None:
        leaves = jax.tree_util.tree_flatten_with_path(self._aparams)[0]
        self._leaf_names = [jax.tree_util.keystr(p) for p, _ in leaves]
        want = tuple(tuple(l.shape) for _, l in leaves)
        got = tuple(s for s, _ in layout.shapes)
        if want != got or layout.treedef != jax.tree_util.tree_structure(self._aparams):
            raise ValueError(
                f"layout does not describe {self.cfg.name!r}'s parameter tree "
                "(build it with interleaved_layout / GradientLayout.per_tensor "
                "over the model params)"
            )

    def _build_plan(self) -> None:
        """Static fold plan: stage-gradient fragments -> segment slots.

        Per slot, contributions with IDENTICAL spans sum (shared leaves: the
        tied embedding accumulates embed + head stage gradients, in backward
        arrival order -- the same order :meth:`grads_fn` uses, so both paths
        add the same arrays in the same order); DISJOINT spans concatenate by
        offset (a split leaf's chunks).  Anything else is a plan bug and
        raises here, as does an uncovered slot (a leaf no stage produces).
        """
        name2id = {n: i for i, n in enumerate(self._leaf_names)}
        slots_by_leaf: Dict[int, List[Tuple[int, int, int, int]]] = {}
        for seg in self.layout.segments:
            for j, (lid, size, off) in enumerate(
                zip(seg.leaf_ids, seg.sizes, seg.leaf_offsets)
            ):
                slots_by_leaf.setdefault(lid, []).append(
                    (seg.index, j, off, off + size)
                )
        self._stage_contribs: List[List[_Contrib]] = []
        self._stage_scalars: List[int] = []
        spans: Dict[Tuple[int, int], List[Tuple[int, int]]] = {}
        for st in self.stages:
            contribs = []
            for gi, (nm, lo, hi) in enumerate(st.ranges):
                if nm not in name2id:
                    raise ValueError(
                        f"stage {st.name!r} produces unknown leaf {nm} "
                        "(stage protocol drifted from the parameter tree)"
                    )
                for sidx, j, slo, shi in slots_by_leaf[name2id[nm]]:
                    ov_lo, ov_hi = max(lo, slo), min(hi, shi)
                    if ov_lo < ov_hi:
                        contribs.append(
                            _Contrib(gi, ov_lo - lo, ov_hi - lo,
                                     sidx, j, ov_lo - slo)
                        )
                        spans.setdefault((sidx, j), []).append(
                            (ov_lo - slo, ov_hi - ov_lo)
                        )
            self._stage_contribs.append(contribs)
            self._stage_scalars.append(sum(hi - lo for _, lo, hi in st.ranges))
        self._pending = [0] * len(self.layout.segments)
        for contribs in self._stage_contribs:
            for cb in contribs:
                self._pending[cb.seg] += 1
        # validate: every slot exactly tiled (identical spans = sums, fine)
        for seg in self.layout.segments:
            for j, size in enumerate(seg.sizes):
                sl = spans.get((seg.index, j))
                if not sl:
                    raise ValueError(
                        f"segment {seg.name!r} slot {j} (leaf "
                        f"{self._leaf_names[seg.leaf_ids[j]]}) is produced by "
                        "no stage"
                    )
                cursor = 0
                for dst, ln in sorted(set(sl)):
                    if dst != cursor:
                        raise ValueError(
                            f"segment {seg.name!r} slot {j}: stage spans "
                            f"overlap or leave a gap at offset {cursor}"
                        )
                    cursor += ln
                if cursor != size:
                    raise ValueError(
                        f"segment {seg.name!r} slot {j}: stages cover "
                        f"{cursor} of {size} scalars"
                    )
        # emit order within a segment = flat scalar order (slot, then offset)
        self._seg_piece_keys: List[List[Tuple[int, int]]] = []
        self._seg_piece_info: List[List[Tuple[int, int]]] = []
        for seg in self.layout.segments:
            keys = sorted({
                (cb.slot, cb.dst)
                for contribs in self._stage_contribs
                for cb in contribs
                if cb.seg == seg.index
            })
            self._seg_piece_keys.append(keys)
            self._seg_piece_info.append([
                (seg.leaf_ids[slot], seg.leaf_offsets[slot] + dst)
                for slot, dst in keys
            ])

    def _build_jits(self) -> None:
        # every stage program runs under the client pass's ``stage`` scope
        # (obs/trace.py), which leaves the programs' names as they were
        stage = lambda f: scoped(f, CLIENT, "stage")
        self._ctx_jit = jax.jit(stage(jax.vmap(lambda b: self._ctx_fn(b, self.cfg))))
        self._fwd_jits, self._bwd_jits = [], []
        for st in self.stages:
            fwd = st.fwd
            if st.has_carry:
                self._fwd_jits.append(
                    jax.jit(stage(jax.vmap(fwd, in_axes=(None, 0, 0))))
                )

                def one(sp, x, ct, c, _fwd=fwd):
                    _, vjp = jax.vjp(lambda p, xi: _fwd(p, xi, c), sp, x)
                    return vjp(ct)  # (gp, gx)

                # the boundary carry is consumed exactly once and the carry
                # cotangent gx has its shape: donate it so XLA writes gx in
                # place (donating ct too would be unusable -- only one output
                # matches the shape -- and just warns)
                self._bwd_jits.append(
                    jax.jit(stage(jax.vmap(one, in_axes=(None, 0, 0, 0))),
                            donate_argnums=(1,))
                )
            else:
                self._fwd_jits.append(
                    jax.jit(stage(jax.vmap(lambda sp, c, _fwd=fwd: _fwd(sp, None, c),
                                           in_axes=(None, 0))))
                )

                def one0(sp, ct, c, _fwd=fwd):
                    _, vjp = jax.vjp(lambda p: _fwd(p, None, c), sp)
                    (gp,) = vjp(ct)
                    return gp

                # no donation: the embed gradient (vocab, d) cannot alias the
                # sequence-shaped cotangent
                self._bwd_jits.append(
                    jax.jit(stage(jax.vmap(one0, in_axes=(None, 0, 0))))
                )
        self._add_jit = jax.jit(stage(jnp.add))
        self._asm_jits: Dict[int, Any] = {}

    def _assemble(self, seg_index: int):
        """Pieces -> (C, rows, N) blocks for one segment, matching
        ``GradientLayout._segment_flat`` value-exactly (concat in flat
        order, cast f32, zero-pad, reshape)."""
        jit = self._asm_jits.get(seg_index)
        if jit is None:
            seg = self.layout.segments[seg_index]
            rows, n, pad = seg.rows, self.layout.n, seg.pad

            def asm(*pieces):
                flat = pieces[0] if len(pieces) == 1 else jnp.concatenate(
                    pieces, axis=-1
                )
                flat = flat.astype(jnp.float32)
                if pad:
                    flat = jnp.concatenate(
                        [flat, jnp.zeros(flat.shape[:-1] + (pad,), jnp.float32)],
                        axis=-1,
                    )
                return flat.reshape(flat.shape[0], rows, n)

            jit = self._asm_jits[seg_index] = jax.jit(scoped(asm, CLIENT, "stage"))
        return jit

    # -- the backward sweep --------------------------------------------------

    def _microbatches(self, batch: Any) -> List[Any]:
        acc = self.grad_accum
        if acc == 1:
            return [batch]
        leaves = jax.tree_util.tree_leaves(batch)
        bsz = leaves[0].shape[1]
        if bsz % acc:
            raise ValueError(
                f"grad_accum={acc} must divide the per-client batch size {bsz}"
            )
        mb = bsz // acc
        return [
            jax.tree_util.tree_map(lambda x: x[:, m * mb:(m + 1) * mb], batch)
            for m in range(acc)
        ]

    def _run(self, params: Any, batch: Any) -> Iterator[Tuple[int, List[Any]]]:
        """Yields ``(segment index, pieces)`` in backward completion order;
        ``pieces`` aligns with ``self._seg_piece_info[segment index]``."""
        stages = self.stages
        ns = len(stages)
        sel = [st.select(params) for st in stages]
        batches = self._microbatches(batch)
        acc = len(batches)
        ctxs = [self._ctx_jit(b) for b in batches]
        c = jax.tree_util.tree_leaves(batch)[0].shape[0]
        # forward: save the carry INTO each stage (the last stage's output --
        # the loss -- is never needed for its own VJP)
        carries: List[Optional[List[Any]]] = [[None] * acc for _ in range(ns)]
        for m in range(acc):
            x = None
            for k in range(ns - 1):
                carries[k][m] = x
                st = stages[k]
                x = (self._fwd_jits[k](sel[k], x, ctxs[m]) if st.has_carry
                     else self._fwd_jits[k](sel[k], ctxs[m]))
            carries[ns - 1][m] = x
        cts: List[Any] = [jnp.ones((c,), jnp.float32) for _ in range(acc)]
        pending = list(self._pending)
        accbuf: Dict[Tuple[int, int, int], Any] = {}
        for k in reversed(range(ns)):
            st = stages[k]
            g = None
            for m in range(acc):
                if st.has_carry:
                    gm, ct_m = self._bwd_jits[k](sel[k], carries[k][m],
                                                 cts[m], ctxs[m])
                    cts[m] = ct_m
                else:
                    gm = self._bwd_jits[k](sel[k], cts[m], ctxs[m])
                g = gm if g is None else jax.tree_util.tree_map(jnp.add, g, gm)
            carries[k] = None  # boundary activations freed as we walk back
            if acc > 1:
                g = jax.tree_util.tree_map(lambda v: v / acc, g)
            flats = [v.reshape(c, -1) for v in jax.tree_util.tree_leaves(g)]
            for cb in self._stage_contribs[k]:
                flat = flats[cb.gleaf]
                piece = (flat if cb.a == 0 and cb.b == flat.shape[1]
                         else jax.lax.slice_in_dim(flat, cb.a, cb.b, axis=1))
                key = (cb.seg, cb.slot, cb.dst)
                prev = accbuf.get(key)
                accbuf[key] = piece if prev is None else self._add_jit(prev, piece)
                pending[cb.seg] -= 1
                if pending[cb.seg] == 0:
                    yield cb.seg, [
                        accbuf.pop((cb.seg,) + pk)
                        for pk in self._seg_piece_keys[cb.seg]
                    ]

    # -- public faces --------------------------------------------------------

    def __call__(
        self, params: Any, batch: Any, layout: GradientLayout
    ) -> Iterator[Tuple[int, jnp.ndarray]]:
        """The engine's ``grad_segments_fn`` hook: backward-ordered
        ``(segment index, (C, rows, N) blocks)``."""
        if layout is not self.layout and layout != self.layout:
            raise ValueError(
                "engine layout differs from the producer's -- pass the same "
                "GradientLayout to CohortEngine(layout=) and "
                "make_interleaved_segments"
            )
        for seg_idx, pieces in self._run(params, batch):
            yield seg_idx, self._assemble(seg_idx)(*pieces)

    def grads_fn(self, params: Any, batch: Any) -> Any:
        """One-pass reference: the batched gradient TREE assembled from the
        SAME stage-gradient arrays the segment stream emits (leaf pieces
        concatenated in offset order).  Slicing this tree through the layout
        reproduces the streamed wire bit-for-bit -- the producer's
        correctness oracle."""
        c = jax.tree_util.tree_leaves(batch)[0].shape[0]
        by_leaf: Dict[int, List[Tuple[int, jnp.ndarray]]] = {}
        for seg_idx, pieces in self._run(params, batch):
            for (lid, abs_off), arr in zip(self._seg_piece_info[seg_idx], pieces):
                by_leaf.setdefault(lid, []).append((abs_off, arr))
        leaves = []
        for lid, (shape, dtype) in enumerate(self.layout.shapes):
            plist = sorted(by_leaf[lid], key=lambda t: t[0])
            flat = plist[0][1] if len(plist) == 1 else jnp.concatenate(
                [p for _, p in plist], axis=-1
            )
            leaves.append(flat.reshape((c,) + shape).astype(dtype))
        return jax.tree_util.tree_unflatten(self.layout.treedef, leaves)

    # -- accounting ----------------------------------------------------------

    @property
    def stage_names(self) -> List[str]:
        return [st.name for st in self.stages]

    def peak_live_grad_bytes(self, clients: int) -> int:
        """Analytic peak of GRADIENT + ENCODER bytes held live at once by the
        interleaved client pass (f32 scalars x clients): walks the fold plan
        backward tracking one stage's gradients plus the pending cross-stage
        accumulators, then adds a double-buffered largest-segment encode
        working set (async dispatch keeps at most the in-flight and the
        just-enqueued segment's encoder state alive).  Stage-boundary
        activations and the packed wire accumulation are accounted by the
        bench on top, per model geometry.  This is the bound
        ``BENCH_interleave.json`` records and CI validates."""
        peak = live = 0
        pending = list(self._pending)
        buf: Dict[Tuple[int, int, int], int] = {}
        for k in reversed(range(len(self.stages))):
            for cb in self._stage_contribs[k]:
                key = (cb.seg, cb.slot, cb.dst)
                if key not in buf:
                    buf[key] = cb.b - cb.a
                    live += cb.b - cb.a
                peak = max(peak, self._stage_scalars[k] + live)
                pending[cb.seg] -= 1
                if pending[cb.seg] == 0:
                    for pk in self._seg_piece_keys[cb.seg]:
                        live -= buf.pop((cb.seg,) + pk)
            peak = max(peak, self._stage_scalars[k] + live)
        return clients * (
            4 * peak + 2 * self.layout.encoder_live_bytes(streamed=True)
        )
