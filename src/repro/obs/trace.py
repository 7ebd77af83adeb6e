"""Monotonic-clock spans with one naming scheme for JSONL and profiler,
and the layer scopes of the round's device programs.

The federated engines wrap each round phase in a span::

    spans = SpanCollector()
    with span("client_pass", spans):
        ...
    spans.ms  # {"client_pass": 12.3, ...}

Span names are the phase vocabulary shared by the ``phase_ms`` field of
round events, the ``span`` event kind, and (when enabled) the
``jax.profiler.TraceAnnotation`` labels -- a profile and a run log line up
by construction.  Canonical engine phase names: ``select`` (cohort
selection, round keys and the per-round prep dispatch), ``uplink``,
``sync`` (the host waiting on a device value: the channel mask, the
round's stats), ``client_pass``, ``fold``, ``decode``, ``apply``; plus the
SUB-phases of the streamed client pass, ``backward`` (time inside the
gradient producer's next() -- for the interleaved producer, one stage's
VJP dispatch) and ``encode_overlap`` (the per-segment encode dispatch
riding on the backward sweep).  Sub-phases nest inside ``client_pass``,
so aggregations that sum phases must exclude :data:`SUB_PHASES` or the
nested time double-counts.  With annotations on, :func:`round_step` marks
each round as a ``jax.profiler.StepTraceAnnotation`` named
:data:`ROUND_STEP` (the profiler's step view reads it).

Layer scopes: every jitted program of the cohort round runs its body
inside one top-level ``jax.named_scope`` naming its layer --
:data:`UPLINK`, :data:`CLIENT`, :data:`DECODE` or :data:`APPLY` -- with
sub-scopes below it: ``fedqcs.client/stage`` (the segment producer's stage
programs), ``fedqcs.client/encode`` (the per-segment encode),
``fedqcs.decode/dequantize`` (the Bussgang-weighted aggregate of the
decoded codes) and ``fedqcs.decode/gamp`` (the chunked GAMP solve).  A
scope is trace-time metadata: it lands in each HLO instruction's
``op_name`` (``jit(_ps_fn)/fedqcs.decode/gamp/...``; a transform applied
outside the scope wraps it, as ``vmap(fedqcs.client)/encode/...``, so a
reader matches the scope anywhere in the path) and changes neither the
computation nor the program's name.  Eager glue (slices, concatenates, key
splits) carries no scope, nor do instructions XLA inserts and the loop
invariants JAX hoists out of a scan's VJP (a reader places those by their
program).

Overhead: with ``collector=None`` and annotations off, ``span`` is two
``time.monotonic()`` calls -- cheap enough to leave in place permanently.
jax.profiler annotations engage only when REPRO_TRACE_ANNOTATIONS=1 is set
in the environment (checked once at import), so the default path never
touches the profiler.

Timing caveat: spans measure host wall-clock.  JAX dispatch is async, so a
span around a jitted call measures dispatch unless the caller blocks;
engines block once per round when pulling metrics anyway, which lands the
full device time in the phase that materializes results.
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
from contextlib import contextmanager
from typing import Callable, Dict, Optional

__all__ = [
    "SpanCollector", "span", "round_step", "scoped", "ANNOTATE", "SUB_PHASES",
    "ROUND_STEP", "UPLINK", "CLIENT", "DECODE", "APPLY",
]

# Checked once at import: profiler annotations are opt-in by environment.
ANNOTATE = os.environ.get("REPRO_TRACE_ANNOTATIONS", "") == "1"

# Phases that time a slice of another phase (they nest inside client_pass):
# excluded when summing phase_ms into a round total.
SUB_PHASES = frozenset({"backward", "encode_overlap"})

# The profiler step that marks one round (annotations on only).
ROUND_STEP = "fedqcs_round"

# Top-level layer scopes of the round's device programs.
UPLINK = "fedqcs.uplink"
CLIENT = "fedqcs.client"
DECODE = "fedqcs.decode"
APPLY = "fedqcs.apply"


class SpanCollector:
    """Accumulates span durations by name (ms, summed over re-entries)."""

    def __init__(self) -> None:
        self.ms: Dict[str, float] = {}

    def add(self, name: str, ms: float) -> None:
        self.ms[name] = self.ms.get(name, 0.0) + ms

    def drain(self) -> Dict[str, float]:
        """Returns the accumulated timings and resets the collector."""
        out, self.ms = self.ms, {}
        return out


@contextmanager
def span(name: str, collector: Optional[SpanCollector] = None):
    """Times a block; records into ``collector`` (None = annotation only)."""
    if ANNOTATE:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation(name):
            t0 = time.monotonic()
            try:
                yield
            finally:
                if collector is not None:
                    collector.add(name, (time.monotonic() - t0) * 1e3)
        return
    t0 = time.monotonic()
    try:
        yield
    finally:
        if collector is not None:
            collector.add(name, (time.monotonic() - t0) * 1e3)


def round_step(t: int):
    """Marks round ``t`` as a profiler step (a no-op unless annotating)."""
    if not ANNOTATE:
        return contextlib.nullcontext()
    from jax.profiler import StepTraceAnnotation

    return StepTraceAnnotation(ROUND_STEP, step_num=t)


def scoped(fn: Callable, *names: str) -> Callable:
    """``fn`` with its body inside the nested ``jax.named_scope``s
    ``names`` (outermost first).  The wrapper keeps ``fn``'s name and
    signature, so ``jax.jit`` names the program and reads static arguments
    as it would for ``fn``."""
    import jax

    @functools.wraps(fn)
    def inner(*args, **kwargs):
        with contextlib.ExitStack() as stack:
            for name in names:
                stack.enter_context(jax.named_scope(name))
            return fn(*args, **kwargs)

    return inner
