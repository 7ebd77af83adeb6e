"""Unified telemetry layer (DESIGN.md #Observability).

Three small pieces, composable and individually optional:

  * recorder -- the MetricsRecorder protocol and its three sinks
    (NullRecorder / InMemoryRecorder / JsonlRecorder).  Engines take a
    recorder at construction; ``recorder.active`` is a *static* property so
    the jitted graphs they build never branch on it at trace time.
  * schema -- the versioned event envelope and the validators the CI smoke
    and the reader share.
  * trace -- monotonic-clock spans with an optional
    jax.profiler.TraceAnnotation passthrough, so profiler traces and JSONL
    phase timings share one naming scheme, a per-round profiler step, and
    the layer scopes of the round's device programs.

The reader/CLI toolchain lives in reader.py and runs as
``python -m repro.obs summarize|tail|compare|validate <run_dir>``.

This package deliberately imports nothing from repro.fed / repro.core --
observability sits *below* the layers it instruments.
"""

from repro.obs.recorder import (
    NULL_RECORDER,
    InMemoryRecorder,
    JsonlRecorder,
    MetricsRecorder,
    NullRecorder,
)
from repro.obs.schema import SCHEMA_VERSION, validate_event, validate_meta
from repro.obs.trace import SpanCollector, span

__all__ = [
    "MetricsRecorder",
    "NullRecorder",
    "InMemoryRecorder",
    "JsonlRecorder",
    "NULL_RECORDER",
    "SCHEMA_VERSION",
    "validate_event",
    "validate_meta",
    "SpanCollector",
    "span",
]
