"""Per-layer metric readers: ``bench/metrics/<metric>.py`` each define
``read(ctx)``, which returns the metric's value or None where the run has
nothing for it to read (a kernel that did not run reports no roofline)."""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from harness import BENCH, load_json, load_module

PEAKS = os.path.join(BENCH, "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(PEAKS)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def kernel(name: str, bench: str = BENCH):
    """A kernel's module in ``bench/kernels``: its op-name ``PATTERN`` and
    ``count(shape) -> (flops, bytes)`` per round."""
    return load_module(os.path.join(bench, "kernels", name + ".py"), "bench_kernel_" + name)


def kernel_names(bench: str = BENCH):
    return sorted(f[:-3] for f in os.listdir(os.path.join(bench, "kernels"))
                  if f.endswith(".py"))


def metric_names(bench: str = BENCH):
    return sorted(f[:-3] for f in os.listdir(os.path.join(bench, "metrics"))
                  if f.endswith(".py"))


class Context:
    """What a reader may use: the cell, the run, the reduced trace, the
    chip's peaks and the round's shape (``shape``: clients, block rows of
    the model ``rows``, N, M, Q, packed words ``words``, GAMP iterations,
    mixture components, client tokens per round and sequence length)."""

    def __init__(self, cell, run: Dict[str, Any], reduced: Dict[str, Any], device_kind: str,
                 shape: Optional[Dict[str, Any]] = None, bench: str = BENCH):
        self.cell, self.run, self.trace, self.bench = cell, run, reduced, bench
        self.bounds: Dict[str, str] = {}  # kernel -> "compute" or "memory"
        self.peaks = peaks(device_kind)
        self.shape = shape if shape is not None else run["shape"]

    @property
    def rounds(self) -> int:
        return self.trace["rounds"]

    def phase_ms(self, phase: str) -> Optional[float]:
        if not self.rounds or phase not in self.trace["phase_s"]:
            return None
        return 1e3 * self.trace["phase_s"][phase] / self.rounds

    def roofline(self, name: str) -> Optional[float]:
        """Percent of the kernel's least time (its operations at peak FLOP/s
        or its bytes at peak bandwidth, whichever is longer) over its summed
        device time in the window."""
        spent = self.trace["kernel_s"].get(name, 0.0)
        if spent <= 0 or not self.rounds:
            return None
        flops, nbytes = kernel(name, self.bench).count(self.shape)
        t_flops = flops / self.peaks["flops_per_s"]
        t_bytes = nbytes / self.peaks["bytes_per_s"]
        self.bounds[name] = "compute" if t_flops >= t_bytes else "memory"
        return 100.0 * self.rounds * max(t_flops, t_bytes) / spent


def read(name: str, ctx: Context):
    mod = load_module(os.path.join(ctx.bench, "metrics", name + ".py"), "bench_metric_" + name)
    return mod.read(ctx)
