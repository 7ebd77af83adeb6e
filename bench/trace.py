"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

From the device planes (``/device:TPU:<i>``) it takes the ``XLA Ops`` line
(each event one operation on the device) and the ``XLA Modules`` line (each
event one program run); on a host without an accelerator, where XLA's CPU
client runs the operations on host threads, it takes every event that
carries an ``hlo_module`` stat as an operation of that module.  From the
host planes it takes the benchmark's and the program's annotations
(``window``, ``round``, ``warmup`` and the engine's phase spans).

Reduced, all within the ``window`` span:

* ``busy_s``: the union of operation intervals, averaged over devices;
* ``window_s``: the window span's length;
* ``phase_s``: module run time by round phase, each module placed by the
  table in ``bench/phases.json`` (see ``place``; no match is ``other``);
* ``kernel_s`` / ``kernel_calls``: summed device time and count of the
  operations whose name matches a kernel's pattern (``bench/kernels``);
* ``breakdown``: the operations that took most time, and the idle gaps
  summed by the host span that was open (innermost) at the gap.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

HOST_SPANS = frozenset({
    "window", "round", "warmup",
    "uplink", "client_pass", "backward", "encode_overlap", "decode", "fold", "apply",
})


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(ev) -> Dict[str, object]:
    return {name: value for name, value in ev.stats}


def _module_name(name: str) -> str:
    """``jit__ps_fn(12)`` -> ``jit__ps_fn``."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(text: str) -> str:
    """A TPU op event is named by its HLO instruction text; keep the
    instruction's name (``%gamp_step_pallas.5 = ...`` -> ``gamp_step_pallas.5``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_events(path: str):
    """(ops, modules, spans): ops and modules are lists of (device, name,
    module, start_ns, end_ns); spans (name, start_ns, end_ns) on the host."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans, host_ops = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops.append((dev, _op_name(ev.name), None, ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append((dev, _module_name(ev.name), None, ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
                        continue
                    st = _stats(ev)
                    if "hlo_module" in st and ev.duration_ns > 0:
                        host_ops.append(("host", str(st.get("hlo_op", ev.name)),
                                         _module_name(str(st["hlo_module"])),
                                         ev.start_ns, ev.start_ns + ev.duration_ns))
    if not ops:  # no accelerator plane: the CPU client's operations
        ops = host_ops
        by_mod: Dict[str, List[Tuple[float, float]]] = {}
        for _, _, mod, s, e in ops:
            by_mod.setdefault(mod, []).append((s, e))
        for mod, ivs in by_mod.items():
            for s, e in _union(ivs):
                modules.append(("host", mod, None, s, e))
    else:  # name each device op by the module run that holds it
        ops = _attach_modules(ops, modules)
    return ops, modules, spans


def _attach_modules(ops, modules):
    out = []
    by_dev: Dict[str, list] = {}
    for m in modules:
        by_dev.setdefault(m[0], []).append(m)
    for dev in by_dev:
        by_dev[dev].sort(key=lambda m: m[3])
    starts = {dev: [m[3] for m in ms] for dev, ms in by_dev.items()}
    import bisect

    for dev, name, _, s, e in ops:
        mod = None
        ms = by_dev.get(dev, [])
        i = bisect.bisect_right(starts.get(dev, []), s) - 1
        if i >= 0 and ms[i][4] >= s:
            mod = ms[i][1]
        out.append((dev, name, mod, s, e))
    return out


def _union(ivs: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def _leaves(events):
    """Events that hold no other event of the same device (an op inside a
    loop or a call is counted, the loop or call itself is not)."""
    out = []
    by_dev: Dict[str, list] = {}
    for ev in events:
        by_dev.setdefault(ev[0], []).append(ev)
    for evs in by_dev.values():
        evs.sort(key=lambda ev: (ev[3], -ev[4]))
        for i, ev in enumerate(evs):
            nxt = evs[i + 1] if i + 1 < len(evs) else None
            if nxt is not None and nxt[3] < ev[4] and nxt[4] <= ev[4]:
                continue  # it contains the next event
            out.append(ev)
    return out


def classify(module: Optional[str], table: Sequence[Tuple[str, str]]) -> str:
    for pattern, phase in table:
        if module is not None and re.search(pattern, module):
            return phase
    return "other"


def place(modules, table: Dict[str, object]) -> List[str]:
    """The phase of each module run (``modules`` in time order on one
    device): by the first pattern of ``table["phases"]`` that matches its
    name; a run between a decode and the next run matching
    ``table["after_decode"]["until"]`` belongs to the server's update (the
    program's update runs under a generic name)."""
    after = table.get("after_decode")
    out, post = [], False
    for m in modules:
        phase = classify(m[1], table["phases"])
        if phase == "decode":
            post = True
        elif after and post and re.search(after["until"], m[1]):
            post = False
        elif after and post:
            phase = after["phase"]
        out.append(phase)
    return out


def reduce(path: str, phases: Dict[str, object],
           kernels: Dict[str, str], top: int = 10) -> Dict[str, object]:
    """The reduced trace (see the module docstring).  ``phases`` is the
    table of ``bench/phases.json``; ``kernels`` maps a kernel's name to the
    pattern of its operations' names."""
    ops, modules, spans = read_events(path)
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if windows:
        w0, w1 = windows[0]
    else:
        w0, w1 = min(o[3] for o in ops), max(o[4] for o in ops)
    rounds = sum(1 for n, s, e in spans if n == "round" and s >= w0 and e <= w1)
    devices = sorted({o[0] for o in ops})
    busy = 0.0
    idle: List[Tuple[float, float]] = []
    for dev in devices:
        u = _union(_clip([(o[3], o[4]) for o in ops if o[0] == dev], w0, w1))
        busy += sum(e - s for s, e in u)
        prev = w0
        for s, e in u + [(w1, w1)]:
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
    busy /= max(len(devices), 1)

    phase_s: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    others: Dict[str, float] = {}
    modules = sorted(modules, key=lambda m: (m[0], m[3]))
    for (_, name, _, s, e), phase in zip(modules, place(modules, phases)):
        for cs, ce in _clip([(s, e)], w0, w1):
            if phase == "other":
                others[name] = others.get(name, 0.0) + (ce - cs) / 1e9
            phase_s[phase] = phase_s.get(phase, 0.0) + (ce - cs) / 1e9
            module_s[name] = module_s.get(name, 0.0) + (ce - cs) / 1e9

    leaf_ops = [o for o in _leaves(ops) if o[4] > w0 and o[3] < w1]
    kernel_s = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    op_s: Dict[str, float] = {}
    for _, name, _, s, e in leaf_ops:
        for cs, ce in _clip([(s, e)], w0, w1):
            op_s[name] = op_s.get(name, 0.0) + (ce - cs) / 1e9
            for k, pattern in kernels.items():
                if re.search(pattern, name):
                    kernel_s[k] += (ce - cs) / 1e9
                    kernel_calls[k] += 1

    gap_s: Dict[str, float] = {}
    host = sorted(spans, key=lambda sp: sp[1])
    for s, e in idle:
        mid = 0.5 * (s + e)
        open_spans = [sp for sp in host if sp[1] <= mid <= sp[2]]
        label = min(open_spans, key=lambda sp: sp[2] - sp[1])[0] if open_spans else "none"
        gap_s[label] = gap_s.get(label, 0.0) + (e - s) / 1e9

    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / 1e9,
        "rounds": rounds,
        "devices": len(devices),
        "phase_s": phase_s,
        "module_s": module_s,
        "unplaced_modules": rank(others),
        "kernel_s": kernel_s,
        "kernel_calls": kernel_calls,
        "breakdown": {"device_ops": rank(op_s), "idle_gaps": rank(gap_s)},
    }
