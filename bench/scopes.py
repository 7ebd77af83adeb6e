"""Reduction of a profiler trace (``.xplane.pb``) to the round's layers, as
the program names them inside its programs, and a traced run that prints
them beside the metrics of ``bench/trace.py``.

The program runs every jitted program of the round under one top-level
layer scope (``jax.named_scope``: ``fedqcs.uplink``, ``fedqcs.client``,
``fedqcs.decode``, ``fedqcs.apply``) with sub-scopes below two of them
(``fedqcs.client/stage``, ``fedqcs.client/encode``,
``fedqcs.decode/dequantize``, ``fedqcs.decode/gamp``); a scope lands in the
``op_name`` of each HLO instruction.  With annotations on it marks each
round as a ``fedqcs_round`` profiler step and each host wait on a device
value as a ``sync`` span.

Where the op_name comes from: the trace's ``/host:metadata`` plane holds,
for every program that ran while tracing, its optimized HLO (an ``Hlo
Proto`` stat on an event metadata named ``<module>(<program id>)``): module,
computations, instructions, each with its ``OpMetadata.op_name``.  An
operation event names its instruction (on a TPU the event is the
instruction's text, in the ``XLA Ops`` line of the device plane; on the CPU
the ``hlo_op`` stat) and its program (on a TPU the ``XLA Modules`` run that
holds it is named ``<module>(<program id>)``; on the CPU the ``hlo_module``
and ``program_id`` stats).  The op events themselves carry no op_name on
either backend.  The protos are read with protobuf's generic wire reader,
by the field numbers of ``xplane.proto`` and ``hlo.proto`` given below.

Placement of each operation (``place``): by the layer scope anywhere in its
op_name (a transform wraps it, as ``vmap(fedqcs.client)/encode/...``), with
the sub-scope that follows it if that is one of the layer's.  An
instruction without one (XLA inserted it, such as a copy) takes the op_name
of the instruction that calls its computation (a loop's body: the loop's
scope); failing that, it goes to its program's layer when every scoped
instruction of that program has the same layer; any other is ``unscoped``
(eager glue and the benchmark's own data program).

Reduced, within the ``window`` span (``reduce``):

* ``device_s``: summed time of the innermost operations (as ``trace.reduce``
  counts kernel time), over devices;
* ``layer_s``: that time by placement (``fedqcs.decode/gamp``,
  ``fedqcs.decode``, ..., ``unscoped``), summing to ``device_s``;
* ``unscoped_modules``: the programs whose operations were unscoped, by time;
* ``rounds``: ``fedqcs_round`` steps that start in the window;
* ``module_runs``: program runs that start in the window, per device;
* ``sync_s``: host time inside ``sync`` spans;
* ``idle_in_s``: device idle time inside ``sync`` and ``select`` spans (the
  spans ``trace.reduce`` does not know, whose gaps it labels ``round``),
  over devices;
* ``op_names``: how many programs had their HLO in the trace.

Run a cell traced and print both reductions' per-layer metrics:

    python3 bench/scopes.py --workload <name> --seed <n> --seconds <s>

(the same set-up, window and reference as ``bench/run.py --trace 1``; the
last line of standard output is one JSON object).
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

ROUND_STEP = "fedqcs_round"
SYNC = "sync"
SELECT = "select"
UNSCOPED = "unscoped"
# layer scope -> its sub-scopes
LAYERS = {
    "fedqcs.uplink": (),
    "fedqcs.client": ("stage", "encode"),
    "fedqcs.decode": ("dequantize", "gamp"),
    "fedqcs.apply": (),
}
_SCOPE = re.compile(r"(fedqcs\.[a-z]+)\)*(?:/([A-Za-z_]\w*))?")

# Field numbers: xplane.proto (XSpace.planes 1; XPlane.name 2,
# event_metadata 4, stat_metadata 5; map entries key 1, value 2;
# XEventMetadata.name 2, stats 5; XStat.metadata_id 1, bytes_value 6;
# XStatMetadata.id 1, name 2) and hlo.proto (HloProto.hlo_module 1;
# HloModuleProto.computations 3; HloComputationProto.instructions 2, id 5;
# HloInstructionProto.name 1, metadata 7, called_computation_ids 38;
# OpMetadata.op_name 2).
HLO_PROTO_STAT = "Hlo Proto"


def _fields(data: bytes):
    """(field number, value) of a serialized message's top-level fields."""
    from google.protobuf import empty_pb2
    from google.protobuf.unknown_fields import UnknownFieldSet

    msg = empty_pb2.Empty()
    msg.ParseFromString(data)
    return [(f.field_number, f.data) for f in UnknownFieldSet(msg)]


def _first(fields, number, default=None):
    for n, v in fields:
        if n == number:
            return v
    return default


def _space_planes(raw: bytes):
    """(name, fields) of each plane, splitting the XSpace without decoding
    the other planes' events."""
    for n, plane in _fields(raw):
        if n == 1:
            fields = _fields(plane)
            yield _first(fields, 2, b"").decode(), fields


def hlo_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{``<module>(<program id>)``: {instruction name: op_name}} of every
    program whose HLO the trace holds."""
    with open(path, "rb") as f:
        raw = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for name, plane in _space_planes(raw):
        if name != "/host:metadata":
            continue
        stat_ids = set()
        for n, entry in plane:
            if n == 5:
                md = _fields(_first(_fields(entry), 2, b""))
                if _first(md, 2, b"").decode() == HLO_PROTO_STAT:
                    stat_ids.add(_first(md, 1))
        for n, entry in plane:
            if n != 4:
                continue
            md = _fields(_first(_fields(entry), 2, b""))
            program = _first(md, 2, b"").decode()
            for sn, stat in md:
                if sn != 5:
                    continue
                st = _fields(stat)
                if _first(st, 1) in stat_ids and _first(st, 6) is not None:
                    out[program] = _instruction_op_names(_first(st, 6))
    return out


def _varints(data) -> List[int]:
    """A repeated integer field's values: one varint, or a packed run."""
    if isinstance(data, int):
        return [data]
    out, shift, acc = [], 0, 0
    for byte in data:
        acc |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            out.append(acc)
            shift, acc = 0, 0
    return out


def _instruction_op_names(hlo_proto: bytes) -> Dict[str, str]:
    """{instruction: op_name}; an instruction with no layer scope in a
    computation that another instruction calls (a loop's body, a called
    computation) takes its caller's op_name, as an XLA-inserted copy in a
    loop's body is the loop's work."""
    instrs: Dict[str, Tuple[str, int]] = {}  # name -> (op_name, computation)
    caller: Dict[int, str] = {}  # computation -> an instruction calling it
    module = _first(_fields(hlo_proto), 1, b"")
    for n, comp in _fields(module):
        if n != 3:
            continue
        fields = _fields(comp)
        cid = _first(fields, 5)
        for m, instr in fields:
            if m != 2:
                continue
            f = _fields(instr)
            name = _first(f, 1, b"").decode()
            meta = _first(f, 7)
            instrs[name] = ((_first(_fields(meta), 2, b"") if meta else b"").decode(), cid)
            for k, v in f:
                if k == 38:
                    for called in _varints(v):
                        caller.setdefault(called, name)

    def resolve(name: str, depth: int = 0) -> str:
        op_name, cid = instrs[name]
        up = caller.get(cid)
        if scope_of(op_name) or up is None or depth > len(instrs):
            return op_name
        inherited = resolve(up, depth + 1)
        return inherited if scope_of(inherited) else op_name

    return {name: resolve(name) for name in instrs}


def scope_of(op_name: str) -> Optional[str]:
    """``fedqcs.decode/gamp``, ``fedqcs.decode`` or None."""
    m = _SCOPE.search(op_name or "")
    if m is None or m.group(1) not in LAYERS:
        return None
    layer, sub = m.groups()
    return f"{layer}/{sub}" if sub in LAYERS[layer] else layer


def place(ops: Sequence[Tuple[str, str]], programs: Dict[str, Dict[str, str]]) -> List[str]:
    """The placement of each ``(program, instruction)`` (module docstring):
    its scope, else its program's one layer, else ``unscoped``."""
    layer_of: Dict[str, Optional[str]] = {}
    for prog, names in programs.items():
        layers = {s.split("/")[0] for s in map(scope_of, names.values()) if s}
        layer_of[prog] = layers.pop() if len(layers) == 1 else None
    out = []
    for prog, instr in ops:
        s = scope_of(programs.get(prog, {}).get(instr, ""))
        out.append(s or layer_of.get(prog) or UNSCOPED)
    return out


def _op_instruction(text: str) -> str:
    """A TPU op event is named by its instruction's text:
    ``%fusion.2 = f32[...] fusion(...)`` -> ``fusion.2``."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_events(path: str):
    """(ops, runs, spans): ops (device, instruction, program, start, end),
    program runs (device, program, start, end) and the host's ``window``,
    ``sync``, ``select`` and round-step spans (name, start, end); times in
    ns."""
    from jax.profiler import ProfileData

    import trace

    ops, runs, spans, host_ops = [], [], [], []
    host_runs: Dict[Tuple[str, str], List[float]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if line.name == "XLA Ops":
                        ops.append((plane.name, _op_instruction(ev.name), None, s, e))
                    elif line.name == "XLA Modules":
                        runs.append((plane.name, ev.name, s, e))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name in ("window", SYNC, SELECT, ROUND_STEP):
                        spans.append((ev.name, s, e))
                        continue
                    st = dict(ev.stats)
                    if "hlo_module" in st and "program_id" in st:
                        prog = f"{st['hlo_module']}({st['program_id']})"
                        host_ops.append(("host", str(st.get("hlo_op", ev.name)), prog, s, e))
                        ext = host_runs.setdefault((prog, str(st.get("run_id"))), [s, e])
                        ext[0], ext[1] = min(ext[0], s), max(ext[1], e)
    if not ops:  # no accelerator plane: the CPU client's operations
        ops = host_ops
        runs = [("host", prog, s, e) for (prog, _), (s, e) in host_runs.items()]
    else:  # each device op belongs to the program run that holds it
        ops = [(d, i, p, s, e) for d, i, p, s, e in trace._attach_modules(
            ops, [(d, p, None, s, e) for d, p, s, e in runs])]
    return ops, runs, spans


def _module(program: Optional[str]) -> str:
    return re.sub(r"\(\d+\)$", "", program or "none")


def reduce(path: str, top: int = 10) -> Dict[str, object]:
    """The reduced trace (see the module docstring)."""
    import trace

    ops, runs, spans = read_events(path)
    programs = hlo_op_names(path)
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if windows:
        w0, w1 = windows[0]
    else:
        w0, w1 = min(o[3] for o in ops), max(o[4] for o in ops)
    leaves = [o for o in trace._leaves(ops) if o[4] > w0 and o[3] < w1]
    layer_s: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    for (_, _, prog, s, e), label in zip(leaves, place([(o[2], o[1]) for o in leaves],
                                                        programs)):
        t = (min(e, w1) - max(s, w0)) / 1e9
        layer_s[label] = layer_s.get(label, 0.0) + t
        if label == UNSCOPED:
            unscoped[_module(prog)] = unscoped.get(_module(prog), 0.0) + t
    devices = max(len({r[0] for r in runs}), 1)
    inside = lambda s: w0 <= s <= w1  # noqa: E731
    idle_in = {SYNC: 0.0, SELECT: 0.0}
    for dev in {o[0] for o in ops}:
        busy = trace._union(trace._clip([(o[3], o[4]) for o in ops if o[0] == dev], w0, w1))
        for name, s, e in spans:
            if name in idle_in and e > w0 and s < w1:
                s, e = max(s, w0), min(e, w1)
                covered = sum(b - a for a, b in trace._clip(busy, s, e))
                idle_in[name] += (e - s - covered) / 1e9 / devices
    return {
        "rounds": sum(1 for n, s, _ in spans if n == ROUND_STEP and inside(s)),
        "device_s": sum(layer_s.values()),
        "layer_s": layer_s,
        "unscoped_modules": [[k, v] for k, v in
                             sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]],
        "module_runs": sum(1 for r in runs if inside(r[2])) / devices,
        "sync_s": sum(e - s for s, e in trace._clip(
            [(s, e) for n, s, e in spans if n == SYNC], w0, w1)) / 1e9,
        "idle_in_s": idle_in,
        "op_names": len(programs),
    }


def layer_ms(ctx, scope: str) -> Optional[float]:
    """Device time per round placed in ``scope``, its sub-scopes included.
    None where the run has no scoped reduction or no round, or nothing ran
    in ``scope``."""
    red = ctx.trace.get("scopes")
    if not red or not red["rounds"]:
        return None
    got = [v for k, v in red["layer_s"].items()
           if k == scope or k.startswith(scope + "/")]
    return 1e3 * sum(got) / red["rounds"] if got else None


def per_round(ctx, key: str, scale: float = 1.0) -> Optional[float]:
    """``scale`` times the reduction's ``key`` over its rounds, or None."""
    red = ctx.trace.get("scopes")
    if not red or not red["rounds"]:
        return None
    return scale * red[key] / red["rounds"]


def main(argv=None) -> None:
    import argparse
    import json
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [bench, os.path.join(os.path.dirname(bench), "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_TRACE_ANNOTATIONS"] = "1"  # read once, at the program's import
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731

    import harness

    cell = harness.load_cell(args.workload)
    devs = harness.require_chip(cell.chips)
    harness.enable_cache()
    log(f"[scopes] {cell.name} on {devs[0].device_kind} x{len(devs)}, seed {args.seed}")
    run = harness.measure(cell, args.seed, args.seconds, True, t_start, devs, log)
    line = traced_line(cell, run, devs, log)
    print(json.dumps(line), flush=True)
    sys.stderr.flush()
    os._exit(0)


def traced_line(cell, run, devs, log=print) -> Dict[str, object]:
    """Reduces the window's trace both ways (this module's reduction first:
    ``harness.reduce_trace`` deletes the trace) and returns the result line
    of ``bench/run.py --trace 1`` with every metric of ``bench/metrics``
    this run has something for, the new ones included."""
    import harness
    import readers
    import trace

    scoped = reduce(trace.newest_xplane(harness.TRACE_DIR))
    reduced = harness.reduce_trace(log)
    reduced["scopes"] = scoped
    log(f"[scopes] layers {scoped['layer_s']} over {scoped['rounds']} rounds; "
        f"device {scoped['device_s']!r} s, busy {reduced['busy_s']!r} s; "
        f"{scoped['op_names']} programs' HLO in the trace")
    log(f"[scopes] unscoped programs {scoped['unscoped_modules']}; sync {scoped['sync_s']!r} s, "
        f"device idle in sync and select spans {scoped['idle_in_s']}")
    line = harness.result_line(cell, run, devs, True, reduced, log)
    ctx = readers.Context(cell, run, reduced, devs[0].device_kind)
    for name in readers.metric_names():
        if name not in line["metrics"]:
            v = readers.read(name, ctx)
            if v is not None:
                line["metrics"][name] = {"value": v}
    line["scopes"] = scoped
    return line


if __name__ == "__main__":
    main()
