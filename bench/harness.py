"""The benchmark harness: builds a cell's federated round, warms it, measures
a window of rounds, reads the trace, and checks the first rounds against
the plain reference.

Everything that belongs to one cell is found by name: the configuration in
``bench/configs/<config>.json`` with its module ``<config>.py`` beside it
(seeded weights, model FLOPs, the float32 reference loss), the traffic in
``bench/traffic/<traffic>.json``, a per-layer metric in
``bench/metrics/<metric>.py`` and a kernel's operation and byte counts in
``bench/kernels/<kernel>.py``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Dict, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# The persistent compilation cache: a fixed path inside the checkout, so a
# cell's later runs there load every program instead of compiling it.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# Rounds that set-up drives before the window; the reference follows them.
FIRST_STEPS = 3


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    sizes: Dict[str, Any]  # the configuration file as run
    model: Any  # the configuration's module
    traffic: Dict[str, Any]
    per_layer: list  # BENCHMARK.json per-layer entries reported in this cell
    end_to_end: list


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    sizes = load_json(os.path.join(root, conf["file"]))
    model = load_module(os.path.splitext(os.path.join(root, conf["file"]))[0] + ".py",
                        "bench_config_" + w["config"].replace("-", "_").replace(".", "_"))
    traffic = load_json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))
    reports = lambda m: "workloads" not in m or name in m["workloads"]
    return Cell(name, w["chips"], sizes, model, traffic,
                [m for m in bench["per_layer"] if reports(m)],
                [m for m in bench["end_to_end"] if reports(m)])


def require_chip(chips: int):
    """Exits non-zero, printing no result, unless JAX sees a TPU with at
    least ``chips`` devices."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[bench] no TPU: JAX sees {devs[0].platform} devices", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"[bench] the cell needs {chips} chips, JAX sees {len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return devs


def enable_cache():
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # every program, however quick to compile: the round is many small ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an entry is written once and read by every later run
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Counts backend compiles (JAX's monitoring events) while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0

        def listener(event, duration, **kw):
            if self.on and event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def init_weights(cell: Cell, seed: int):
    """The cell's weights from ``seed``, on the device, in one jitted call,
    in the dtype they are stored in."""
    import jax
    import jax.numpy as jnp

    from data import seed_key

    dtype = jnp.bfloat16 if cell.sizes["torch_dtype"] == "bfloat16" else jnp.float32
    key = jax.random.fold_in(seed_key(seed), 0x5EED)
    return jax.jit(lambda k: cell.model.init_params(cell.sizes, k, dtype))(key)


def make_data(cell: Cell, seed: int):
    from data import ClientTokens

    t = cell.traffic
    return ClientTokens(seed, cell.sizes["vocab_size"], t["clients"], t["batch"], t["seq"],
                        noise=t["noise"], dialects=t["dialects"])


def build_engine(cell: Cell, seed: int, params=None):
    """The launcher's cohort round for this cell (``repro.launch.train``'s
    construction): interleaved segment producer, streamed encode, full
    participation, ideal uplink, FedAdam."""
    import jax

    from repro.core.compression import FedQCSConfig
    from repro.fed.channel import ChannelConfig
    from repro.fed.engine import CohortConfig, CohortEngine, make_interleaved_segments
    from repro.fed.scheduler import SchedulerConfig
    from repro.fed.server_opt import ServerOptConfig
    from repro.models import model
    from repro.models.segment_tap import interleaved_layout

    t, c, sv = cell.traffic, cell.traffic["codec"], cell.traffic["server"]
    cfg = cell.model.program_config(cell.sizes)
    fed = FedQCSConfig(
        block_size=c["block_size"], reduction_ratio=c["reduction_ratio"], bits=c["bits"],
        s_ratio=c["s_ratio"], gamp_iters=c["gamp_iters"],
        gamp_components=c["gamp_components"], gamp_variance_mode="scalar",
        recon_chunk=c["recon_chunk"], use_kernels=c["use_kernels"], seed=c["sensing_seed"],
    )
    if params is None:
        params = init_weights(cell, seed)
    want = jax.eval_shape(lambda k: model.init_params(cfg, k), jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got))
    ):
        raise SystemExit("the configuration's weights do not match the program's tree")
    layout = interleaved_layout(cfg, fed.block_size, layer_chunks=t["interleave"])
    producer = make_interleaved_segments(cfg, layout, grad_accum=t["grad_accum"],
                                         layer_chunks=t["interleave"])
    return CohortEngine(
        params,
        jax.grad(lambda p, b: model.train_loss(p, b, cfg)),
        make_data(cell, seed),
        fed_cfg=fed,
        cohort=CohortConfig(method=t["method"], encode_stream=True,
                            grad_accum=t["grad_accum"]),
        sched=SchedulerConfig(kind="full"),
        chan=ChannelConfig(),
        server=ServerOptConfig(kind="fedadam", lr=sv["lr"], b1=sv["b1"], b2=sv["b2"],
                               eps=sv["eps"]),
        layout=layout,
        grad_segments_fn=producer,
    )


def round_shape(cell: Cell) -> Dict[str, Any]:
    """The round's sizes that the kernel counts and the model FLOPs read."""
    import jax

    from reference import Blocking

    t, c = cell.traffic, cell.traffic["codec"]
    n, m, bits = c["block_size"], c["block_size"] // c["reduction_ratio"], c["bits"]
    params = jax.eval_shape(lambda: init_weights_abstract(cell))
    return {
        "method": t["method"], "clients": t["clients"],
        "rows": Blocking(params, n, t["interleave"]).rows,
        "n": n, "m": m, "bits": bits, "words": -(-m // (32 // bits)),
        "iters": c["gamp_iters"], "components": c["gamp_components"],
        "tokens": t["clients"] * t["batch"] * t["seq"], "seq": t["seq"],
    }


def init_weights_abstract(cell: Cell):
    import jax
    import jax.numpy as jnp

    return cell.model.init_params(cell.sizes, jax.random.PRNGKey(0), jnp.float32)


def sync(engine):
    import jax

    jax.block_until_ready((engine.params, engine.server_state, engine.residuals))


def span(name: str, on: bool):
    """A host span in the profiler's trace (a no-op unless tracing)."""
    import contextlib

    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def program_first_steps(engine, cell: Cell, seed: int, steps: int = FIRST_STEPS,
                        annotate: bool = False):
    """Drives the engine through its first ``steps`` rounds, the window's
    own call on rows that all differ, and reads what the reference is
    compared with: per-leaf norms of the aggregate as FedAdam received it in
    round 0 (from its first moment), of each client's residual after round
    0, and of the parameters' change after ``steps`` rounds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reference import change_norms, leaf_norms

    b1 = cell.traffic["server"]["b1"]
    out: Dict[str, Any] = {}
    for t in range(steps):
        with span("warmup", annotate):
            engine.run_round()
            sync(engine)
        if t == 0:
            out["ghat"] = leaf_norms(engine.server_state["m"]) / (1.0 - b1)
            out["residual"] = residual_norms(engine)
    out["update"] = change_norms(engine.params, init_weights(cell, seed))
    return out


def residual_norms(engine):
    """(clients, leaves) norms of the residual grid, summed over the rows of
    each leaf's layout segments (padding rows hold zeros)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows_sq = np.asarray(jax.jit(lambda r: jnp.sum(jnp.square(r), axis=-1))(
        engine.residuals), np.float64)
    n_leaves = len(jax.tree_util.tree_leaves(engine.params))
    sq = np.zeros((rows_sq.shape[0], n_leaves))
    for seg in engine.layout.segments:
        if len(seg.leaf_ids) != 1:
            raise SystemExit(f"layout segment {seg.name!r} holds several leaves")
        sq[:, seg.leaf_ids[0]] += rows_sq[:, seg.row_slice].sum(axis=1)
    return np.sqrt(sq)


def reference_first_steps(cell: Cell, seed: int, steps: int = FIRST_STEPS,
                          precision: str = "f32"):
    """The plain reference over the same first rounds, from the same seed."""
    import jax

    import reference

    params0 = init_weights(cell, seed)
    data = make_data(cell, seed)
    ids = list(range(cell.traffic["clients"]))
    with jax.default_matmul_precision("highest"):
        return reference.first_steps(cell.model, cell.sizes, cell.traffic, params0,
                                     lambda t: data.cohort_batch(t, ids), steps, precision)


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------


def limits_for(cell: Cell, bench: str = BENCH) -> Dict[str, float]:
    """The cell's limits of the numbers that decide ``correct``
    (``bench/limits/<cell>.json``; PERF.md gives the readings each was set
    from).  A run is correct when every number is within its limit."""
    return load_json(os.path.join(bench, "limits", cell.name + ".json"))


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(math.isfinite(v) and v <= limits[k] for k, v in numbers.items())


def device_info(devs, peak=None, extra=None):
    """The device as JAX reports it; ``peak`` is the program's peak, read
    before the reference ran (the process's peak never falls again)."""
    dev = devs[0]
    if peak is None:
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
            "memory_peak_bytes": peak}
    info.update(extra or {})
    return info


def measure(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
            devs, log=print) -> Dict[str, Any]:
    """One run: set-up (build, compile or load, the first rounds), the
    window of whole rounds, the trace reading, then the reference."""
    import jax

    counter = CompileCounter()
    shape = round_shape(cell)
    log(f"[bench] uplink {(shape['words'] * 32 + 32) / shape['n']!r} bits per entry "
        f"({shape['words']} words and a scale per {shape['n']}-entry block), "
        f"{shape['rows']} block rows per client")
    engine = build_engine(cell, seed)
    prog = program_first_steps(engine, cell, seed, annotate=trace)
    setup_s = time.perf_counter() - t_start
    log(f"[bench] set-up {setup_s!r} s ({FIRST_STEPS} rounds)")

    if trace:
        os.makedirs(TRACE_DIR, exist_ok=True)
        jax.profiler.start_trace(TRACE_DIR)
    counter.on = True
    rounds = 0
    t0 = time.perf_counter()
    marks = [t0]
    with span("window", trace):
        while True:
            with span("round", trace):
                engine.run_round()
            rounds += 1
            marks.append(time.perf_counter())
            if marks[-1] - t0 >= seconds:
                break
        sync(engine)
    window = time.perf_counter() - t0
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    laps = sorted(b - a for a, b in zip(marks, marks[1:]))
    log(f"[bench] window {window!r} s, {rounds} rounds, {counter.count} compiles inside; "
        f"host time per round min {laps[0]!r} median {laps[len(laps) // 2]!r} "
        f"max {laps[-1]!r}")
    peak = device_info(devs)["memory_peak_bytes"]
    del engine
    gc.collect()
    live = max(int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devs)
    log(f"[bench] peak {peak} bytes; {live} bytes in use before the reference")

    t_ref = time.perf_counter()
    ref = reference_first_steps(cell, seed)
    log(f"[bench] reference {time.perf_counter() - t_ref!r} s")
    import reference

    numbers = reference.compare(prog, ref)
    for name, where in reference.worst_leaves(prog, ref).items():
        log(f"[bench] {name} worst leaf {where}")
    log("[bench] leaf norms " + json.dumps(reference.norms_record(prog, ref)))
    return {"setup_s": setup_s, "window_s": window, "rounds": rounds,
            "peak_bytes": peak, "numbers": numbers, "compiles": counter.count,
            "shape": shape}


def reduce_trace(log=print) -> Dict[str, Any]:
    """Reads the window's trace, then deletes it."""
    import shutil

    import readers
    import trace

    path = trace.newest_xplane(TRACE_DIR)
    phases = load_json(os.path.join(BENCH, "phases.json"))
    kernels = {k: readers.kernel(k).PATTERN for k in readers.kernel_names()}
    reduced = trace.reduce(path, phases, kernels)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    log(f"[bench] trace: window {reduced['window_s']!r} s, busy {reduced['busy_s']!r} s, "
        f"{reduced['rounds']} rounds; phases {reduced['phase_s']}")
    log(f"[bench] trace: kernels {reduced['kernel_s']} calls {reduced['kernel_calls']}")
    log(f"[bench] trace: programs no phase holds {reduced['unplaced_modules']}")
    return reduced


def result_line(cell: Cell, run: Dict[str, Any], devs, trace: bool,
                reduced=None, log=print) -> Dict[str, Any]:
    limits = limits_for(cell)
    numbers = run["numbers"]
    correct = judge(numbers, limits)
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    metrics: Dict[str, Any] = {}
    extra = None
    if trace:
        import readers

        ctx = readers.Context(cell, run, reduced, devs[0].device_kind)
        for m in cell.per_layer:
            v = readers.read(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        for name, bound in ctx.bounds.items():
            log(f"[bench] {name} roofline: {bound}-bound at the chip's peaks")
        extra = {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
    else:
        values = {"round_s": run["window_s"] / run["rounds"],
                  "peak_hbm_gb": run["peak_bytes"] / 1e9,
                  "setup_s": run["setup_s"]}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    line = {"correct": correct, "attempted": run["rounds"] + FIRST_STEPS, "failed": 0,
            "metrics": metrics, "device": device_info(devs, run["peak_bytes"], extra)}
    if trace:
        line["breakdown"] = reduced["breakdown"]
    line["checks"] = checks
    return line
