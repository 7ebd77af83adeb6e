"""The benchmark's one command: a run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's federated round on the chip, drives its first rounds from
the seed (set-up, with the compiles or cache loads), runs whole rounds for
``--seconds``, and checks those first rounds against the plain float32
reference.  The last line of standard output is one JSON object: the cell's
end-to-end metrics (``--trace 0``) or its per-layer metrics from a profiler
trace of the window (``--trace 1``), ``correct``, and the device; each number
compared with the reference is printed beside its limit, on standard error
and under ``checks``.  Exits non-zero, printing no result, without a TPU or
outside a checkout of the repository.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"[bench] no program: {os.path.join(ROOT, 'src', 'repro')} is missing")
        sys.exit(2)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    # libtpu writes no logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.trace:
        # the engine's phase spans become profiler annotations; set before
        # the program is imported (it reads this once)
        os.environ["REPRO_TRACE_ANNOTATIONS"] = "1"

    import harness

    cell = harness.load_cell(args.workload)
    devs = harness.require_chip(cell.chips)
    harness.enable_cache()
    log(f"[bench] {cell.name} on {devs[0].device_kind} x{len(devs)}, seed {args.seed}")
    run = harness.measure(cell, args.seed, args.seconds, bool(args.trace), T_START, devs, log)
    reduced = harness.reduce_trace(log) if args.trace else None
    line = harness.result_line(cell, run, devs, bool(args.trace), reduced, log)
    for k, v in line["checks"].items():
        log(f"[bench] check {k} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(line), flush=True)
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
