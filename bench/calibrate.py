"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <name> --seeds 12 --control-seeds 3 \
        --fault-seeds 3 --out <file.json>

In one process on the chip (set-up is long, so the program's programs
compile once): the program's first rounds on each seed against the plain
reference (the lower readings); the reference in int8 put in the program's
place (the control: the upper readings); and the program with half of each
client's batch left out, the mean taken over the rest (a planted fault).  A
step that returns its state unchanged reads 1 on ``update_gap`` and
``grad_gap`` by construction and needs no run.  Prints and writes every
reading; the benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class HalfBatch:
    """Client data with half of each client's sequences left out."""

    def __init__(self, data):
        self.data = data
        self.counts = data.counts

    def cohort_batch(self, round_idx, ids):
        batch = self.data.cohort_batch(round_idx, ids)
        half = batch["tokens"].shape[1] // 2
        return {k: v[:, :half] for k, v in batch.items()}


def reset(engine, harness, cell, seed, data=None):
    """Puts a built engine back at round 0 with ``seed``'s weights and data
    (its compiled programs are kept)."""
    import jax.numpy as jnp

    from repro.fed.scheduler import SchedulerState
    from repro.fed.server_opt import init_server_state

    engine.params = harness.init_weights(cell, seed)
    engine.server_state = init_server_state(engine.server, engine.params)
    engine.residuals = jnp.zeros_like(engine.residuals)
    engine.sched_state = SchedulerState.init(engine.clients)
    engine.round = 0
    engine.data = data if data is not None else harness.make_data(cell, seed)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_000_000_011)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import harness
    import reference

    cell = harness.load_cell(args.workload)
    devs = harness.require_chip(cell.chips)
    harness.enable_cache()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    out = {"cell": cell.name, "seeds": seeds, "program": [], "control": [], "half_batch": []}

    def save():
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    def log(msg):
        print(f"[calibrate] {msg} ({time.perf_counter() - T_START:.1f} s)", flush=True)

    # the program's first rounds on every seed, then the faulted program
    prog, half = {}, {}
    engine = harness.build_engine(cell, seeds[0])
    for s in seeds:
        reset(engine, harness, cell, s)
        prog[s] = harness.program_first_steps(engine, cell, s)
        log(f"program seed {s}")
    for s in seeds[: args.fault_seeds]:
        reset(engine, harness, cell, s, HalfBatch(harness.make_data(cell, s)))
        half[s] = harness.program_first_steps(engine, cell, s)
        log(f"half batch seed {s}")
    del engine
    gc.collect()

    for s in seeds:
        ref = harness.reference_first_steps(cell, s)
        row = {"seed": s, **reference.compare(prog[s], ref)}
        row["worst"] = reference.worst_leaves(prog[s], ref)
        row["norms"] = reference.norms_record(prog[s], ref)
        out["program"].append(row)
        if s in half:
            out["half_batch"].append({"seed": s, **reference.compare(half[s], ref),
                                      "norms": reference.norms_record(half[s], ref)})
            log(f"half batch {out['half_batch'][-1]}")
        if len(out["control"]) < args.control_seeds:
            ctl = harness.reference_first_steps(cell, s, precision="int8")
            out["control"].append({"seed": s, **reference.compare(ctl, ref),
                                   "worst": reference.worst_leaves(ctl, ref),
                                   "norms": reference.norms_record(ctl, ref)})
            log(f"control {out['control'][-1]}")
        log(f"program {row}")
        save()
    for key in ("program", "control", "half_batch"):
        rows = out[key]
        if rows:
            names = [k for k in rows[0] if k not in ("seed", "worst", "norms")]
            log(f"{key}: " + ", ".join(
                f"{k} max {max(r[k] for r in rows)!r} min {min(r[k] for r in rows)!r}"
                for k in names))
    save()


if __name__ == "__main__":
    main()
