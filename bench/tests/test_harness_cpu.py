"""The harness's control flow on the CPU at a tiny size: a whole run with
its trace, the result line's required keys, and the refusal to run without
a chip or without the program.  No number here is a device number."""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import harness
import readers
from smoke import smoke_cell

SEED = 2**31 + 977  # run seeds may exceed 32 signed bits


@pytest.fixture(scope="module")
def run():
    cell = smoke_cell("qwen3-0.6b.ae")
    os.environ["REPRO_TRACE_ANNOTATIONS"] = "1"  # read by the program at import
    t0 = time.perf_counter()
    out = harness.measure(cell, SEED, 1.0, True, t0, jax.devices(), log=lambda m: None)
    reduced = harness.reduce_trace(log=lambda m: None)
    return cell, out, reduced


def test_run_and_result_lines(run, monkeypatch):
    cell, out, reduced = run
    assert out["rounds"] >= 1 and out["compiles"] == 0
    assert set(out["numbers"]) == {"grad_gap", "residual_gap", "update_gap"}
    assert not os.path.exists(harness.TRACE_DIR)
    # the CPU has no entry in the peaks table; give the readers dummy peaks
    monkeypatch.setattr(readers, "peaks", lambda kind: {"flops_per_s": 1.0, "bytes_per_s": 1.0})
    for trace in (False, True):
        line = harness.result_line(cell, out, jax.devices(), trace, reduced)
        assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
        assert list(line)[-1] == "checks"
        assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
        want = {m["name"] for m in (cell.per_layer if trace else cell.end_to_end)}
        if trace:
            assert {"busy_s", "window_s"} <= set(line["device"])
            assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
            # interpret-mode kernels leave no kernel events: no roofline
            want -= {m for m in want if m.endswith("_roofline")}
        assert set(line["metrics"]) == want
        for v in line["checks"].values():
            assert set(v) == {"value", "limit"}
        json.dumps(line)


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-0.6b.ae", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _run_py(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
