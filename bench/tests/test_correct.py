"""``correct`` comes out false for the control and for each fault a one-chip
training cell can have, judged by the cell's own limits.

* the control: the plain reference computed with int8 matmuls (the
  precision below the configuration's bfloat16) in the program's place;
* a round that returns its state unchanged;
* half of each client's batch left out, the mean taken over the rest.

Each drives the harness's run (set-up, window, reference, judgement) with
the timed path broken underneath, at a CPU test's size."""

import time

import jax
import pytest

import harness
import reference
from calibrate import HalfBatch
from smoke import smoke_cell

SEED = 2**31 + 4242


def _correct(cell, monkeypatch, fault):
    build = harness.build_engine

    def broken(cell, seed, params=None):
        engine = build(cell, seed, params)
        if fault == "unchanged":
            engine.run_round = lambda inspect=None: {}
        elif fault == "half_batch":
            engine.data = HalfBatch(engine.data)
        return engine

    monkeypatch.setattr(harness, "build_engine", broken)
    run = harness.measure(cell, SEED, 0.5, False, time.perf_counter(), jax.devices(),
                          log=lambda m: None)
    return harness.judge(run["numbers"], harness.limits_for(cell)), run["numbers"]


@pytest.mark.parametrize("name", ["qwen3-0.6b.ae", "qwen3-0.6b.ea"])
@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_not_correct(name, fault, monkeypatch):
    ok, numbers = _correct(smoke_cell(name), monkeypatch, fault)
    assert not ok, numbers


@pytest.mark.parametrize("name", ["qwen3-0.6b.ae", "qwen3-0.6b.ea"])
def test_control_is_not_correct(name):
    cell = smoke_cell(name)
    ref = harness.reference_first_steps(cell, SEED)
    ctl = harness.reference_first_steps(cell, SEED, precision="int8")
    numbers = reference.compare(ctl, ref)
    assert not harness.judge(numbers, harness.limits_for(cell)), numbers
