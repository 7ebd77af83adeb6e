"""The trace reduction on a small trace recorded on the CPU
(``record_trace.py``): three rounds, a 50 ms host sleep inside ``uplink``
with the device idle in each."""

import os

import pytest

import harness
import trace

PATH = os.path.join(os.path.dirname(__file__), "data", "cpu_round.xplane.pb")
PHASES = harness.load_json(os.path.join(harness.BENCH, "phases.json"))


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(PATH, PHASES, {"sorts": r"^sort", "absent": r"^no_such_op"})


def test_window_and_rounds(reduced):
    assert reduced["rounds"] == 3
    assert reduced["devices"] == 1
    assert 0.15 < reduced["window_s"] < 5.0
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_busy_is_the_union_of_operation_intervals(reduced):
    ops, _, spans = trace.read_events(PATH)
    (w0, w1), = [(s, e) for n, s, e in spans if n == "window"]
    ivs = sorted((max(s, w0), min(e, w1)) for _, _, _, s, e in ops if e > w0 and s < w1)
    busy, end = 0.0, w0
    for s, e in ivs:
        busy += max(0.0, e - max(s, end))
        end = max(end, e)
    assert reduced["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)


def test_modules_placed_by_the_table(reduced):
    phases = reduced["phase_s"]
    assert set(phases) == {"client_pass", "decode", "apply"}
    mods = reduced["module_s"]
    assert phases["decode"] == pytest.approx(mods["jit__ps_fn"])
    # the update's lambda runs after the decode, so it is the update's
    assert phases["apply"] == pytest.approx(mods["jit__lambda"])
    assert phases["client_pass"] == pytest.approx(
        mods["jit_one"] + mods["jit__encode_segment_fn"] + mods["jit__prep_fn"])
    assert reduced["unplaced_modules"] == []


def test_place_after_decode_rule():
    mods = [("d", n, None, i, i + 1) for i, n in enumerate(
        ["jit__prep_fn", "jit__lambda", "jit_one", "jit__ps_fn", "jit_scatter",
         "jit__lambda", "jit_x", "jit__prep_fn", "jit__lambda"])]
    assert trace.place(mods, PHASES) == [
        "client_pass", "client_pass", "client_pass", "decode", "apply",
        "apply", "apply", "client_pass", "client_pass"]


def test_kernel_events_and_breakdown(reduced):
    assert reduced["kernel_calls"] == {"sorts": 3, "absent": 0}
    assert reduced["kernel_s"]["absent"] == 0.0
    ops = reduced["breakdown"]["device_ops"]
    assert ops[0][0].startswith("sort") and len(ops) <= 10
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    # the host slept 3 x 50 ms in uplink with nothing on the device
    assert gaps["uplink"] >= 0.15
    assert max(gaps, key=gaps.get) == "uplink"
