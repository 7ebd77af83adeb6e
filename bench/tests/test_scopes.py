"""The layer-scope reduction (``bench/scopes.py``) and its readers, on a
small scoped trace recorded on the CPU (``record_scoped_trace.py``): three
rounds of 8 program runs, each round a ``fedqcs_round`` step with two 20 ms
``sync`` spans; and the placement rule on synthetic operations."""

import os

import pytest

import harness
import readers
import scopes
import trace
from record_scoped_trace import PROGRAMS_PER_ROUND, SLEEP_S

PATH = os.path.join(os.path.dirname(__file__), "data", "cpu_round_scoped.xplane.pb")
NEW_METRICS = ["client_pass_scope_ms", "decode_scope_ms", "apply_scope_ms",
               "dequantize_scope_ms", "gamp_scope_ms", "unscoped_ms", "host_sync_ms",
               "programs_per_round"]


@pytest.fixture(scope="module")
def reduced():
    return scopes.reduce(PATH)


class _Ctx:
    bench = harness.BENCH

    def __init__(self, scoped):
        self.trace = {"rounds": 3, "scopes": scoped}


def test_steps_and_programs_per_round(reduced):
    assert reduced["rounds"] == 3
    assert reduced["module_runs"] == 3 * PROGRAMS_PER_ROUND
    assert readers.read("programs_per_round", _Ctx(reduced)) == PROGRAMS_PER_ROUND


def test_host_sync_ms(reduced):
    # two sync spans a round, each a 20 ms sleep and a read of a ready value
    ms = readers.read("host_sync_ms", _Ctx(reduced))
    assert 2e3 * SLEEP_S <= ms < 2e3 * SLEEP_S + 15.0
    assert ms == pytest.approx(1e3 * reduced["sync_s"] / 3)
    # the device idles through most of the sleeps (the uplink program runs
    # during the first one)
    assert 0.8 * 6 * SLEEP_S <= reduced["idle_in_s"]["sync"] <= reduced["sync_s"]
    assert reduced["idle_in_s"]["select"] == 0.0  # the fixture has no select span


def test_every_layer_found_and_the_layers_sum_to_device_time(reduced):
    layers = reduced["layer_s"]
    assert set(layers) == {
        "fedqcs.uplink", "fedqcs.client", "fedqcs.client/stage", "fedqcs.client/encode",
        "fedqcs.decode", "fedqcs.decode/dequantize", "fedqcs.decode/gamp",
        "fedqcs.apply", "unscoped"}
    assert all(v > 0 for v in layers.values())
    assert sum(layers.values()) == pytest.approx(reduced["device_s"], rel=1e-12)
    # the eager concatenate is the one program with no scope
    assert [m for m, _ in reduced["unscoped_modules"]] == ["jit_concatenate"]
    assert reduced["op_names"] >= PROGRAMS_PER_ROUND


def test_device_time_is_the_leaf_operations_in_the_window(reduced):
    ops, _, spans = scopes.read_events(PATH)
    (w0, w1), = [(s, e) for n, s, e in spans if n == "window"]
    leaves = [o for o in trace._leaves(ops) if o[4] > w0 and o[3] < w1]
    total = sum(min(e, w1) - max(s, w0) for _, _, _, s, e in leaves) / 1e9
    assert reduced["device_s"] == pytest.approx(total, rel=1e-12)


def test_readers(reduced):
    ctx = _Ctx(reduced)
    got = {m: readers.read(m, ctx) for m in NEW_METRICS}
    layers = reduced["layer_s"]
    per = lambda *keys: 1e3 * sum(layers[k] for k in keys) / 3  # noqa: E731
    assert got["client_pass_scope_ms"] == pytest.approx(
        per("fedqcs.client", "fedqcs.client/stage", "fedqcs.client/encode"))
    assert got["decode_scope_ms"] == pytest.approx(
        per("fedqcs.decode", "fedqcs.decode/dequantize", "fedqcs.decode/gamp"))
    assert got["apply_scope_ms"] == pytest.approx(per("fedqcs.apply"))
    assert got["dequantize_scope_ms"] == pytest.approx(per("fedqcs.decode/dequantize"))
    assert got["gamp_scope_ms"] == pytest.approx(per("fedqcs.decode/gamp"))
    assert got["unscoped_ms"] == pytest.approx(per("unscoped"))
    # the layers and the unscoped time make up the device time
    assert (got["client_pass_scope_ms"] + got["decode_scope_ms"] + got["apply_scope_ms"]
            + got["unscoped_ms"] + per("fedqcs.uplink")) == pytest.approx(
                1e3 * reduced["device_s"] / 3)


def test_readers_find_nothing_without_scopes():
    """A program without the scopes, spans and steps (or a run whose trace
    was not reduced this way) reports none of the new metrics."""
    for scoped in (None, {"rounds": 0, "layer_s": {}, "sync_s": 0.0, "module_runs": 0}):
        ctx = _Ctx(scoped)
        assert all(readers.read(m, ctx) is None for m in NEW_METRICS)


def test_old_reduction_reads_the_scoped_trace():
    """The table-based reduction of ``bench/trace.py`` still places the
    scoped programs by their names: the new spans and steps are not its."""
    phases = harness.load_json(os.path.join(harness.BENCH, "phases.json"))
    red = trace.reduce(PATH, phases, {})
    mods = red["module_s"]
    assert red["phase_s"]["decode"] == pytest.approx(mods["jit__ps_fn"])
    # the named residual scatter runs after the decode: the update's
    assert red["phase_s"]["apply"] >= mods["jit__scatter_residuals"] > 0
    assert red["unplaced_modules"] == []


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_ps_fn)/fedqcs.decode/gamp/while/body/dot_general", "fedqcs.decode/gamp"),
    ("jit(_ps_fn)/fedqcs.decode/dequantize/gather", "fedqcs.decode/dequantize"),
    ("jit(_ps_fn)/fedqcs.decode/mul", "fedqcs.decode"),
    ("jit(_encode_segment_fn)/vmap(fedqcs.client)/encode/sort", "fedqcs.client/encode"),
    ("jit(one)/vmap(transpose(jvp(fedqcs.client)))/stage/mul", "fedqcs.client/stage"),
    ("jit(_lambda)/fedqcs.apply/scatter", "fedqcs.apply"),
    ("jit(_lambda)/fedqcs.uplink/gamp/x", "fedqcs.uplink"),  # not a sub-scope of uplink
    ("jit(concatenate)/concatenate", None),
    ("fedqcs.other/x", None),
    ("", None),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_place_rule():
    programs = {
        # every scoped op in one layer: unscoped instructions join it
        "jit__ps_fn(1)": {"fusion": "jit(_ps_fn)/fedqcs.decode/gamp/add",
                          "gather.2": "jit(_ps_fn)/fedqcs.decode/dequantize/gather",
                          "copy.3": ""},
        # scoped ops in two layers: an unscoped instruction stays unscoped
        "jit_mixed(2)": {"a": "jit(mixed)/fedqcs.client/add",
                         "b": "jit(mixed)/fedqcs.apply/add", "copy": ""},
        # no scoped op at all
        "jit_concatenate(3)": {"concatenate": "jit(concatenate)/concatenate"},
    }
    ops = [("jit__ps_fn(1)", "fusion"), ("jit__ps_fn(1)", "gather.2"),
           ("jit__ps_fn(1)", "copy.3"), ("jit_mixed(2)", "a"), ("jit_mixed(2)", "copy"),
           ("jit_concatenate(3)", "concatenate"), ("jit_unknown(4)", "x"), (None, "y")]
    assert scopes.place(ops, programs) == [
        "fedqcs.decode/gamp", "fedqcs.decode/dequantize", "fedqcs.decode",
        "fedqcs.client", "unscoped", "unscoped", "unscoped", "unscoped"]


def test_traced_run_reports_the_new_metrics(monkeypatch):
    """``bench/scopes.py``'s traced run at a CPU test's size: the result
    line of ``bench/run.py --trace 1`` with the new metrics beside the old,
    one ``fedqcs_round`` step per round of the window.  No number here is a
    device number."""
    import time

    import jax

    import repro.obs.trace as obs_trace
    from smoke import smoke_cell

    monkeypatch.setattr(obs_trace, "ANNOTATE", True)
    # the CPU has no entry in the peaks table; give the readers dummy peaks
    monkeypatch.setattr(readers, "peaks", lambda kind: {"flops_per_s": 1.0, "bytes_per_s": 1.0})
    cell = smoke_cell("qwen3-0.6b.ae")
    run = harness.measure(cell, 2**31 + 977, 1.0, True, time.perf_counter(), jax.devices(),
                          log=lambda m: None)
    line = scopes.traced_line(cell, run, jax.devices(), log=lambda m: None)
    assert not os.path.exists(harness.TRACE_DIR)
    assert set(NEW_METRICS) <= set(line["metrics"])
    assert {"client_pass_ms", "decode_ms", "apply_ms"} <= set(line["metrics"])
    red = line["scopes"]
    assert red["rounds"] == run["rounds"]
    assert red["op_names"] > 0 and red["sync_s"] > 0
    assert {"fedqcs.uplink", "fedqcs.client/stage", "fedqcs.client/encode",
            "fedqcs.decode/dequantize", "fedqcs.decode/gamp", "fedqcs.apply"} <= set(red["layer_s"])
    value = lambda m: line["metrics"][m]["value"]  # noqa: E731
    assert value("programs_per_round") > 10
    assert value("dequantize_scope_ms") + value("gamp_scope_ms") <= value("decode_scope_ms")


def _varint(v):
    out = b""
    while True:
        b, v = v & 0x7F, v >> 7
        out += bytes([b | (0x80 if v else 0)])
        if not v:
            return out


def _field(number, payload):
    """A length-delimited field (a string or a message)."""
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _instruction(name, op_name=None, calls=()):
    out = _field(1, name)
    if op_name is not None:
        out += _field(7, _field(2, op_name))  # OpMetadata.op_name
    if calls:
        out += _field(38, b"".join(_varint(c) for c in calls))  # packed ids
    return out


def _computation(cid, *instructions):
    return _field(1, f"c{cid}") + b"".join(_field(2, i) for i in instructions) \
        + _varint(5 << 3) + _varint(cid)


def test_loop_body_instructions_take_the_loop_scope():
    """An XLA-inserted instruction (no op_name) in a loop's body is the
    loop's work: it takes the scope of the instruction calling the body;
    one in the entry computation keeps none (the program rule decides)."""
    module = _field(3, _computation(
        2, _instruction("copy.7"),
        _instruction("fusion.8", "jit(_ps_fn)/fedqcs.decode/gamp/while/body/dot_general")))
    module += _field(3, _computation(
        1, _instruction("copy.1"),
        _instruction("while.3", "jit(_ps_fn)/fedqcs.decode/gamp/while", calls=[2])))
    names = scopes._instruction_op_names(_field(1, module))  # HloProto.hlo_module
    assert scopes.scope_of(names["copy.7"]) == "fedqcs.decode/gamp"
    assert scopes.scope_of(names["fusion.8"]) == "fedqcs.decode/gamp"
    assert names["copy.1"] == ""
