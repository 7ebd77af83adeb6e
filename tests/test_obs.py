"""Observability-layer tests (DESIGN.md #Observability): the versioned
event schema and its JSONL roundtrip, sink equivalence, the jit-safe
decode-health counters (clip saturation, GAMP health, buffer accounting
under fault injection, post-combining aux), the recorded round events on
both the barrier and streaming engine paths, the ``ReconSpec.return_info``
API surface, and the run-log reader CLI."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, bussgang
from repro.core.compression import BQCSCodec, FedQCSConfig, packed_width
from repro.core.recon_engine import ReconSpec
from repro.fed.channel import (
    ChannelConfig,
    get_channel_family,
    mimo_tx_gain,
    realize_uplink,
)
from repro.fed.engine import ArrayClientData, CohortConfig, CohortEngine
from repro.fed.partition import PartitionConfig, partition_indices
from repro.fed.scheduler import SchedulerConfig
from repro.fed.server_opt import ServerOptConfig
from repro.fed.stream import StreamConfig, batch_arrivals, stream_decode
from repro.fed.toy import toy_classification, toy_loss, toy_params
from repro.obs import (
    NULL_RECORDER,
    InMemoryRecorder,
    JsonlRecorder,
    SCHEMA_VERSION,
    validate_event,
)
from repro.obs.reader import iter_events, load_meta, load_rounds, summarize, validate_dir
from repro.obs.schema import validate_run
from repro.obs.trace import SpanCollector, span

jax.config.update("jax_platform_name", "cpu")

FED = FedQCSConfig(block_size=64, reduction_ratio=2, bits=3, s_ratio=0.2,
                   gamp_iters=10, gamp_variance_mode="scalar")


def _engine(obs=None, stream=None, channel=None):
    xs, ys = toy_classification(n_samples=512)
    parts = partition_indices(
        ys, 8, PartitionConfig(kind="dirichlet", alpha=0.5, min_size=2))
    return CohortEngine(
        toy_params(), jax.grad(toy_loss),
        ArrayClientData(xs, ys, parts, batch_size=2),
        fed_cfg=FED,
        cohort=CohortConfig(method="fedqcs-ae"),
        sched=SchedulerConfig(),
        chan=channel or ChannelConfig(kind="awgn", snr_db=10.0),
        server=ServerOptConfig(lr=0.01),
        stream=stream,
        obs=obs,
    )


@pytest.fixture(scope="module")
def barrier_events():
    rec = InMemoryRecorder()
    _engine(obs=rec).run(2)
    return rec.events


@pytest.fixture(scope="module")
def stream_events():
    rec = InMemoryRecorder()
    _engine(obs=rec, stream=StreamConfig(batch_clients=3, deadline=1e9)).run(2)
    return rec.events


# ---------------------------------------------------------------------------
# schema + sinks
# ---------------------------------------------------------------------------


def test_jsonl_schema_roundtrip(tmp_path):
    """Events written by JsonlRecorder read back enveloped, schema-valid,
    in order, with numpy/jax payload values coerced to JSON natives."""
    run_dir = str(tmp_path / "run_a")
    with JsonlRecorder(run_dir, config={"method": "fedqcs-ae", "Q": 3}) as rec:
        rec.record("round", {"round": 0, "cohort": 8, "participating": 7.0,
                             "nmse": np.float32(0.25),
                             "gamp_iters_mean": jnp.asarray(12.5)})
        rec.record("eval", {"round": 0, "accuracy": 0.9, "loss": 0.3})
        rec.record("span", {"name": "decode", "ms": 1.5})
        rec.record("note", {"msg": "checkpointed"})
    meta = load_meta(run_dir)
    events = list(iter_events(run_dir))
    assert validate_run(meta, events) == []
    assert meta["schema_version"] == SCHEMA_VERSION
    assert meta["config"]["Q"] == 3
    assert [ev["kind"] for ev in events] == ["round", "eval", "span", "note"]
    assert [ev["seq"] for ev in events] == [0, 1, 2, 3]
    rnd = events[0]
    assert rnd["v"] == SCHEMA_VERSION
    assert rnd["nmse"] == pytest.approx(0.25)  # np scalar -> plain float
    assert isinstance(rnd["nmse"], float) and isinstance(rnd["gamp_iters_mean"], float)
    # the file really is one JSON object per line
    with open(tmp_path / "run_a" / "events.jsonl") as f:
        assert all(json.loads(line) for line in f)


def test_jsonl_meta_outside_git_checkout(tmp_path, monkeypatch):
    """A run started outside any git checkout (an exported source tree)
    records ``git_sha: null`` in a schema-valid meta.json, without raising."""
    work = tmp_path / "export"
    work.mkdir()
    monkeypatch.chdir(work)
    # git must not find a repository above the directory either
    monkeypatch.setenv("GIT_CEILING_DIRECTORIES", str(tmp_path))
    with JsonlRecorder(str(work / "run")) as rec:
        rec.record("note", {"msg": "no checkout"})
    meta = load_meta(str(work / "run"))
    assert meta["git_sha"] is None
    assert validate_run(meta, list(iter_events(str(work / "run")))) == []


def test_validate_catches_malformed_events():
    ok = {"v": SCHEMA_VERSION, "kind": "round", "seq": 0, "t": 0.1,
          "round": 0, "cohort": 4, "participating": 4.0, "mystery_field": 1}
    assert validate_event(ok) == []  # unknown payload fields are fine
    assert validate_event({**ok, "v": 99})  # wrong version
    assert validate_event({**ok, "kind": "nope"})  # unknown kind
    bad = dict(ok)
    del bad["cohort"]
    assert any("cohort" in p for p in validate_event(bad))
    meta = {"run_id": "x", "schema_version": SCHEMA_VERSION, "created_unix": 0.0}
    assert validate_run(meta, [ok, {**ok, "seq": 0}])  # seq not monotone


def test_sink_equivalence(tmp_path):
    """The in-memory and JSONL sinks produce identical enveloped events for
    the same record() sequence (timestamps aside)."""
    payloads = [("round", {"round": 0, "cohort": 2, "participating": 2.0}),
                ("eval", {"round": 0, "loss": 1.0}),
                ("note", {"msg": "hi"})]
    mem = InMemoryRecorder()
    jsl = JsonlRecorder(str(tmp_path / "run_b"))
    for kind, p in payloads:
        mem.record(kind, p)
        jsl.record(kind, p)
    jsl.close()
    disk = list(iter_events(str(tmp_path / "run_b")))
    assert len(mem.events) == len(disk) == len(payloads)
    for a, b in zip(mem.events, disk):
        a, b = dict(a), dict(b)
        a.pop("t"), b.pop("t")
        assert a == b


def test_null_recorder_is_inert_default():
    assert NULL_RECORDER.active is False
    NULL_RECORDER.record("round", {"anything": 1})  # no-op, no error
    NULL_RECORDER.close()
    eng = _engine()  # no obs -> the null singleton, no aux collection
    assert eng.obs is NULL_RECORDER
    stats = eng.run_round()
    assert "gamp_iters_mean" not in stats  # health aux only when collecting
    assert all(np.isfinite(float(v)) for v in stats.values())


def test_span_collector_accumulates_and_drains():
    col = SpanCollector()
    with span("decode", col):
        pass
    with span("decode", col):
        pass
    with span("apply", col):
        pass
    assert set(col.ms) == {"decode", "apply"}
    drained = col.drain()
    assert drained["decode"] >= 0.0 and col.ms == {}
    with span("free"):  # collector-less: pure no-op timing
        pass


# ---------------------------------------------------------------------------
# decode-health counters
# ---------------------------------------------------------------------------


def test_clip_saturation_counts_extreme_lanes():
    """The counter is exactly the fraction of code lanes at an extreme
    level, packed and unpacked views agree, and a vq codebook (no level
    order) reports a constant 0."""
    codec = BQCSCodec(FED)
    nlev = codec.codebook.n_levels
    # known input: half the lanes pinned at the extremes
    idx = jnp.asarray(
        np.tile([0, nlev - 1, 1, nlev - 2], codec.cfg.m // 4), jnp.uint8
    )[None, :]
    assert float(codec.clip_saturation(idx, packed=False)) == pytest.approx(0.5)
    # packed/unpacked parity on real payloads
    blocks = jax.random.normal(jax.random.PRNGKey(0), (3, FED.block_size))
    words, _, _ = codec.compress_blocks_packed(blocks, jnp.zeros_like(blocks))
    codes, _, _ = codec.compress_blocks(blocks, jnp.zeros_like(blocks))
    sat_w = float(codec.clip_saturation(words, packed=True))
    sat_c = float(codec.clip_saturation(codes, packed=False))
    assert sat_w == pytest.approx(sat_c)
    assert sat_w == pytest.approx(
        float(np.mean((np.asarray(codes) == 0) | (np.asarray(codes) == nlev - 1))))
    vq_codec = BQCSCodec(FedQCSConfig(
        block_size=64, reduction_ratio=2, bits=4, s_ratio=0.2,
        gamp_iters=5, codebook="vq", vq_dim=2))
    assert float(vq_codec.clip_saturation(jnp.zeros((1, 4), jnp.uint8),
                                          packed=False)) == 0.0


def test_buffer_accounting_under_faults():
    """One streamed round under combined faults: a dropped batch shrinks
    admissions, duplicates are counted but never admitted, reordering
    changes neither, and a 1-slot buffer counts every forced drain."""
    codec = BQCSCodec(FED)
    c, nb = 9, 2
    blocks = jax.random.normal(jax.random.PRNGKey(1), (c, nb, FED.block_size))
    words, alphas, _ = jax.vmap(codec.compress_blocks_packed)(
        blocks, jnp.zeros_like(blocks))
    w = np.ones(c, np.float32)
    scfg = StreamConfig(batch_clients=3, buffer_batches=4)
    batches = batch_arrivals(np.arange(c, dtype=float), 1e9, 3)  # 3 batches
    _, clean = stream_decode(codec, words, alphas, w, batches, stream=scfg)
    assert clean["batches_admitted"] == 3
    assert clean["batches_rejected_dup"] == 0
    assert clean["participating"] == float(c)

    # drop batch 1, deliver batch 2 twice, reversed order
    faulty = [batches[2], batches[0], batches[2]]
    _, info = stream_decode(codec, words, alphas, w, faulty, stream=scfg)
    assert info["batches_admitted"] == 2
    assert info["batches_rejected_dup"] == 1
    assert info["participating"] == float(c - 3)

    # 1-slot buffer: every push after the first forces a drain
    tight = StreamConfig(batch_clients=3, buffer_batches=1)
    _, info = stream_decode(codec, words, alphas, w, batches, stream=tight)
    assert info["batches_backpressure"] == len(batches) - 1
    assert info["buffer_peak_occupancy"] == 1
    assert info["batches_admitted"] == 3
    assert clean["batches_backpressure"] == 0  # roomy buffer: none


def test_stream_decode_health_counters():
    """collect_health=True streams GAMP health out of the folds (EA) or the
    finalize decode (AE) without changing the decoded aggregate."""
    codec = BQCSCodec(FED)
    c, nb = 8, 2
    blocks = jax.random.normal(jax.random.PRNGKey(2), (c, nb, FED.block_size))
    words, alphas, _ = jax.vmap(codec.compress_blocks_packed)(
        blocks, jnp.zeros_like(blocks))
    w = np.ones(c, np.float32)
    batches = batch_arrivals(np.arange(c, dtype=float), 1e9, 4)
    for mode in ("ae", "ea"):
        ref, _ = stream_decode(
            codec, words, alphas, w, batches, mode=mode,
            stream=StreamConfig(batch_clients=4))
        from repro.fed.stream import StreamingPS

        ps = StreamingPS(codec, mode=mode, stream=StreamConfig(batch_clients=4),
                         collect_health=True)
        got, info = stream_decode(
            codec, words, alphas, w, batches, mode=mode,
            stream=StreamConfig(batch_clients=4), ps=ps)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        assert 0.0 < info["gamp_iters_mean"] <= FED.gamp_iters
        assert info["gamp_iters_max"] <= FED.gamp_iters
        assert 0.0 <= info["gamp_converged_frac"] <= 1.0


def test_mimo_combine_aux_counters():
    """with_aux=True surfaces the post-combining CSI health: near-zero
    target mismatch under perfect CSI, strictly worse under CSI error."""
    codec = BQCSCodec(FED)
    c, nb = 4, 2
    blocks = jax.random.normal(jax.random.PRNGKey(3), (c, nb, FED.block_size))
    words, alphas, _ = jax.vmap(codec.compress_blocks_packed)(
        blocks, jnp.zeros_like(blocks))
    w = jnp.ones((c,), jnp.float32)
    mism = {}
    for err in (0.0, 0.3):
        chan = ChannelConfig(kind="mimo_mac", snr_db=40.0, n_rx=16, csi_error=err)
        fam = get_channel_family("mimo_mac")
        real = realize_uplink(chan, jax.random.PRNGKey(4), c, nb)
        deq = codec.codebook.decode_packed(words, codec.cfg.m)
        wq = bussgang.bussgang_weight(w[:, None], alphas, codec.codebook)
        active = (w > 0).astype(jnp.float32)
        eta = mimo_tx_gain(wq, active)
        y_rx = fam.transmit(chan, real, (eta * wq)[..., None] * deq,
                            jax.random.PRNGKey(5))
        y_eff, nu, aux = fam.combine(chan, real, y_rx, wq, active,
                                     psi=codec.codebook.psi, tx_gain=eta,
                                     with_aux=True)
        assert set(aux) >= {"csi_target_mismatch", "combiner_norm2"}
        assert float(aux["combiner_norm2"]) > 0.0
        mism[err] = float(aux["csi_target_mismatch"])
    assert mism[0.0] == pytest.approx(0.0, abs=1e-6)  # perfect CSI
    assert mism[0.3] > mism[0.0]


# ---------------------------------------------------------------------------
# engine round events: barrier + streaming paths
# ---------------------------------------------------------------------------


def _round_events(events):
    return [ev for ev in events if ev["kind"] == "round"]


def test_barrier_round_events(barrier_events):
    rounds = _round_events(barrier_events)
    assert len(rounds) == 2
    for i, ev in enumerate(rounds):
        assert validate_event(ev) == []
        assert ev["round"] == i
        assert ev["cohort"] == 8
        # decode health rides every round
        assert 0.0 < ev["gamp_iters_mean"] <= FED.gamp_iters
        assert ev["gamp_iters_max"] <= FED.gamp_iters
        assert 0.0 <= ev["gamp_converged_frac"] <= 1.0
        assert 0.0 <= ev["clip_saturation"] <= 1.0
        assert np.isfinite(ev["nmse"])
        assert ev["update_norm"] > 0.0 and ev["param_norm"] > 0.0
        # the barrier phase vocabulary, and round_ms is their sum
        assert set(ev["phase_ms"]) == {"select", "uplink", "sync", "client_pass",
                                       "decode", "apply"}
        assert ev["round_ms"] == pytest.approx(sum(ev["phase_ms"].values()))
        # wire accounting: packed words + one f32 alpha per block, up;
        # an nbar-f32 model broadcast per cohort member, down
        codec = BQCSCodec(FED)
        width = packed_width(codec.codebook.n_codes(FED.m), codec.codebook.bits)
        nb = -(-toy_params_size() // FED.block_size)
        assert ev["wire_up_bytes"] == pytest.approx(
            ev["participating"] * nb * (width * 32 + 32) / 8.0)
        assert ev["wire_down_bytes"] == pytest.approx(
            ev["cohort"] * toy_params_size() * 4.0)


def toy_params_size():
    return sum(x.size for x in jax.tree_util.tree_leaves(toy_params()))


def test_streaming_round_events(stream_events):
    rounds = _round_events(stream_events)
    assert len(rounds) == 2
    for ev in rounds:
        assert validate_event(ev) == []
        # buffer accounting rides the streaming round event
        assert ev["batches_admitted"] >= 1
        assert ev["buffer_peak_occupancy"] >= 1
        assert ev["batches_rejected_dup"] == 0
        assert ev["batches_backpressure"] >= 0
        assert ev["peak_live_stats_bytes"] > 0
        # health from the finalize decode + the saturation counter
        assert 0.0 < ev["gamp_iters_mean"] <= FED.gamp_iters
        assert 0.0 <= ev["clip_saturation"] <= 1.0
        # the streaming phase vocabulary: fold, not decode
        assert set(ev["phase_ms"]) == {"select", "uplink", "sync", "client_pass",
                                       "fold", "apply"}


def test_round_stats_unchanged_by_recording(barrier_events):
    """The recorder must not perturb the round itself: the same seeded
    engine without a recorder walks the same parameter trajectory."""
    eng = _engine()
    stats = [eng.run_round() for _ in range(2)]
    rounds = _round_events(barrier_events)
    for s, ev in zip(stats, rounds):
        assert s["nmse"] == pytest.approx(ev["nmse"], rel=1e-5)


# ---------------------------------------------------------------------------
# ReconSpec.return_info
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["ae", "ea"])
def test_reconstruct_return_info(mode):
    codec = api.make_codec(FED)
    grads = {"w": jax.random.normal(jax.random.PRNGKey(6), (200,))}
    state = api.init_state(codec, grads)
    payloads = []
    for k in range(3):
        g = {"w": jax.random.normal(jax.random.PRNGKey(10 + k), (200,))}
        p, spec, _ = api.compress(codec, g, state)
        payloads.append(p)
    rhos = [1 / 3] * 3
    bare = api.reconstruct(codec, payloads, rhos, spec, recon=ReconSpec(mode=mode))
    tree, info = api.reconstruct(
        codec, payloads, rhos, spec,
        recon=ReconSpec(mode=mode, return_info=True))
    np.testing.assert_allclose(
        np.asarray(tree["w"]), np.asarray(bare["w"]), rtol=1e-6)
    assert set(info) >= {"converged", "iters", "gamp_iters_mean",
                         "gamp_iters_max", "gamp_converged_frac"}
    assert 0.0 < float(info["gamp_iters_mean"]) <= FED.gamp_iters
    assert 0.0 <= float(info["gamp_converged_frac"]) <= 1.0
    assert int(np.max(np.asarray(info["iters"]))) <= FED.gamp_iters


# ---------------------------------------------------------------------------
# reader CLI over a real engine run
# ---------------------------------------------------------------------------


def test_jsonl_run_summarize_and_validate(tmp_path):
    run_dir = str(tmp_path / "run_c")
    rec = JsonlRecorder(run_dir, config={"clients": 8})
    eng = _engine(obs=rec)
    eng.run_round()
    rec.record("eval", {"round": 0, "accuracy": 0.5})
    rec.close()
    assert validate_dir(run_dir) == []
    out = summarize(run_dir)
    assert "rnd" in out and "nmse" in out and "it_mean" in out
    assert "phase wall-clock" in out and "decode health" in out
    assert len(load_rounds(run_dir)) == 1
    # closed recorder refuses further events rather than corrupting the log
    with pytest.raises(ValueError, match="close"):
        rec.record("note", {})
