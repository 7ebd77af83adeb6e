"""Layer scopes of the cohort round's device programs (obs/trace.py).

Every jitted program of the round runs its body under one top-level
``jax.named_scope`` naming its layer (``fedqcs.uplink``, ``fedqcs.client``,
``fedqcs.decode``, ``fedqcs.apply``), so a profile can place each device
operation by the ``op_name`` its HLO instruction carries.  Pinned here, on
programs compiled for the CPU from a toy qwen3 round through the
interleaved segment producer (the benchmark's path):

* every instruction traced from the program's body (``op_name`` starting
  ``jit(``) carries its layer's scope, in AE and EA, kernel and XLA paths;
* the sub-scopes: AE's dequantize gather under ``fedqcs.decode/dequantize``,
  the GAMP solve under ``fedqcs.decode/gamp``, the per-segment encode under
  ``fedqcs.client/encode``, the producer's stage programs under
  ``fedqcs.client/stage`` (but for the loop invariants JAX hoists out of a
  layer scan's VJP, which keep no scope and no other layer's);
* the scopes rename no program (a profile's reduction places programs by
  name), and the residual scatter is its own named program;
* with annotations on, each round is a ``fedqcs_round`` profiler step and
  the host's waits on device values are ``sync`` spans.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import smoke_config
from repro.core.compression import FedQCSConfig
from repro.fed.engine import CohortConfig, CohortEngine, make_interleaved_segments
from repro.models import model as M
from repro.models.segment_tap import interleaved_layout

jax.config.update("jax_platform_name", "cpu")

C = 2
# Each engine program, the name its compiled module carries and the scope
# every traced op in it carries.
PROGRAMS = {
    "_uplink_jit": ("jit__lambda", "fedqcs.uplink"),
    "_prep_jit": ("jit__prep_fn", "fedqcs.client"),
    "_encode_seg_jit": ("jit__encode_segment_fn", "fedqcs.client/encode"),
    "_seg_true_sum_jit": ("jit__lambda", "fedqcs.client"),
    "_ps_jit": ("jit__ps_fn", "fedqcs.decode"),
    "_scatter_jit": ("jit__scatter_residuals", "fedqcs.apply"),
    "_apply_jit": ("jit__lambda", "fedqcs.apply"),
}


class _Data:
    counts = np.ones(C, np.int64)

    def __init__(self, vocab):
        self.vocab = vocab

    def cohort_batch(self, t, ids):
        tok = (jnp.ones((C, 2, 16), jnp.int32) + t) % self.vocab
        return {"tokens": tok, "labels": (tok + 2) % self.vocab}


def _engine(method, kernels):
    fed = FedQCSConfig(block_size=64, reduction_ratio=2, bits=3, gamp_iters=3,
                       recon_chunk=512, use_kernels=kernels,
                       gamp_variance_mode="scalar")
    cfg = smoke_config("qwen3-0.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    layout = interleaved_layout(cfg, fed.block_size, layer_chunks=2)
    prod = make_interleaved_segments(cfg, layout, layer_chunks=2)
    eng = CohortEngine(params, jax.grad(lambda p, b: M.train_loss(p, b, cfg)),
                       _Data(cfg.vocab_size), fed_cfg=fed,
                       cohort=CohortConfig(method=method, encode_stream=True),
                       layout=layout, grad_segments_fn=prod)
    return eng, prod


def _spec(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype) if hasattr(x, "shape") else x


def _recording(jitted, calls, key):
    """``jitted``, keeping the argument shapes of its first call under
    ``key`` (a donated buffer is gone after the call)."""

    def call(*args):
        calls.setdefault(key, (jitted, jax.tree_util.tree_map(_spec, args)))
        return jitted(*args)

    return call


def _compiled(jitted, args):
    return jitted.lower(*args).compile().as_text()


def _module(text):
    return re.search(r"^HloModule ([^,\s]+)", text, re.M).group(1)


def _in_scope(op_name, scope):
    """``op_name`` lies under ``scope`` (``layer`` or ``layer/sub``); a
    transform wraps the layer, as ``vmap(fedqcs.client)/encode/...``."""
    layer, _, sub = scope.partition("/")
    return re.search(re.escape(layer) + r"\)*" + (f"/{sub}/" if sub else "/"),
                     op_name) is not None


def _traced_op_names(text):
    """The op_name of every instruction traced from the program's body
    (parameters carry their argument's name and reducer bodies a bare
    primitive name: neither starts with the program's ``jit(...)``)."""
    return [n for n in re.findall(r'op_name="([^"]*)"', text) if n.startswith("jit(")]


@pytest.fixture(scope="module", params=[("fedqcs-ae", False), ("fedqcs-ae", True),
                                        ("fedqcs-ea", False), ("fedqcs-ea", True)],
                ids=["ae-xla", "ae-kernels", "ea-xla", "ea-kernels"])
def round_programs(request):
    """{program: compiled HLO text} of one round of the toy engine."""
    method, kernels = request.param
    eng, _ = _engine(method, kernels)
    calls = {}
    for name in PROGRAMS:
        setattr(eng, name, _recording(getattr(eng, name), calls, name))
    eng.run_round()
    assert set(calls) == set(PROGRAMS)
    return method, kernels, {n: _compiled(*calls[n]) for n in PROGRAMS}


def test_programs_keep_their_names(round_programs):
    _, _, texts = round_programs
    assert {n: _module(t) for n, t in texts.items()} == {
        n: mod for n, (mod, _) in PROGRAMS.items()}


def test_every_traced_op_carries_its_layer_scope(round_programs):
    _, _, texts = round_programs
    for name, text in texts.items():
        scope = PROGRAMS[name][1]
        names = _traced_op_names(text)
        assert names, name
        missing = [n for n in names if not _in_scope(n, scope)]
        assert not missing, (name, missing[:5])


def test_decode_sub_scopes(round_programs):
    method, kernels, texts = round_programs
    text = texts["_ps_jit"]
    names = _traced_op_names(text)
    gamp = [n for n in names if _in_scope(n, "fedqcs.decode/gamp")]
    assert gamp
    if kernels:  # the kernel runs inside the GAMP solve's scope
        kernel = "qgamp_step_pallas" if method == "fedqcs-ea" else "gamp_step_pallas"
        assert any(f"/{kernel}/" in n for n in gamp)
    gathers = [re.search(r'op_name="([^"]*)"', line).group(1)
               for line in text.splitlines() if " gather(" in line]
    if method == "fedqcs-ae":
        # levels[codes], the Bussgang aggregate's dequantize: a select chain
        # over the levels, not a table gather
        dequantize = [n for n in names if _in_scope(n, "fedqcs.decode/dequantize")]
        assert any(n.endswith("/select_n") for n in dequantize), dequantize
        assert not any(_in_scope(n, "fedqcs.decode/dequantize") for n in gathers)
    else:  # EA dequantizes inside the per-client solve
        assert not any("/dequantize/" in n for n in names)


def test_stage_programs_carry_the_stage_scope():
    eng, prod = _engine("fedqcs-ae", False)
    calls = {}
    # the producer's programs: per-stage lists, two shared ones and the
    # per-segment assemblers
    for k, j in enumerate(list(prod._fwd_jits)):
        prod._fwd_jits[k] = _recording(j, calls, f"fwd{k}")
    for k, j in enumerate(list(prod._bwd_jits)):
        prod._bwd_jits[k] = _recording(j, calls, f"bwd{k}")
    prod._ctx_jit = _recording(prod._ctx_jit, calls, "ctx")
    prod._add_jit = _recording(prod._add_jit, calls, "add")
    assemble = prod._assemble
    prod._assemble = lambda i: _recording(assemble(i), calls, f"asm{i}")
    eng.run_round()
    mods = {}
    for key, (jitted, args) in calls.items():
        text = _compiled(jitted, args)
        mods[key] = _module(text)
        names = _traced_op_names(text)
        staged = [n for n in names if _in_scope(n, "fedqcs.client/stage")]
        # (an assembler that only reshapes one piece compiles to a bitcast
        # of its argument: no traced op left)
        assert staged or (key.startswith("asm") and not names), key
        # the rest is what JAX hoists out of the layer scan's VJP as loop
        # invariant (the RoPE tables, the causal mask): it keeps no name
        # scope at all, so a reader places it by its program's one layer
        assert not [n for n in names if n not in staged and "fedqcs." in n], key
    # the names bench/phases.json places: forwards and the context run as
    # lambdas, the VJPs as one / one0, the assembler as asm
    assert {m for k, m in mods.items() if k.startswith(("fwd", "ctx"))} == {"jit__lambda"}
    assert {m for k, m in mods.items() if k.startswith("bwd")} == {"jit_one", "jit_one0"}
    assert {m for k, m in mods.items() if k.startswith("asm")} == {"jit_asm"}


def test_round_is_a_profiler_step_with_sync_spans(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    import repro.obs.trace as trace

    eng, _ = _engine("fedqcs-ea", False)
    eng.run_round()  # compile outside the trace
    monkeypatch.setattr(trace, "ANNOTATE", True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            eng.run_round()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    steps, spans = [], {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.ROUND_STEP:
                    steps.append(int(dict(ev.stats)["step_num"]))
                elif ev.name in ("select", "sync", "uplink", "client_pass", "decode", "apply"):
                    spans[ev.name] = spans.get(ev.name, 0) + 1
    assert sorted(steps) == [1, 2]
    # per round: the mask read and the stats read; selection before and
    # after the uplink
    assert spans["sync"] == 4 and spans["select"] == 4
    assert all(spans[p] == 2 for p in ("uplink", "client_pass", "decode", "apply"))
