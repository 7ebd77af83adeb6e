"""Records the small CPU trace that ``test_scopes.py`` reads:

    JAX_PLATFORMS=cpu python3 bench/tests/record_scoped_trace.py

Three rounds of a toy round whose programs carry the engine's names and its
layer scopes: the uplink's ``lambda`` (``fedqcs.uplink``), ``_prep_fn``
(``fedqcs.client``), a staged VJP ``one`` (``fedqcs.client/stage``),
``_encode_segment_fn`` (``fedqcs.client/encode``), ``_ps_fn``
(``fedqcs.decode``, with a ``dequantize`` gather and a ``gamp`` scan),
``_scatter_residuals`` and the update's ``lambda`` (``fedqcs.apply``), and
one unscoped eager ``concatenate``: 8 program runs a round.  Each round is
a ``fedqcs_round`` profiler step inside the harness's ``window`` span, and
the host sleeps 20 ms in each of its two ``sync`` spans.  Writes
``bench/tests/data/cpu_round_scoped.xplane.pb``.
"""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "data", "cpu_round_scoped.xplane.pb")
SLEEP_S = 0.02
PROGRAMS_PER_ROUND = 8


def _scoped(fn, *names):
    def inner(*args):
        with jax.named_scope(names[0]):
            if len(names) == 1:
                return fn(*args)
            with jax.named_scope(names[1]):
                return fn(*args)

    inner.__name__ = fn.__name__
    return inner


def one(x):
    return jnp.tanh(x @ x.T) @ x


def _encode_segment_fn(x):
    return jnp.sort(jnp.abs(x), axis=-1)


def _prep_fn(x):
    return x * 2.0


def _ps_fn(codes, x):
    levels = jnp.linspace(-1.0, 1.0, 8)
    with jax.named_scope("dequantize"):
        y = levels[codes] * x
    with jax.named_scope("gamp"):
        _, out = jax.lax.scan(lambda c, r: (c, jnp.sin(r @ x.T) @ x), None,
                              y.reshape(4, -1, y.shape[1]))
    return out.reshape(y.shape)[:256]


def _scatter_residuals(res, rows):
    return res.at[jnp.arange(rows.shape[0])].set(rows)


def main():
    uplink = jax.jit(_scoped(lambda x: jnp.cos(x) @ x, "fedqcs.uplink"))
    prep = jax.jit(_scoped(_prep_fn, "fedqcs.client"))
    stage = jax.jit(_scoped(one, "fedqcs.client", "stage"))
    encode = jax.jit(_scoped(_encode_segment_fn, "fedqcs.client", "encode"))
    decode = jax.jit(_scoped(_ps_fn, "fedqcs.decode"))
    scatter = jax.jit(_scoped(_scatter_residuals, "fedqcs.apply"))
    apply = jax.jit(_scoped(lambda x, g: x - 0.1 * jnp.tanh(g), "fedqcs.apply"))
    x = jnp.ones((256, 256), jnp.float32) / 256
    codes = (jnp.arange(512 * 256, dtype=jnp.int32).reshape(512, 256) * 7) % 8
    res = jnp.zeros((512, 256), jnp.float32)

    def round_(x, res):
        c = uplink(x)
        with jax.profiler.TraceAnnotation("sync"):
            time.sleep(SLEEP_S)
            np.asarray(c)
        p = prep(x)
        g = encode(stage(p))
        g2 = jnp.concatenate([g, p])  # eager glue, no scope
        d = decode(codes, g2)
        res = scatter(res, g2).block_until_ready()
        x = apply(x, d).block_until_ready()
        with jax.profiler.TraceAnnotation("sync"):
            time.sleep(SLEEP_S)
            np.asarray(x)
        return x, res

    x, res = round_(x, res)  # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("window"):
        for t in range(3):
            with jax.profiler.StepTraceAnnotation("fedqcs_round", step_num=t):
                x, res = round_(x, res)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    shutil.copy(path, OUT)
    shutil.rmtree(tmp)
    print(OUT, os.path.getsize(OUT))


if __name__ == "__main__":
    main()
