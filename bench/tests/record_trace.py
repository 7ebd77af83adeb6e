"""Records the small CPU trace that ``test_trace.py`` reads:

    JAX_PLATFORMS=cpu python3 bench/tests/record_trace.py

Three rounds of a toy round whose programs carry the names of the engine's
(a staged VJP ``one``, ``_encode_segment_fn``, ``_ps_fn``, the update's
``lambda`` and ``_prep_fn``) under the harness's and the engine's spans, each
program waited for; in every round the host sleeps 50 ms inside ``uplink``
with the device idle.  Writes ``bench/tests/data/cpu_round.xplane.pb``.
"""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "data", "cpu_round.xplane.pb")
SLEEP_S = 0.05


def one(x):
    return jnp.tanh(x @ x.T) @ x


def _encode_segment_fn(x):
    return jnp.sort(jnp.abs(x), axis=-1)


def _ps_fn(x):
    for _ in range(4):
        x = jnp.sin(x @ x.T) @ x
    return x


def _prep_fn(x):
    return x * 2.0


def main():
    progs = [jax.jit(f) for f in (one, _encode_segment_fn, _ps_fn, _prep_fn)]
    one_j, enc_j, ps_j, prep_j = progs
    apply_j = jax.jit(lambda x, g: x - 0.1 * g)
    x = jnp.ones((256, 256), jnp.float32) / 256
    for f in (one_j, enc_j, ps_j, prep_j):
        f(x).block_until_ready()
    apply_j(x, x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    ann = jax.profiler.TraceAnnotation
    with ann("window"):
        for _ in range(3):
            with ann("round"):
                with ann("uplink"):
                    time.sleep(SLEEP_S)
                prep_j(x).block_until_ready()
                with ann("client_pass"):
                    g = one_j(x).block_until_ready()
                    enc_j(g).block_until_ready()
                with ann("decode"):
                    g = ps_j(g).block_until_ready()
                with ann("apply"):
                    x = apply_j(x, g).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    shutil.copy(path, OUT)
    shutil.rmtree(tmp)
    print(OUT, os.path.getsize(OUT))


if __name__ == "__main__":
    main()
