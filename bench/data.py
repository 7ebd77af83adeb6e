"""The benchmark's traffic generator: per-client synthetic-language batches.

A copy of the program's ``fed.engine.TokenClientData`` and
``data.synthetic.affine_rule_batch`` (the noisy affine next-token rule), kept
here so that a later change to the program cannot change the traffic.  Every
batch is made on the device and is a pure function of (seed, round, client
id), so a client's draw does not depend on who else is in the cohort, and two
runs with one seed send the same tokens.  The parameters come from a traffic
file (``bench/traffic/<name>.json``): ``clients``, ``batch``, ``seq``,
``noise`` and ``dialects``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def affine_rule_batch(key, batch: int, seq: int, vocab: int, noise: float, c):
    """``batch`` sequences of ``seq`` tokens following
    ``(start * 31**(i % 8) + c * i) % vocab``, with a share ``noise`` of
    tokens replaced by uniform draws; labels are the tokens shifted by one."""
    k_start, k_noise, k_rand = jax.random.split(key, 3)
    start = jax.random.randint(k_start, (batch, 1), 0, vocab)
    idx = jnp.arange(seq + 1)
    seqs = (start * jnp.power(31, idx % 8) + c * idx) % vocab
    flip = jax.random.bernoulli(k_noise, noise, seqs.shape)
    rand = jax.random.randint(k_rand, seqs.shape, 0, vocab)
    seqs = jnp.where(flip, rand, seqs).astype(jnp.int32)
    return {"tokens": seqs[:, :-1], "labels": seqs[:, 1:]}


class ClientTokens:
    """Duck type of the program's client data (``counts`` and
    ``cohort_batch(round, ids)``).  Every client mixes ``dialects`` rule
    variants uniformly; each sequence draws its dialect, which shifts the
    rule's additive constant."""

    def __init__(self, seed: int, vocab: int, clients: int, batch: int, seq: int,
                 noise: float = 0.2, dialects: int = 10):
        self.seed = seed
        self.vocab, self.batch, self.seq = vocab, batch, seq
        self.noise, self.dialects = noise, dialects
        self.counts = np.ones(clients, np.int64)
        self._make = jax.jit(self._cohort)

    def _one(self, key):
        k_rule, k_dialect = jax.random.split(key)
        dialect = jax.random.randint(k_dialect, (self.batch, 1), 0, self.dialects)
        return affine_rule_batch(k_rule, self.batch, self.seq, self.vocab,
                                 self.noise, 17 + 5 * dialect)

    def _cohort(self, seed_key, round_idx, ids):
        base = jax.random.fold_in(seed_key, round_idx)
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(ids)
        return jax.vmap(self._one)(keys)

    def cohort_batch(self, round_idx: int, ids) -> dict:
        return self._make(seed_key(self.seed), jnp.int32(round_idx),
                          jnp.asarray(ids, jnp.int32))


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**64 (run seeds may exceed 32
    signed bits): the low and high 32 bits are folded in one after the
    other."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
