"""Seeded training batches: a copy of the program's synthetic LM stream.

A fixed random Markov chain over the vocabulary (order 1, 64 states, each
preferring 4 next tokens) with a copy channel that repeats the token 8
positions back with probability 0.3, generated counter-based from
(seed, step). The same seed gives the same batches; every step's rows
differ. Seeds of any size are taken (see ``weights.seed_key``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import fold, seed_key


def chain_table(seed: int, vocab: int, n_states: int = 64) -> np.ndarray:
    """Each state's 4 preferred next tokens."""
    rng = np.random.default_rng(int(seed))
    return rng.integers(0, vocab, size=(n_states, 4)).astype(np.int32)


def make_batch_fn(seed: int, vocab: int, seq_len: int, batch: int,
                  copy_offset: int = 8, copy_prob: float = 0.3):
    """``fn(step) -> {"tokens": (batch, seq_len + 1) int32}``, one
    program (``batch_program``) for every seed and step."""
    prog = jax.jit(batch_program(vocab, seq_len, batch, copy_offset,
                                 copy_prob))
    table = jnp.asarray(chain_table(seed, vocab))
    base = seed_key(seed)
    return lambda step: prog(base, table, step)


def batch_program(vocab: int, seq_len: int, batch: int,
                  copy_offset: int = 8, copy_prob: float = 0.3):
    """``fn(key, table, step)``: the batch of ``step`` from the seed's key
    and chain table."""

    def fn(base, table, step):
        n_states = table.shape[0]

        def sample_row(key):
            def body(carry, k):
                state, hist = carry
                k1, k2 = jax.random.split(k)
                choice = table[state % n_states,
                               jax.random.randint(k1, (), 0, 4)]
                tok = jnp.where(jax.random.uniform(k2) < copy_prob, hist[0],
                                choice) % vocab
                hist = jnp.concatenate([hist[1:], tok[None]])
                return (tok % n_states, hist), tok

            k0, k1, k2 = jax.random.split(key, 3)
            hist0 = jax.random.randint(k0, (copy_offset,), 0, vocab)
            state0 = jax.random.randint(k1, (), 0, n_states)
            _, toks = jax.lax.scan(body, (state0, hist0),
                                   jax.random.split(k2, seq_len + 1))
            return toks

        keys = jax.random.split(fold(base, "batch", step), batch)
        return {"tokens": jax.vmap(sample_row)(keys).astype(jnp.int32)}

    return fn
