"""Training batches made from a seed.

A copy of the Markov-ish token generator the program uses for its synthetic
data (``data/synthetic.token_batch``), kept here so that no change to the
program can change what the benchmark feeds it. Every step of a run gets its
own rows: batch ``i`` of seed ``s`` is drawn from ``fold_in(key(s), i)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SEED_BITS = 31


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one wider than 32 bits."""
    hi, lo = divmod(int(seed), 1 << SEED_BITS)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def program_seed(seed: int) -> int:
    """The 31-bit seed handed to the program for its weights."""
    hi, lo = divmod(int(seed), 1 << SEED_BITS)
    return (lo ^ (hi * 0x9E3779B1)) & ((1 << SEED_BITS) - 1)


def token_batch(key, batch: int, seq: int, vocab: int) -> dict:
    """Next token = (31·t[-2] + 17·t[-1] + 7) mod vocab with probability 3/4,
    else uniform; ``labels`` are ``tokens`` shifted by one."""
    k1, k2 = jax.random.split(key)
    x = jax.random.randint(k1, (batch, seq + 1), 0, vocab)
    det = (31 * x[:, :-2] + 17 * x[:, 1:-1] + 7) % vocab
    coin = jax.random.bernoulli(k2, 0.75, det.shape)
    toks = x.at[:, 2:].set(jnp.where(coin, det, x[:, 2:]))
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def batches(seed: int, n: int, batch: int, seq: int, vocab: int) -> list[dict]:
    """``n`` batches of ``batch × seq`` tokens, every row its own."""
    key = seed_key(seed)
    make = jax.jit(lambda i: token_batch(jax.random.fold_in(key, i), batch, seq, vocab))
    return [make(i) for i in range(n)]
