"""Seeded synthetic recordings, made on the device.

A ``jax.numpy`` copy of the AR(1) smoothing of ``dummy_brain`` (the
repository's dummy-dataset generator): standard-normal noise, smoothed as
``y[t] = 0.8 y[t-1] + 0.2 x[t]``, then standardised per series.  It lives
here, not in the program, so that a later change to the program cannot
change the data the benchmark measures.

Series are made in blocks of ``BLOCK``, each from ``fold_in(key, block)``,
so the whole recording is one jitted call whose memory is the output plus
one block's temporaries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ALPHA = 0.8
BLOCK = 4096


def _block(key, L: int):
    x = jax.random.normal(key, (L, BLOCK), jnp.float32)

    def step(prev, xt):
        y = ALPHA * prev + (1.0 - ALPHA) * xt
        return y, y

    _, ys = jax.lax.scan(step, x[0], x[1:])
    ts = jnp.concatenate([x[:1], ys], axis=0).T  # (BLOCK, L)
    ts = ts - jnp.mean(ts, axis=1, keepdims=True)
    return ts / (jnp.std(ts, axis=1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnames=("N", "L"))
def recording(key, N: int, L: int):
    """(N, L) float32 recording from ``key``."""
    nb = -(-N // BLOCK)
    keys = jax.vmap(lambda b: jax.random.fold_in(key, b))(jnp.arange(nb))
    ts = jax.lax.map(lambda k: _block(k, L), keys)
    return ts.reshape(nb * BLOCK, L)[:N]


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**64 - 1."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    hi, lo = divmod(seed, 2**32)
    return jax.random.fold_in(jax.random.key(lo), hi)


def histogram_counts(hist: dict, N: int) -> dict[int, int]:
    """Split N targets over the E values of ``hist`` ({E: count}) in
    proportion, by largest remainder: the same counts for every seed."""
    Es = sorted(int(e) for e in hist)
    w = np.array([float(hist[str(e)] if str(e) in hist else hist[e])
                  for e in Es])
    exact = w / w.sum() * N
    counts = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - counts), kind="stable")[: N - counts.sum()]:
        counts[i] += 1
    return {e: int(c) for e, c in zip(Es, counts) if c > 0}


def draw_optE(seed: int, hist: dict, N: int) -> np.ndarray:
    """optE of N targets: fixed counts from ``hist``, placed by a seeded
    permutation, so every seed runs the same bucket plan."""
    counts = histogram_counts(hist, N)
    optE = np.repeat(np.array(list(counts), np.int32), list(counts.values()))
    return np.random.default_rng([int(seed), 1]).permutation(optE)
