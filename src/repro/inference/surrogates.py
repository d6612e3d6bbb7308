"""Surrogate null models, batched along a surrogate axis (DESIGN.md SS9).

Two generators, both (key, (L,) series, n) -> (n, L) surrogates:

  * random_shuffle  — i.i.d. permutations: preserves the amplitude
    distribution only.  The strictest null (destroys ALL temporal
    structure), appropriate when any dynamics at all should count as
    signal.
  * phase_randomized — FFT phase randomization: preserves the power
    spectrum (hence the full linear autocorrelation) while destroying
    nonlinear/state-dependent structure.  The standard CCM null: a
    linear-stochastic twin of the target that no manifold can
    cross-map, so surviving skill evidences nonlinear coupling.

`surrogate_futures` is the batched entry the significance pipeline
consumes: per-target keys are derived by fold_in on the GLOBAL series
id, so the null draw for a pair is independent of chunk/tile geometry
and reproducible from the single run seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import embedding


def random_shuffle(key: jax.Array, x: jax.Array, n: int) -> jax.Array:
    """(L,) -> (n, L) independent random permutations of x."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: jax.random.permutation(k, x))(keys)


def phase_randomized(key: jax.Array, x: jax.Array, n: int) -> jax.Array:
    """(L,) -> (n, L) FFT phase-randomized surrogates of x.

    Every surrogate has BIT-the-same rfft magnitudes as x (the power
    spectrum is preserved exactly up to the irfft round trip): magnitudes
    are kept, phases of the strictly-positive-frequency bins are
    replaced by i.i.d. uniform draws.  The DC bin — and, for even L, the
    Nyquist bin — must stay real for the inverse transform to be a real
    series, so those bins keep their ORIGINAL complex value (a random
    sign flip would change the mean / alternating component).
    """
    L = x.shape[-1]
    X = jnp.fft.rfft(x)
    nf = X.shape[-1]
    keep = jnp.zeros((nf,), bool).at[0].set(True)
    if L % 2 == 0:
        keep = keep.at[nf - 1].set(True)
    keys = jax.random.split(key, n)
    phases = jax.vmap(
        lambda k: jax.random.uniform(
            k, (nf,), minval=0.0, maxval=2.0 * jnp.pi
        )
    )(keys)
    Xs = jnp.where(
        keep[None, :],
        X[None, :],
        jnp.abs(X)[None, :] * jnp.exp(1j * phases),
    )
    return jnp.fft.irfft(Xs, n=L).astype(x.dtype)


_GENERATORS = {"shuffle": random_shuffle, "phase": phase_randomized}

#: The module :func:`surrogate_futures` compiles to, and the name the
#: device trace's ``XLA Modules`` line gives each of its runs.  On the TPU
#: its FFT lowers to matrix-unit convolutions, whose operations carry no
#: name of their own, so the module is what names surrogate generation.
TRACE_NAME = "jit_surrogate_futures"


@functools.partial(jax.jit, static_argnames=("n", "kind", "cfg"))
def surrogate_futures(
    key: jax.Array,
    ts_rows: jax.Array,
    series_ids: jax.Array,
    n: int,
    kind: str,
    cfg,
) -> jax.Array:
    """Null-model target futures for a tile of series.

    ts_rows: (t, L) raw target series; series_ids: (t,) GLOBAL series
    ids (the fold_in salt).  Returns (t * n, Lp) future-value rows —
    target 0's n surrogates first, then target 1's, ... — i.e. exactly
    the layout of a bucket-sorted column tile whose every segment count
    is scaled by n, so the batch streams through the same
    ccm_lookup path as the real targets (DESIGN.md SS9).
    """
    gen = _GENERATORS[kind]
    L = ts_rows.shape[-1]
    Lp = cfg.n_points(L)

    def per_series(x, sid):
        surr = gen(jax.random.fold_in(key, sid), x, n)  # (n, L)
        return jax.vmap(
            lambda s: embedding.future_values(s, cfg.E_max, cfg.tau, cfg.Tp, Lp)
        )(surr)

    fut = jax.vmap(per_series)(ts_rows, series_ids)  # (t, n, Lp)
    return fut.reshape(-1, Lp)
