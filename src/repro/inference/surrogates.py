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

The phase surrogates' inverse transform is one real DFT product at
``highest`` precision, computed only at the samples the caller keeps
(:func:`_phase_samples` says why it is not ``jnp.fft.irfft``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import embedding


def random_shuffle(key: jax.Array, x: jax.Array, n: int) -> jax.Array:
    """(L,) -> (n, L) independent random permutations of x."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: jax.random.permutation(k, x))(keys)


def _phase_spectra(key: jax.Array, x: jax.Array, n: int) -> jax.Array:
    """(L,) -> (n, 2 * nf) phase-randomised half spectra, nf = L // 2 + 1,
    as the real operand ``[Re | Im]`` of the inverse transform.

    Magnitudes are kept, phases of the strictly-positive-frequency bins
    are replaced by i.i.d. uniform draws.  The DC bin, and for even L the
    Nyquist bin, must stay real for the inverse transform to be a real
    series, so they keep their ORIGINAL real part (a random sign flip
    would change the mean / alternating component); their imaginary part,
    which a real inverse transform ignores, is 0.
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
    mag = jnp.abs(X)[None, :]
    re = jnp.where(keep[None, :], X.real[None, :], mag * jnp.cos(phases))
    im = jnp.where(keep[None, :], 0.0, mag * jnp.sin(phases))
    return jnp.concatenate([re, im], axis=-1)


@functools.lru_cache(maxsize=None)
def _inverse_rdft_basis(L: int, start: int, count: int) -> np.ndarray:
    """(2 * nf, count) float32 W with ``[Re | Im] @ W`` = samples
    ``start .. start + count - 1`` of the length-L inverse real DFT.

    Rows are ``w_k cos(2 pi k n / L) / L`` and ``-w_k sin(2 pi k n / L) / L``,
    w_k = 1 for DC and the Nyquist bin of even L, 2 otherwise.  Built in
    float64 with the exact integer reduction (k n) mod L before the
    cosine, then rounded once to float32.
    """
    nf = L // 2 + 1
    k = np.arange(nf, dtype=np.int64)[:, None]
    angle = (2.0 * np.pi / L) * ((k * np.arange(start, start + count)) % L)
    w = np.full((nf, 1), 2.0)
    w[0] = 1.0
    if L % 2 == 0:
        w[-1] = 1.0
    W = np.concatenate([w * np.cos(angle), -w * np.sin(angle)]) / L
    return W.astype(np.float32)


def _phase_samples(key: jax.Array, x: jax.Array, n: int, start: int,
                   count: int) -> jax.Array:
    """Samples ``start .. start + count - 1`` of n phase surrogates of x.

    The inverse transform is one real product of :func:`_phase_spectra`
    and the basis at ``highest`` precision, not ``jnp.fft.irfft``: on the
    TPU the compiler lowers an irfft of these lengths (1,450, 8,528) to a
    complex DFT of the padded full spectrum, three dense (L x L)
    products, about three times the work of this one.  The basis is a
    constant of the compiled program, so it reaches the device once with
    the program, not once per call.  Batched over targets, it is one
    product whose rows each meet the same basis: on the TPU a target's
    values do not depend on the targets beside it.  XLA:CPU's matmul
    changes kernel with the row count (at about 64 rows), so there a
    target's values can move in the last bit between a batch below and
    one above that size.
    """
    W = jnp.asarray(_inverse_rdft_basis(x.shape[-1], start, count))
    return jnp.einsum("mk,kl->ml", _phase_spectra(key, x, n), W,
                      precision=jax.lax.Precision.HIGHEST)


def _shuffle_samples(key: jax.Array, x: jax.Array, n: int, start: int,
                     count: int) -> jax.Array:
    """Samples ``start .. start + count - 1`` of n shuffle surrogates of x."""
    return random_shuffle(key, x, n)[:, start:start + count]


def phase_randomized(key: jax.Array, x: jax.Array, n: int) -> jax.Array:
    """(L,) -> (n, L) FFT phase-randomized surrogates of x.

    Every surrogate keeps the rfft magnitudes of x (the power spectrum
    is preserved up to the float32 round trip) with i.i.d. uniform
    phases on the strictly-positive-frequency bins; DC and, for even L,
    Nyquist keep their original value (:func:`_phase_spectra`).  The
    inverse transform is one real DFT product (:func:`_phase_samples`).
    """
    L = x.shape[-1]
    return _phase_samples(key, x, n, 0, L).astype(x.dtype)


_GENERATORS = {"shuffle": _shuffle_samples, "phase": _phase_samples}

#: The module :func:`surrogate_futures` compiles to, and the name the
#: device trace's ``XLA Modules`` line gives each of its runs.  On the TPU
#: its rfft and inverse-DFT product lower to matrix-unit convolutions,
#: whose operations carry no name of their own, so the module is what
#: names surrogate generation.
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
    Lp = cfg.n_points(ts_rows.shape[-1])
    start = embedding.future_start(cfg.E_max, cfg.tau, cfg.Tp)
    fut = jax.vmap(
        lambda x, sid: gen(jax.random.fold_in(key, sid), x, n, start, Lp)
    )(ts_rows, series_ids)  # (t, n, Lp)
    return fut.reshape(-1, Lp)
