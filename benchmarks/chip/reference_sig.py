"""Plain significance statistics, the yardstick for the significance cell.

Written from the method (DESIGN.md SS9 of the program, Theiler et al.
1992 for the surrogates, Sugihara et al. 2012 for convergence) in
straightforward ``jax.numpy``; it imports nothing of the program, only
``reference.py``'s plain CCM.  For library series x and target series y
(optE E of y), with one run seed:

- keys: ``PRNGKey(seed)`` split in two, ``perm_key`` and ``surr_key``;
- surrogates of y: ``fold_in(surr_key, id of y)`` split into m keys, each
  drawing nf = L // 2 + 1 uniform phases in [0, 2 pi); the rfft of y keeps
  its magnitudes and takes those phases, except the DC bin and, for even
  L, the Nyquist bin, which keep their own values; the irfft (length L) is
  the surrogate;
- the null: rho of each surrogate's futures cross-mapped from x on the
  whole library (``reference.rho_rows``); the p-value is
  (1 + #{null >= rho}) / (m + 1), rho the observed cross map;
- convergence: the libraries are the nested prefixes
  ``perm[:size]`` of ``permutation(perm_key, Lp)``: each point's E + 1
  nearest neighbours among them (self excluded, ties to the earlier place
  in the permutation), the same weights and Pearson as ``reference.py``;
  drho = max - min of the curve over the sizes, trend = the mean over
  pairs of sizes s < t of sign(rho_t - rho_s).

Products run at ``highest`` matrix precision: the TPU computes an FFT of a
length that is not a power of two as products on its matrix unit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference


def keys(seed: int):
    """(perm_key, surr_key) of a run seed."""
    return jax.random.split(jax.random.PRNGKey(seed))


def permutation(seed: int, Lp: int) -> jax.Array:
    return jax.random.permutation(keys(seed)[0], Lp)


@functools.partial(jax.jit, static_argnames=("m",))
def _surrogates(surr_key, y, sid, m: int):
    """(L,) series -> (m, L) phase-randomised surrogates."""
    L = y.shape[0]
    X = jnp.fft.rfft(y)
    nf = X.shape[0]
    ks = jax.random.split(jax.random.fold_in(surr_key, sid), m)
    phases = jnp.stack([jax.random.uniform(k, (nf,), minval=0.0,
                                           maxval=2.0 * jnp.pi) for k in ks])
    keep = np.zeros(nf, bool)
    keep[0] = True
    if L % 2 == 0:
        keep[-1] = True
    Xs = jnp.where(jnp.asarray(keep)[None, :], X[None, :],
                   jnp.abs(X)[None, :] * jnp.exp(1j * phases))
    return jnp.fft.irfft(Xs, n=L).astype(jnp.float32)


def surrogates(seed: int, ys, ids, m: int) -> jax.Array:
    """(T, L) target series with their ids -> (T, m, L) surrogates."""
    surr_key = keys(seed)[1]
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_surrogates(surr_key, ys[i], int(ids[i]), m)
                          for i in range(ys.shape[0])])


def pvalues(rho, null):
    """p (R, T) of observed rho (R, T) against null (R, T, m)."""
    m = null.shape[-1]
    return (1.0 + np.sum(null >= rho[..., None], axis=-1)) / (m + 1.0)


@functools.partial(jax.jit, static_argnames=("E_max", "tau", "Tp", "E",
                                             "size", "exclude_self"))
def _prefix_table(x, perm, *, E_max, tau, Tp, E, size, exclude_self):
    """Neighbour indices and weights ((Lp, E+1) each) of x at dimension E
    among the library points perm[:size]."""
    L = x.shape[0]
    Lp = L - (E_max - 1) * tau - Tp
    t = jnp.arange(Lp)
    lib = perm[:size]
    d = jnp.zeros((Lp, size), jnp.float32)
    for e in range(1, E + 1):
        lag = x[(E_max - 1) * tau + t - (e - 1) * tau]
        d = d + jnp.square(lag[:, None] - lag[lib][None, :])
    if exclude_self:
        d = jnp.where(lib[None, :] == t[:, None], jnp.inf, d)
    neg, pos = jax.lax.top_k(-d, E + 1)
    dist = jnp.sqrt(jnp.maximum(-neg, 0.0))
    d1 = dist[:, :1]
    w = jnp.where(d1 > 0, jnp.exp(-dist / jnp.where(d1 > 0, d1, 1.0)),
                  (dist <= 0).astype(jnp.float32))
    w = jnp.where(jnp.isfinite(w), w, 0.0)
    return lib[pos], w / jnp.sum(w, axis=1, keepdims=True)


def curves(lib_rows, target_fut, target_E, perm, lib_sizes, *, E_max: int,
           tau: int, Tp: int, exclude_self: bool) -> np.ndarray:
    """rho (S, R, T) of every library row against every target at each
    library size."""
    target_E = np.asarray(target_E)
    out = np.zeros((len(lib_sizes), len(lib_rows), len(target_E)), np.float32)
    for r, x in enumerate(lib_rows):
        x = jnp.asarray(x)
        for s, size in enumerate(lib_sizes):
            for e in np.unique(target_E):
                cols = np.flatnonzero(target_E == e)
                idx, w = _prefix_table(x, perm, E_max=E_max, tau=tau, Tp=Tp,
                                       E=int(e), size=int(size),
                                       exclude_self=exclude_self)
                rho = reference._rho(idx, w, target_fut[jnp.asarray(cols)])
                out[s, r, cols] = np.asarray(rho)
    return out


def convergence(c) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(drho, trend, the smallest |rho_t - rho_s| over pairs of sizes) of
    curves (S, ...)."""
    S = c.shape[0]
    i, j = np.triu_indices(S, 1)
    diff = c[j] - c[i]
    return (c.max(axis=0) - c.min(axis=0), np.sign(diff).mean(axis=0),
            np.abs(diff).min(axis=0))
