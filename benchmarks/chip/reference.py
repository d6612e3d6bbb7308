"""Plain convergent cross mapping, the yardstick for ``correct``.

Written from the method (Sugihara et al. 2012; the mpEDM paper,
arXiv:2011.11082, Alg. 2) in straightforward ``jax.numpy``; it imports
nothing of the program.  For a library series x and a target series y:

- embed x with lags 0..E-1 (lag ``tau``), every dimension aligned on the
  present time ``p(t) = t + (E_max - 1) tau``, t in [0, Lp);
- for every point, its E + 1 nearest other points by squared Euclidean
  distance summed over the E lags (self excluded; ties to the lower
  index);
- weights ``exp(-d_j / d_1)`` on the distances, normalised; when
  ``d_1`` is 0 the neighbours at distance 0 share the weight equally;
- the cross-mapped prediction of ``y[p(t) + Tp]`` is the weighted sum of
  the neighbours' values of ``y[. + Tp]``;
- rho is the Pearson correlation of prediction and truth, 0 when either
  side is constant or not finite.

Only elementwise float32 arithmetic is used (no matrix unit), so the
precision is float32 on every backend.  Rows are done one at a time and
targets in blocks, so that the check fits beside nothing else on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TARGET_BLOCK = 2048


def futures(ts, E_max: int, tau: int, Tp: int):
    """y[p(t) + Tp] for t in [0, Lp): (N, L) -> (N, Lp)."""
    L = ts.shape[-1]
    off = (E_max - 1) * tau + Tp
    return ts[..., off: off + L - (E_max - 1) * tau - Tp]


@functools.partial(jax.jit, static_argnames=("E_max", "tau", "Tp", "Es",
                                             "exclude_self"))
def _tables(x, *, E_max, tau, Tp, Es, exclude_self):
    """Neighbour indices and weights of library series x for each E in
    Es: a tuple of ((Lp, E+1) int32, (Lp, E+1) float32)."""
    L = x.shape[0]
    Lp = L - (E_max - 1) * tau - Tp
    t = jnp.arange(Lp)
    d = jnp.zeros((Lp, Lp), jnp.float32)
    out = []
    for E in range(1, max(Es) + 1):
        lag = x[(E_max - 1) * tau + t - (E - 1) * tau]
        d = d + jnp.square(lag[:, None] - lag[None, :])
        if E not in Es:
            continue
        dm = jnp.where(jnp.eye(Lp, dtype=bool), jnp.inf, d) if exclude_self else d
        neg, idx = jax.lax.top_k(-dm, E + 1)
        dist = jnp.sqrt(jnp.maximum(-neg, 0.0))
        d1 = dist[:, :1]
        w = jnp.where(d1 > 0, jnp.exp(-dist / jnp.where(d1 > 0, d1, 1.0)),
                      (dist <= 0).astype(jnp.float32))
        w = jnp.where(jnp.isfinite(w), w, 0.0)
        out.append((idx, w / jnp.sum(w, axis=1, keepdims=True)))
    return tuple(out)


@jax.jit
def _rho(idx, w, fut):
    """rho of target futures fut (T, Lp) through one table (Lp, k)."""
    pred = jnp.zeros_like(fut)
    for j in range(idx.shape[1]):
        pred = pred + w[None, :, j] * jnp.take(fut, idx[:, j], axis=1)
    a = fut - jnp.mean(fut, axis=1, keepdims=True)
    b = pred - jnp.mean(pred, axis=1, keepdims=True)
    num = jnp.sum(a * b, axis=1)
    den = jnp.sqrt(jnp.sum(a * a, axis=1)) * jnp.sqrt(jnp.sum(b * b, axis=1))
    good = (den > 1e-8) & jnp.isfinite(den) & jnp.isfinite(num)
    return jnp.where(good, num / jnp.where(good, den, 1.0), 0.0)


def rho_rows(lib_rows, target_fut, target_E, *, E_max: int, tau: int,
             Tp: int, exclude_self: bool) -> np.ndarray:
    """rho (rows, targets) of every library row against every target.

    lib_rows: (R, L) library series; target_fut: (T, Lp) the targets'
    futures (see :func:`futures`), on the device; target_E: (T,) each
    target's embedding dimension."""
    target_E = np.asarray(target_E)
    Es = tuple(int(e) for e in np.unique(target_E))
    groups = [np.flatnonzero(target_E == e) for e in Es]
    out = np.zeros((len(lib_rows), len(target_E)), np.float32)
    for r, x in enumerate(lib_rows):
        tables = _tables(jnp.asarray(x), E_max=E_max, tau=tau, Tp=Tp, Es=Es,
                         exclude_self=exclude_self)
        for (idx, w), cols in zip(tables, groups):
            for b0 in range(0, len(cols), TARGET_BLOCK):
                blk = cols[b0: b0 + TARGET_BLOCK]
                pad = np.resize(blk, TARGET_BLOCK)  # one shape per table
                rho = _rho(idx, w, target_fut[jnp.asarray(pad)])
                out[r, blk] = np.asarray(rho)[: len(blk)]
    return out
