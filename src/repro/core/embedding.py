"""Delay embedding (Takens) utilities.

All embeddings are *aligned on present time*: for a length-L series and a
maximum embedding dimension E_max, point index ``t`` refers to present time
``p(t) = t + (E_max - 1) * tau`` regardless of the actual embedding dimension
E <= E_max in use.  Dimension-E coordinates of point t are

    ( x[p(t)], x[p(t) - tau], ..., x[p(t) - (E-1) tau] )

This costs (E_max - E)*tau unusable points at the series head (negligible:
19 steps for E_max=20, tau=1 vs L >= 1450) and buys two things:

  * every E shares one point indexing -> kNN tables for all E stack into a
    single dense [E_max, Lp, k_max] array, and
  * the squared distance obeys the prefix recurrence
        D_E = D_{E-1} + outer_sq_diff(lag_{E-1})
    so all E_max tables cost O(L^2 E_max) instead of O(L^2 E_max^2)
    (beyond-paper optimization; DESIGN.md SS2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lag_matrix(x: jax.Array, E_max: int, tau: int, Lp: int) -> jax.Array:
    """Return V[k, t] = x[p(t) - k*tau] for k in [0, E_max), t in [0, Lp).

    V[:E] are exactly the dimension-E coordinates of every point.
    """
    offset = (E_max - 1) * tau
    idx = offset + jnp.arange(Lp)[None, :] - tau * jnp.arange(E_max)[:, None]
    return x[idx]


def delay_embed(x: jax.Array, E: int, tau: int, Tp: int = 0) -> jax.Array:
    """Classic standalone delay embedding: rows are points, columns lags.

    Point t has coordinates (x[t+(E-1)tau], ..., x[t]) — i.e. present time
    t + (E-1)tau.  Used by the oracle tests; the pipeline uses lag_matrix.
    """
    Lp = x.shape[0] - (E - 1) * tau - Tp
    idx = (E - 1) * tau + jnp.arange(Lp)[:, None] - tau * jnp.arange(E)[None, :]
    return x[idx]


def future_start(E_max: int, tau: int, Tp: int) -> int:
    """Series index of point 0's future, p(0) + Tp."""
    return (E_max - 1) * tau + Tp


def future_values(x: jax.Array, E_max: int, tau: int, Tp: int, Lp: int) -> jax.Array:
    """fut[t] = x[p(t) + Tp]: the value a simplex forecast of point t targets."""
    return x[future_start(E_max, tau, Tp) + jnp.arange(Lp)]
