"""Pure-jnp oracle for the ccm_lookup Pallas kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ccm_lookup_ref(
    idx: jax.Array, w: jax.Array, Y_fut: jax.Array
) -> jax.Array:
    """pred[b, t] = sum_k w[t, k] * Y_fut[b, idx[t, k]].

    idx, w: (Lq, k) — one library kNN table; Y_fut: (B, Lp) — a batch of
    target-series future values sharing that table (same optimal E).
    Returns (B, Lq).
    """
    g = Y_fut[:, idx]  # (B, Lq, k)
    # HIGHEST: on TPU a default-precision f32 einsum runs in bf16 passes.
    return jnp.einsum(
        "tk,btk->bt", w, g, precision=jax.lax.Precision.HIGHEST
    )
