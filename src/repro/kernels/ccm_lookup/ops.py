"""jit'd public wrapper for the ccm_lookup kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ccm_lookup.ccm_lookup import ccm_lookup_pallas


@functools.lru_cache(maxsize=None)
def _tables_lookup(block_b: int, block_t: int, interpret: bool):
    """(R, Lq, k) tables x (B, Lp) futures -> (R, B, Lq), with a batching
    rule of its own.

    Pallas' generic batching rule would add a vmapped axis to every
    operand, and the SMEM table blocks of a 2-D array break the TPU
    tiling rule.  So vmap over tables (the library rows of a phase-2
    chunk) folds into the kernel's own table axis, and vmap over the
    futures maps the batch through the kernel one slice at a time."""

    @jax.custom_batching.custom_vmap
    def lookup(idx, w, Y):
        return ccm_lookup_pallas(
            idx, w, Y, block_b=block_b, block_t=block_t, interpret=interpret
        )

    @lookup.def_vmap
    def _batched(axis_size, in_batched, idx, w, Y):
        idx_b, w_b, y_b = in_batched
        if not idx_b:
            idx = jnp.broadcast_to(idx, (axis_size,) + idx.shape)
        if not w_b:
            w = jnp.broadcast_to(w, (axis_size,) + w.shape)
        if y_b:
            return jax.lax.map(lambda a: lookup(*a), (idx, w, Y)), True
        lead = idx.shape[:2]
        out = lookup(
            idx.reshape((-1,) + idx.shape[2:]),
            w.reshape((-1,) + w.shape[2:]),
            Y,
        )
        return out.reshape(lead + out.shape[1:]), True

    return lookup


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_t", "interpret")
)
def ccm_lookup(
    idx: jax.Array,
    w: jax.Array,
    Y_fut: jax.Array,
    block_b: int = 1024,
    block_t: int = 256,
    *,
    interpret: bool,
) -> jax.Array:
    """Batched simplex lookup: pred[b, t] = sum_k w[t,k] * Y_fut[b, idx[t,k]].

    idx/w: (Lq, k) one library table; Y_fut: (B, Lp) targets sharing it.
    Under vmap over tables the kernel runs once with a table axis.
    interpret: True runs the Pallas interpreter, False compiles for TPU.
    """
    lookup = _tables_lookup(block_b, block_t, interpret)
    return lookup(idx[None], w[None], Y_fut)[0]
