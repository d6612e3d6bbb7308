"""Pallas TPU kernel: batched CCM lookup (paper Alg. 5).

The paper identifies lookup as the next bottleneck at large N (SSIV-B3,
Fig. 8a): it is a random-gather, memory-bandwidth-bound kernel.  TPU
adaptation (DESIGN.md SS2): batch *many target series* that share one
library table (same optimal E) through a single pass, so each (Lq, k)
index block is read once and reused across block_b targets — raising
arithmetic intensity by block_b versus the paper's one-target-at-a-time
CPU kernel.

Layout: targets ride the LANE axis.  The wrapper hands the kernel the
futures transposed, (Lp, B), so neighbour ``idx[t, j]`` is one ROW of
the VMEM block — a dynamic sublane read of all block_b targets at once —
and the table itself sits in SMEM (scalar reads drive the row offsets).
A gather along the lane axis, the natural form for (B, Lp) futures,
does not lower in Mosaic.

Grid: (target blocks, time blocks), time minor, so the (Lp, block_b)
futures block stays resident while the table streams through.  Per
program VMEM: futures (Lp, block_b) + out (block_t, block_b) — about
4.4 MB for block_b=128 at Lp=8,528; SMEM: idx + w blocks
(block_t * 32 words) each.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SMEM_ROW = 32  # kp * block_t rows stays a multiple of 1,024 SMEM words


def ccm_lookup_kernel(idx_ref, w_ref, y_ref, out_ref, *, k: int, kp: int):
    """out[t, :] = sum_j w[t, j] * y[idx[t, j], :], summed in j order.

    idx_ref/w_ref: SMEM (block_t * kp,) — the table rows of this time
    block, flattened with rows padded to kp >= k entries; y_ref: VMEM
    (Lp, block_b); out_ref: (block_t, block_b)."""
    block_t, block_b = out_ref.shape

    def row(t, carry):
        acc = jnp.zeros((1, block_b), jnp.float32)
        for j in range(k):  # static unroll: k <= 21
            r = idx_ref[t * kp + j]
            acc = acc + w_ref[t * kp + j] * y_ref[pl.ds(r, 1), :]
        out_ref[pl.ds(t, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, block_t, row, 0)


def ccm_lookup_pallas(
    idx: jax.Array,
    w: jax.Array,
    Y_fut: jax.Array,
    *,
    interpret: bool,
    block_b: int = 128,
    block_t: int = 256,
) -> jax.Array:
    """pred (B, Lq) for futures Y_fut (B, Lp) through one table idx/w
    (Lq, k) — or (R, B, Lq) through R tables idx/w (R, Lq, k) sharing the
    futures, as one grid with a table axis (the batched form vmap over
    library rows maps onto, see ops.ccm_lookup).  block_b is made
    lane-legal: one block of all B targets when B <= block_b, else a
    multiple of 128.  ``interpret``: True runs the Pallas interpreter,
    False compiles for TPU."""
    single = idx.ndim == 2
    if single:
        idx, w = idx[None], w[None]
    R, Lq, k = idx.shape
    B, Lp = Y_fut.shape
    # SMEM blocks of a 1-D array must be multiples of 1,024 words (or the
    # whole array): table rows are padded to kp = 32 >= k entries and the
    # time block to a multiple of 32 rows whenever it does not cover the
    # flattened tables.
    kp = max(_SMEM_ROW, pl.cdiv(k, _SMEM_ROW) * _SMEM_ROW)
    if R == 1 and Lq <= block_t:
        block_t = pl.cdiv(Lq, 8) * 8
    else:
        block_t = pl.cdiv(min(block_t, Lq), _SMEM_ROW) * _SMEM_ROW
    block_b = B if B <= block_b else pl.cdiv(block_b, _LANES) * _LANES
    Lq_pad = pl.cdiv(Lq, block_t) * block_t
    n_t = Lq_pad // block_t
    B_pad = pl.cdiv(B, block_b) * block_b
    pad = ((0, 0), (0, Lq_pad - Lq), (0, kp - k))
    idx_p = jnp.pad(idx.astype(jnp.int32), pad).reshape(-1)
    w_p = jnp.pad(w.astype(jnp.float32), pad).reshape(-1)
    Y_t = jnp.pad(Y_fut, ((0, B_pad - B), (0, 0))).T  # (Lp, B_pad)

    table = pl.BlockSpec(
        (block_t * kp,), lambda b, r, t: (r * n_t + t,),
        memory_space=pltpu.SMEM,
    )
    # Grid (target blocks, tables, time blocks): the (Lp, block_b)
    # futures block is fetched once per target block and stays resident
    # while every table streams through it.
    out = pl.pallas_call(
        functools.partial(ccm_lookup_kernel, k=k, kp=kp),
        grid=(B_pad // block_b, R, n_t),
        in_specs=[
            table,
            table,
            pl.BlockSpec((Lp, block_b), lambda b, r, t: (0, b)),
        ],
        out_specs=pl.BlockSpec(
            (None, block_t, block_b), lambda b, r, t: (r, t, b)
        ),
        out_shape=jax.ShapeDtypeStruct((R, Lq_pad, B_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
        name="ccm_lookup",
    )(idx_p, w_p, Y_t)
    out = out[:, :Lq, :B].transpose(0, 2, 1)
    return out[0] if single else out
