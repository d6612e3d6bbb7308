"""Pallas TPU kernel: batched CCM lookup (paper Alg. 5).

The paper identifies lookup as the next bottleneck at large N (SSIV-B3,
Fig. 8a): it is a random-gather, memory-bandwidth-bound kernel.  TPU
adaptation (DESIGN.md SS2): batch *many target series* that share one
library table (same optimal E) through a single pass, so each (Lq, k)
index block is read once and reused across block_b targets — raising
arithmetic intensity by block_b versus the paper's one-target-at-a-time
CPU kernel.

Layout: targets ride the LANE and SUBLANE axes.  The wrapper hands the
kernel the futures transposed and cut into dense tiles, (Lp, B/(S*128),
S, 128): one grid step holds an (Lp, S, 128) futures block, and
neighbour ``idx[t, j]`` is an address offset on its untiled leading
axis, so each neighbour step loads, scales and adds one whole (S, 128)
tile of S*128 targets — a full vreg at S = 8.  The table sits in SMEM
(scalar reads drive the offsets).  A gather along the lane axis, the
natural form for (B, Lp) futures, does not lower in Mosaic.

S (:func:`lookup_tile`) is the largest of 8, 4, 2, 1 that neither pads
a small batch past its own 128-lane rows nor overflows the VMEM budget:
a neighbour step costs the same instructions at any S, so fewer, fuller
target blocks win.  Per program VMEM, double-buffered: futures
(Lp, S, 128) + out (block_t, S, 128) — 5.9 MB + 1 MB a buffer at
Lp 1,430, S 8; 34.8 MB + 1 MB at Lp 8,508, past the 16 MiB default
scoped limit, so the kernel passes its own ``vmem_limit_bytes`` (a
budget under the v5e's 128 MiB).  SMEM: idx + w blocks (block_t * 32
words) each.

Grid: (target blocks, tables, time blocks), time minor, so the futures
block stays resident while every table streams through it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = (8, 4, 2, 1)  # targets per neighbour step: S * 128
_SMEM_ROW = 32  # kp * block_t rows stays a multiple of 1,024 SMEM words
_VMEM_BUDGET = 96 * 2**20  # of the v5e's 128 MiB VMEM per core
_ROWS = 2  # table rows a kernel loop step; block_t is a multiple of 8


def lookup_tile(
    B: int, Lp: int, *, block_b: int = 1024, block_t: int = 256
) -> tuple[int, int]:
    """(S, vmem_limit_bytes): the sublanes S of the (S, 128) target tile
    each neighbour step adds, for B targets of Lp futures, and the
    scoped VMEM limit the kernel compiles with.

    S is the largest of 8, 4, 2, 1 with S * 128 <= max(block_b, 128)
    targets, S <= cdiv(B, 128) (a small batch is not padded to more
    128-lane rows than it fills) and the double-buffered futures and
    output blocks, 2 * (Lp + block_t) * S * 128 float32, within the
    budget; S 1 when nothing larger fits."""
    cap = min(pl.cdiv(B, _LANES), max(1, block_b // _LANES))
    for S in _SUBLANES:
        need = 2 * (Lp + block_t) * S * _LANES * 4
        if S <= cap and need <= _VMEM_BUDGET:
            break
    return S, max(_VMEM_BUDGET, need)


def ccm_lookup_kernel(idx_ref, w_ref, y_ref, out_ref, *, k: int, kp: int):
    """out[t] = sum_j w[t, j] * y[idx[t, j]], summed in j order.

    idx_ref/w_ref: SMEM (block_t * kp,) — the table rows of this time
    block, flattened with rows padded to kp >= k entries; y_ref: VMEM
    (Lp, S, 128), one (S, 128) tile of targets per library point;
    out_ref: (block_t, S, 128).  Each loop step does _ROWS rows with
    their add chains interleaved, so one row's adds overlap another's."""
    block_t = out_ref.shape[0]

    def rows(i, carry):
        ts = [i * _ROWS + d for d in range(_ROWS)]
        acc = [jnp.zeros(out_ref.shape[1:], jnp.float32) for _ in ts]
        for j in range(k):  # static unroll: k <= 21
            for d, t in enumerate(ts):
                r = idx_ref[t * kp + j]
                acc[d] = acc[d] + w_ref[t * kp + j] * y_ref[r]
        for d, t in enumerate(ts):
            out_ref[t] = acc[d]
        return carry

    jax.lax.fori_loop(0, block_t // _ROWS, rows, 0)


def ccm_lookup_pallas(
    idx: jax.Array,
    w: jax.Array,
    Y_fut: jax.Array,
    *,
    interpret: bool,
    block_b: int = 1024,
    block_t: int = 256,
) -> jax.Array:
    """pred (B, Lq) for futures Y_fut (B, Lp) through one table idx/w
    (Lq, k) — or (R, B, Lq) through R tables idx/w (R, Lq, k) sharing the
    futures, as one grid with a table axis (the batched form vmap over
    library rows maps onto, see ops.ccm_lookup).  block_b bounds the
    targets of one block; the block is S * 128 targets, S from
    :func:`lookup_tile`.  ``interpret``: True runs the Pallas
    interpreter, False compiles for TPU."""
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
    S, vmem_limit = lookup_tile(B, Lp, block_b=block_b, block_t=block_t)
    Lq_pad = pl.cdiv(Lq, block_t) * block_t
    n_t = Lq_pad // block_t
    n_b = pl.cdiv(B, S * _LANES)
    B_pad = n_b * S * _LANES
    pad = ((0, 0), (0, Lq_pad - Lq), (0, kp - k))
    idx_p = jnp.pad(idx.astype(jnp.int32), pad).reshape(-1)
    w_p = jnp.pad(w.astype(jnp.float32), pad).reshape(-1)
    # (Lp, B_pad) cut into (S, 128) target tiles: a free reshape.
    Y_t = jnp.pad(Y_fut, ((0, B_pad - B), (0, 0))).T.reshape(
        Lp, n_b, S, _LANES
    )

    table = pl.BlockSpec(
        (block_t * kp,), lambda b, r, t: (r * n_t + t,),
        memory_space=pltpu.SMEM,
    )
    # Grid (target blocks, tables, time blocks): the (Lp, S, 128) futures
    # block is fetched once per target block and stays resident while
    # every table streams through it.
    out = pl.pallas_call(
        functools.partial(ccm_lookup_kernel, k=k, kp=kp),
        grid=(n_b, R, n_t),
        in_specs=[
            table,
            table,
            pl.BlockSpec((Lp, None, S, _LANES), lambda b, r, t: (0, b, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, block_t, None, S, _LANES), lambda b, r, t: (r, t, b, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (R, Lq_pad, n_b, S, _LANES), jnp.float32
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
        name="ccm_lookup",
    )(idx_p, w_p, Y_t)
    # Targets out of the tiles: not a bitcast, the tiled minor dims change.
    out = out.transpose(0, 2, 3, 4, 1)[..., :Lq].reshape(R, B_pad, Lq)[:, :B]
    return out[0] if single else out
