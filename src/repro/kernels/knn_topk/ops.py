"""jit'd public wrappers for the knn_topk streaming kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.knn_topk.knn_topk import (
    knn_topk_prefix_pallas,
    knn_topk_stream_pallas,
)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "exclude_self", "block_q", "tile_c", "dist_dtype", "interpret"
    ),
)
def knn_topk_streaming(
    Vq: jax.Array,
    Vc: jax.Array,
    k: int,
    exclude_self: bool = False,
    block_q: int = 128,
    tile_c: int = 512,
    dist_dtype: str = "float32",
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Multi-E kNN tables, STREAMING layout (DESIGN.md SS8).

    Vq: (E_max, Lq) query lag matrix, Vc: (E_max, Lc) candidates.
    Returns (idx, sq_dists) each (E_max, Lq, k): for every embedding
    dimension E=e+1, the k nearest candidates under the dimension-E
    delay-embedding distance.  The grid streams candidate tiles of width
    ``tile_c`` through a running sorted VMEM top-k (partial merge
    network), so per-program VMEM is independent of the library length
    (see knn_topk.stream_vmem_bytes) and arbitrary Lc fits the chip.
    dist_dtype: distance-accumulator dtype (EDMConfig.dist_dtype;
    bfloat16 halves the tile working set, merge keys stay float32).
    Bit-identical to the dense jnp oracle (ref.knn_topk_ref).
    interpret: True runs the Pallas interpreter, False compiles for TPU.
    """
    if exclude_self and Vq.shape != Vc.shape:
        raise ValueError("exclude_self requires query set == candidate set")
    return knn_topk_stream_pallas(
        Vq, Vc, k, exclude_self, block_q=block_q, tile_c=tile_c,
        interpret=interpret, dist_dtype=jnp.dtype(dist_dtype),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "exclude_self", "buckets", "lib_sizes", "block_q", "tile_c",
        "dist_dtype", "interpret",
    ),
)
def knn_topk_prefix(
    Vq: jax.Array,
    Vc: jax.Array,
    k: int,
    exclude_self: bool,
    buckets: tuple[int, ...],
    lib_sizes: tuple[int, ...],
    block_q: int = 128,
    tile_c: int = 512,
    dist_dtype: str = "float32",
    col_ids: jax.Array | None = None,
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """In-kernel prefix-snapshot kNN tables (DESIGN.md SS9).

    Returns (idx, sq_dists), each (len(lib_sizes), len(buckets), Lq, k):
    for every library prefix size Ls (candidate sweep positions [0, Ls),
    optionally routed through the ``col_ids`` permutation) and every
    bucket dimension E, the k nearest candidates.  Candidate tiles are
    clipped at library-size boundaries and the running carry emitted at
    each boundary — ONE sweep over the largest library, bit-identical to
    core/knn.knn_tables_prefix_streaming and the per-size rebuild oracle.
    interpret: True runs the Pallas interpreter, False compiles for TPU.
    """
    if exclude_self and Vq.shape != Vc.shape:
        raise ValueError("exclude_self requires query set == candidate set")
    return knn_topk_prefix_pallas(
        Vq, Vc, k, exclude_self, tuple(buckets), tuple(lib_sizes),
        block_q=block_q, tile_c=tile_c, interpret=interpret,
        dist_dtype=jnp.dtype(dist_dtype), col_ids=col_ids,
    )
