"""Execution-engine abstraction for the EDM hot path (DESIGN.md SS5).

An :class:`Engine` owns the three named ops that dominate EDM runtime —
kNN-table construction, simplex forecast, and the batched CCM lookup —
behind one interface so the pipeline, phase-1 simplex sweep, and the
benchmarks are backend-agnostic (the kEDM "performance portability"
design point).  Concrete engines:

  * ``reference``        — pure jnp (core/knn.py); the oracle everything
                           else is checked against.
  * ``pallas-interpret`` — Pallas kernels in the Pallas interpreter;
                           numerics of the TPU kernels, runs anywhere.
  * ``pallas-compiled``  — Pallas kernels compiled with Mosaic for the
                           TPU; raises on any other backend.

Engines are *stateless*; ops may be called inside jit/shard_map traces
(engine resolution happens at trace time because ``EDMConfig`` is a
static argument).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp



class Engine:
    """Base engine: named EDM ops with reference fallbacks.

    Subclasses override the ops they accelerate; anything not overridden
    falls back to a correct (if slower) composition of the others.
    """

    #: registry key; subclasses must set this.
    name: str = "base"

    # -------------------------------------------------------------- ops
    @staticmethod
    def knn_selection_tile(Lc: int, cfg) -> int:
        """Candidate-tile width for the (always streaming) kNN-table
        construction (DESIGN.md SS8): cfg.knn_tile_c > 0 forces that
        width, 0 takes the one-shot VMEM-budget calibration
        (knn.calibrate_knn_tile).  One resolver for every backend so
        cfg.knn_tile_c means the same thing under all engines.  Always
        returns a positive width — a tile covering the whole library
        degenerates to one direct selection, so small libraries pay
        nothing for the tiling."""
        from repro.core import knn

        return knn.resolve_stream_tile(Lc, cfg)

    def knn_tables(self, Vq, Vc, k, *, exclude_self, cfg):
        """kNN tables for every embedding dimension 1..E_max.

        Vq: (E_max, Lq) query lag matrix, Vc: (E_max, Lc) candidates.
        Returns (idx, sq_dists), each (E_max, Lq, k).  Implementations
        stream candidate tiles of width :meth:`knn_selection_tile`
        through the running sorted-merge; the tiling is invisible to
        callers (any tile width is bit-identical to the dense oracle).
        """
        raise NotImplementedError

    def knn_tables_bucketed(self, Vq, Vc, k, *, buckets, exclude_self, cfg):
        """kNN tables only for the embedding dimensions in ``buckets``.

        buckets: static ascending tuple of distinct E values (DESIGN.md
        SS3).  Returns (idx, sq_dists), each (len(buckets), Lq, k).

        Default: build tables up to max(buckets) and gather the bucket
        rows — already a ``max(buckets)/E_max`` truncation win (for the
        Pallas kernels it is the whole saving available without a
        bucket-aware kernel); the reference engine overrides this to also
        skip the top-k at non-bucket E.
        """
        E_hi = buckets[-1]
        idx, sqd = self.knn_tables(
            Vq[:E_hi], Vc[:E_hi], k, exclude_self=exclude_self, cfg=cfg
        )
        rows = jnp.asarray([e - 1 for e in buckets], jnp.int32)
        return idx[rows], sqd[rows]

    def knn_tables_prefix(
        self, Vq, Vc, k, *, buckets, lib_sizes, exclude_self, cfg,
        col_ids=None,
    ):
        """Per-library-size kNN tables for the CCM convergence diagnostic.

        lib_sizes: static ascending tuple of nested library prefix sizes
        (candidate COLUMNS [0, Ls)); col_ids: optional (Lc,) permutation
        making the prefixes seeded random subsamples (DESIGN.md SS9).
        Returns (idx, sq_dists), each (len(lib_sizes), len(buckets), Lq, k).

        Default: the per-size rebuild oracle — one independent streaming
        sweep per library size.  Correct on every backend, but every
        concrete engine overrides it with a ONE-sweep prefix-snapshot
        path (bit-identical output, ~S x less candidate traffic): the
        reference engine with the jnp one-sweep builder, the Pallas
        engines with the in-kernel snapshot kernel (running VMEM top-k
        emitted at library-size boundary tiles).
        """
        from repro.core import knn

        tile = self.knn_selection_tile(Vc.shape[1], cfg)
        return knn.knn_tables_prefix_rebuild(
            Vq, Vc, k, exclude_self, buckets, lib_sizes, tile,
            dist_dtype=jnp.dtype(cfg.dist_dtype), col_ids=col_ids,
        )

    def simplex_forecast(self, idx, w, fut_c):
        """Weighted neighbour-future average (paper Alg. 5).

        idx, w: (..., Lq, k); fut_c: (Lc,).  Returns (..., Lq).
        """
        return jnp.sum(w * fut_c[idx], axis=-1)

    def ccm_lookup(self, idx, w, Y_fut):
        """Batched simplex lookup: many targets sharing ONE library table.

        idx, w: (Lq, k); Y_fut: (B, Lp).  Returns preds (B, Lq).

        The batch axis is the unit of phase-2 column tiling (DESIGN.md
        SS7): a target tile's bucket segments map directly onto this op
        with the SAME table — per-target results are independent, so any
        tile/segment partition of the batch yields bit-identical rho.
        """
        return jax.vmap(lambda y: self.simplex_forecast(idx, w, y))(Y_fut)

    def lookup_sublanes(self, B: int, Lp: int):
        """Sublanes of the target tile one neighbour step of a batched
        lookup of B targets by Lp futures adds, where the engine runs a
        lookup kernel; None where it runs none (this composition)."""
        return None

    # ------------------------------------------------------------ misc
    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<Engine {self.name}>"
