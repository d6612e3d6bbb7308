"""Pallas-kernel engines (DESIGN.md SS5).

``pallas-compiled`` compiles the kernels with Mosaic for the TPU and
raises on any other backend — it never falls back to the interpreter, so
a run that names it either runs on the chip or fails.
``pallas-interpret`` runs the same kernels in the Pallas interpreter on
any backend: the kernel numerics, validated without a chip (tests, CPU
checks).  Interpret mode happens only when this engine is named.

Both route kNN-table construction through the streaming kernels in
kernels/knn_topk — including the in-kernel prefix-snapshot kernel for
``knn_tables_prefix`` (DESIGN.md SS9), so the CCM convergence diagnostic
no longer rebuilds per library size — and the batched CCM lookup through
kernels/ccm_lookup.
"""
from __future__ import annotations

import jax

from repro.engine.base import Engine

class PallasEngine(Engine):
    """The kernels compiled for the TPU; raises on any other backend."""

    name = "pallas-compiled"

    def _interpret(self) -> bool:
        backend = jax.default_backend()
        if backend != "tpu":
            raise RuntimeError(
                f"engine 'pallas-compiled' compiles its Pallas kernels for "
                f"a TPU, but the JAX backend is {backend!r}; name engine "
                f"'pallas-interpret' to run the kernels in the Pallas "
                f"interpreter, or 'reference'"
            )
        return False

    def knn_tables(self, Vq, Vc, k, *, exclude_self, cfg):
        from repro.kernels.knn_topk.ops import knn_topk_streaming

        # Streaming kernel (DESIGN.md SS8): per-program VMEM is flat in
        # Lc, so library length is HBM-bound, not VMEM-bound.
        tile = self.knn_selection_tile(Vc.shape[1], cfg)
        return knn_topk_streaming(
            Vq, Vc, k, exclude_self=exclude_self, tile_c=tile,
            dist_dtype=cfg.dist_dtype, interpret=self._interpret(),
        )

    # knn_tables_bucketed: the base truncate-to-max(buckets) + gather
    # (routed through knn_tables above, so it inherits the resolved tile
    # width) is the whole saving available without a bucket-aware kernel
    # (in-kernel bucket masking: DESIGN.md SS3, future work).

    def knn_tables_prefix(
        self, Vq, Vc, k, *, buckets, lib_sizes, exclude_self, cfg,
        col_ids=None,
    ):
        from repro.kernels.knn_topk.ops import knn_topk_prefix

        tile = self.knn_selection_tile(Vc.shape[1], cfg)
        return knn_topk_prefix(
            Vq, Vc, k, exclude_self, tuple(buckets), tuple(lib_sizes),
            tile_c=tile, dist_dtype=cfg.dist_dtype,
            interpret=self._interpret(), col_ids=col_ids,
        )

    def ccm_lookup(self, idx, w, Y_fut):
        from repro.kernels.ccm_lookup.ops import ccm_lookup

        return ccm_lookup(idx, w, Y_fut, interpret=self._interpret())

    def lookup_sublanes(self, B, Lp):
        from repro.kernels.ccm_lookup.ccm_lookup import lookup_tile

        return lookup_tile(B, Lp)[0]  # ccm_lookup's default blocks


class PallasInterpretEngine(PallasEngine):
    """The same kernels in the Pallas interpreter, on any backend."""

    name = "pallas-interpret"

    def _interpret(self) -> bool:
        return True
