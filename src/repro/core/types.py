"""Shared EDM configuration and result types (notation DESIGN.md SS1;
every knob names the design section that owns it)."""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional


@dataclasses.dataclass(frozen=True)
class EDMConfig:
    """Configuration of one causal-inference run (paper §III).

    Attributes:
      E_max: maximum embedding dimension swept in simplex projection
        (paper uses <= 20 in practice).
      tau: delay-embedding lag.
      Tp: prediction horizon in time steps (paper: one step ahead).
      exclude_self: mask the zero-distance self neighbour when library ==
        target (cppEDM exclusionRadius semantics; see DESIGN.md SS4).
      lib_block: number of library series processed per device per chunk in
        the distributed CCM phase (granularity of progress checkpoints).
      engine: execution-engine registry key (repro.engine) that owns kNN
        tables, simplex forecast, and CCM lookup: "reference" (pure jnp),
        "pallas-interpret", "pallas-compiled", or any registered backend
        (DESIGN.md SS5).
      bucketed: must be True.  Phase 2 has one table layout: kNN tables
        only for the distinct optE values present, targets grouped by
        bucket for contiguous lookups (DESIGN.md SS3).  The all-E phase-2
        layout was removed; its oracle is repro.core.baseline.ccm_naive.
        The field stays because stored run fingerprints hash it.
      stream_depth: CCM row chunks in flight in the pipeline's streaming
        loop.  2 = double buffering (chunk i+1 dispatched while chunk i's
        device->host copy and row-block write drain); 1 = the fully
        synchronous legacy behaviour.
      target_tile: phase-2 COLUMN tile width (DESIGN.md SS7).  0 (default)
        keeps the single-tile path: the full (N, Lp) ts_fut is replicated
        per device and rho rows span all N columns.  > 0 splits phase 2
        into a second tiling dimension: kNN tables are built ONCE per row
        chunk, then targets stream through in column tiles of this width —
        only the live (tile, Lp) slice is resident per device and rho is
        emitted as (row-chunk x col-tile) blocks.  Phase 2 then allocates
        nothing that scales beyond the O(N L) inputs (ts and ts_fut stay
        host-resident): its own working set is O(chunk x tile) on the
        host (no (N, N) map when streaming to a store) and
        O(lib_block x buckets x Lp x k + tile x Lp) per device (no
        (N, Lp) replication).
      knn_tile_c: streaming kNN candidate-tile width (DESIGN.md SS8).
        Selection is ALWAYS streaming: candidate tiles folded through the
        running sorted top-k via the partial merge network.  0 (default)
        = one-shot calibration (knn.calibrate_knn_tile: widest
        power-of-two tile under the VMEM budget — a tile covering the
        whole library degenerates to one direct selection, so small
        libraries lose nothing).  > 0 = force that tile width.  -1 (the
        removed dense distance-matrix selection path) raises a
        deprecation error.  The distance working set is
        O(Lq x (tile + k log k)) — independent of the library length —
        and every tile width is bit-identical to the dense lax.top_k
        oracle (values and tie order) on every engine.
      use_kernels: DEPRECATED alias — True selects engine="pallas-compiled"
        (the old kernel routing), False engine="reference".
    """

    E_max: int = 20
    tau: int = 1
    Tp: int = 1
    exclude_self: bool = True
    lib_block: int = 8
    target_block: int = 2048
    engine: str = "reference"
    bucketed: bool = True
    stream_depth: int = 2
    target_tile: int = 0
    use_kernels: Optional[bool] = None
    # Accumulation variant of the DENSE ORACLE builders (knn_tables_dense
    # and friends — the lax.top_k A/B reference used by tests and
    # benchmarks; no engine routes through them):
    #   rebuild    — per-E matmul-form rebuild (the PAPER-FAITHFUL shape:
    #                mpEDM recomputes each E's kNN from scratch)
    #   scan       — cumulative-E lax.scan (beyond-paper; cost_analysis
    #                cannot see scan bodies)
    #   unroll     — cumulative-E python loop (XLA fuses consecutive updates)
    #   blocked:g  — scan over blocks of g unrolled steps (DEFAULT; falls
    #                back to unroll when E_max %% g != 0)
    knn_impl: str = "blocked:4"
    dist_dtype: str = "float32"  # bfloat16 halves distance-tile HBM traffic
    knn_tile_c: int = 0  # 0 auto-calibrated; >0 forced streaming tile width
    # k_override: pins the neighbour-table width independent of E_max.
    # None = unset (k tracks E_max / the bucket set); 0 is rejected so
    # "unset" can never be confused with a (meaningless) zero-neighbour
    # table.
    k_override: Optional[int] = None

    def __post_init__(self):
        if self.use_kernels is not None:
            warnings.warn(
                "EDMConfig.use_kernels is deprecated; pass "
                "engine='pallas-compiled' (True) or engine='reference' "
                "(False) instead",
                DeprecationWarning,
                stacklevel=3,
            )
            want = "pallas-compiled" if self.use_kernels else "reference"
            if self.engine not in ("reference", want):
                raise ValueError(
                    f"conflicting config: use_kernels={self.use_kernels} "
                    f"implies engine={want!r} but engine={self.engine!r} "
                    "was passed; drop use_kernels"
                )
            object.__setattr__(self, "engine", want)
            # Normalize so the shimmed config equals (and shares jit cache
            # entries with) the equivalent engine=... config, and so
            # dataclasses.replace(cfg, engine=...) is not overridden again.
            object.__setattr__(self, "use_kernels", None)
        if not self.bucketed:
            raise ValueError(
                "bucketed=False is not supported: the all-E phase-2 table "
                "layout was removed; use repro.core.baseline.ccm_naive as "
                "the all-E oracle"
            )
        if self.stream_depth < 1:
            raise ValueError("stream_depth must be >= 1")
        if self.target_tile < 0:
            raise ValueError("target_tile must be >= 0 (0 = untiled)")
        if self.knn_tile_c == -1:
            raise ValueError(
                "knn_tile_c=-1 (the removed dense distance-matrix "
                "selection path) is deprecated: selection is always "
                "streaming; pass 0 (auto-calibrated tile width) or a "
                "positive tile width"
            )
        if self.knn_tile_c < 0:
            raise ValueError(
                f"knn_tile_c={self.knn_tile_c} is invalid: 0 = "
                "auto-calibrated tile width, > 0 = forced tile width"
            )
        if self.k_override is not None and self.k_override < 1:
            raise ValueError(
                f"k_override={self.k_override} is invalid: pass None (unset; "
                "k tracks E_max / the bucket set) or a positive table width"
            )

    @property
    def k_max(self) -> int:
        # Simplex uses E+1 neighbours for embedding dimension E.
        return self.k_override if self.k_override is not None else self.E_max + 1

    def n_points(self, L: int) -> int:
        """Number of embeddable query/candidate points for a length-L series.

        All embedding dimensions share the aligned 'present-time' indexing
        (offset (E_max-1)*tau) so that tables for every E have one shape and
        the cumulative-distance recurrence applies (DESIGN.md SS2).
        """
        return L - (self.E_max - 1) * self.tau - self.Tp


@dataclasses.dataclass
class CausalMap:
    """Output of the pipeline: rho[i, j] = skill of cross-mapping target j
    from library i's reconstructed manifold (j "CCM-causes" i when high)."""

    rho: "jax.Array | numpy.ndarray"  # (N, N) float32
    optE: "jax.Array | numpy.ndarray"  # (N,) int32
    simplex_rho: Optional["jax.Array | numpy.ndarray"] = None  # (N, E_max)
