"""Phase 2 — Convergent Cross Mapping, mpEDM improved algorithm (paper Alg. 2).

Key idea reproduced from the paper: the kNN table depends only on the
*library* series, so per library series i we precompute tables once and
reuse them across all N targets — O(N L^2 E^2 + N^2 L E) vs cppEDM's
O(N^2 L^2 E).  Phase 2 has one table layout, the optE-BUCKETED one
(DESIGN.md SS3): tables only for the DISTINCT optE values present, with
targets grouped by bucket so every lookup batch shares one table
(contiguous gathers; the layout kernels/ccm_lookup is built for).  It
cuts table top-k work and footprint by len(buckets)/E_max against the
paper's all-E shape, whose oracle is :func:`repro.core.baseline.ccm_naive`.

rho[i, j] = pearson(ts_j_future, cross_map_prediction) — the skill of
predicting series j from library i's reconstructed manifold; high skill
means j CCM-causes i (paper SSII-B).

Phase 2 is additionally TILEABLE along the target (column) axis
(DESIGN.md SS7): table construction (`ccm_row_tables_bucketed` /
`ccm_block_tables_bucketed`) is split from the lookup
(`ccm_row_lookup_bucketed` / `ccm_block_tile_bucketed`) so one kNN table
set per library chunk is reused across every column tile — tables depend
only on the library series, so tiling never rebuilds them — and only the
live (tile, Lp) slice of the target futures needs to be resident.
`cfg.target_tile = 0` keeps the single-tile path.

All device compute routes through the execution engine named by
cfg.engine (repro.engine; DESIGN.md SS5).  Table construction inside the
engines is candidate-tiled STREAMING selection (cfg.knn_tile_c = forced
or auto-calibrated tile width, DESIGN.md SS8) — every tile width yields
bit-identical tables, so every CCM path here is oblivious to the choice;
the flat-in-Lc working set is what keeps per-device table construction
inside the VMEM/HBM budget at paper-scale library lengths.  For
libraries too long for one device, pipeline.knn_tables_library_sharded
shards the CANDIDATE axis and reduces per-shard tables host-side.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine as engines
from repro.core import embedding, knn
from repro.core.stats import pearson
from repro.core.types import EDMConfig


# ---------------------------------------------------------------- bucketing
@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static phase-2 grouping of targets by optimal embedding dimension.

    buckets: ascending distinct E values present in optE;
    counts[b]: number of targets whose optE == buckets[b].
    Hashable -> usable as a static jit argument.
    """

    buckets: tuple[int, ...]
    counts: tuple[int, ...]

    @property
    def offsets(self) -> tuple[int, ...]:
        """Start offset of each bucket's segment in the sorted target order."""
        out, off = [], 0
        for c in self.counts:
            out.append(off)
            off += c
        return tuple(out)

    @property
    def n_targets(self) -> int:
        return sum(self.counts)


def make_bucket_plan(optE: np.ndarray) -> tuple[BucketPlan, np.ndarray]:
    """Group targets by optE.

    Returns (plan, order) where ``order`` (a host ndarray) permutes targets
    into bucket-sorted layout: targets order[offsets[b]:offsets[b]+counts[b]]
    all share embedding dimension buckets[b].  The sort is stable so
    within-bucket target order is the original one.
    """
    optE = np.asarray(optE)
    values, counts = np.unique(optE, return_counts=True)
    plan = BucketPlan(
        buckets=tuple(int(v) for v in values),
        counts=tuple(int(c) for c in counts),
    )
    order = np.argsort(optE, kind="stable")
    return plan, order


def _check_k(k: int, Lp: int, cfg: EDMConfig, where: str) -> None:
    """Fail with a diagnosable message instead of crashing inside lax.top_k
    when the requested neighbour-table width exceeds the library points."""
    if k < 1:
        raise ValueError(f"{where}: neighbour count k={k} must be >= 1")
    if k > Lp:
        raise ValueError(
            f"{where}: k={k} neighbours requested but only Lp={Lp} library "
            f"points are embeddable (series too short for E_max={cfg.E_max}, "
            f"tau={cfg.tau}, Tp={cfg.Tp}; shrink E_max/k_override or use a "
            "longer series)"
        )


def _bucket_k(cfg: EDMConfig, plan: BucketPlan) -> int:
    """Neighbour-table width for the bucketed layout.

    ``k_override`` is honoured when SET (None = unset; 0 is rejected by
    EDMConfig) — the old ``cfg.k_override or ...`` idiom silently dropped
    an explicit 0 into the default path.
    """
    return plan.buckets[-1] + 1 if cfg.k_override is None else cfg.k_override


def ccm_row_tables_bucketed(
    x: jax.Array, cfg: EDMConfig, plan: BucketPlan
) -> tuple[jax.Array, jax.Array]:
    """kNN tables + weights for ONE library series, bucketed layout.

    Returns (idx, w), each (len(plan.buckets), Lp, k).
    """
    eng = engines.get_engine(cfg.engine)
    Lp = cfg.n_points(x.shape[0])
    kb = _bucket_k(cfg, plan)
    _check_k(kb, Lp, cfg, "ccm_row_tables_bucketed")
    V = embedding.lag_matrix(x, cfg.E_max, cfg.tau, Lp)
    idx, sqd = eng.knn_tables_bucketed(
        V, V, kb, buckets=plan.buckets, exclude_self=cfg.exclude_self, cfg=cfg
    )
    return knn.tables_with_weights_bucketed(idx, sqd, plan.buckets)


def _rho_for_table(eng, idx, w, seg, cfg: EDMConfig) -> jax.Array:
    """rho of every target in one bucket segment against one table.

    idx/w: (Lq, k) the bucket's table; seg: (n, Lp) bucket-sorted target
    futures.  The batched lookup makes the gather contiguous: all n targets
    stream through the SAME index table (the kernels/ccm_lookup access
    pattern) instead of per-target table rows.
    """
    n = seg.shape[0]
    tb = min(cfg.target_block, n)
    if n <= tb:
        return pearson(seg, eng.ccm_lookup(idx, w, seg))
    if n % tb != 0:  # pad to a block multiple; padded rows sliced off below
        seg = jnp.pad(seg, ((0, tb - n % tb), (0, 0)))
    blocks = seg.reshape(-1, tb, seg.shape[1])
    rho = jax.lax.map(
        lambda s: pearson(s, eng.ccm_lookup(idx, w, s)), blocks
    ).reshape(-1)
    return rho[:n]


def ccm_row_lookup_bucketed(
    idx: jax.Array, w: jax.Array, fut_tile: jax.Array, cfg: EDMConfig,
    seg_plan: tuple[tuple[int, int], ...],
) -> jax.Array:
    """rho of one bucket-sorted target tile against one library's tables.

    idx/w: (len(buckets), Lp, k) tables from :func:`ccm_row_tables_bucketed`;
    fut_tile: (t, Lp) a contiguous slice of the bucket-sorted target
    futures; seg_plan: static ((table_row, count), ...) describing how the
    tile decomposes into bucket segments (counts sum to t).  Each segment
    streams through its ONE shared table via the batched ccm_lookup — the
    contiguous access pattern the kernels are built for — exactly as in
    the untiled path, so tiled and untiled rho are bit-identical.
    """
    eng = engines.get_engine(cfg.engine)
    segs, off = [], 0
    for b, cnt in seg_plan:
        seg = jax.lax.slice_in_dim(fut_tile, off, off + cnt)
        segs.append(_rho_for_table(eng, idx[b], w[b], seg, cfg))
        off += cnt
    if fut_tile.shape[0] != off:
        raise ValueError(
            f"seg_plan covers {off} targets but tile has {fut_tile.shape[0]}"
        )
    return segs[0] if len(segs) == 1 else jnp.concatenate(segs)


def ccm_library_row_bucketed(
    x: jax.Array, ts_fut_sorted: jax.Array, cfg: EDMConfig, plan: BucketPlan
) -> jax.Array:
    """Cross-map every target from one library series, bucketed layout.

    ts_fut_sorted: (N, Lp) target futures permuted into plan order (see
    make_bucket_plan).  Returns the rho row (N,) in SORTED target order;
    the caller owns the inverse permutation.
    """
    idx, w = ccm_row_tables_bucketed(x, cfg, plan)
    seg_plan = tuple(enumerate(plan.counts))
    return ccm_row_lookup_bucketed(idx, w, ts_fut_sorted, cfg, seg_plan)


def make_tile_plans(
    plan: BucketPlan, tile: int
) -> list[tuple[int, tuple[tuple[int, int], ...]]]:
    """Static column-tile decomposition of the bucket-sorted target axis.

    Returns [(col0, seg_plan), ...] covering sorted columns [0, N) in
    tiles of ``tile`` (the last may be short); seg_plan is the
    ((table_row, count), ...) intersection of the tile with the bucket
    segments, consumable by :func:`ccm_row_lookup_bucketed`.  Distinct
    seg_plan values are few — interior tiles of a bucket share one — so
    jit recompilation stays bounded at ~2 x len(buckets) regardless of
    the number of tiles.
    """
    if tile < 1:
        raise ValueError(f"tile must be >= 1, got {tile}")
    N = plan.n_targets
    plans: list[tuple[int, tuple[tuple[int, int], ...]]] = []
    for c0 in range(0, N, tile):
        c1 = min(c0 + tile, N)
        segs = []
        for b, (off, cnt) in enumerate(zip(plan.offsets, plan.counts)):
            lo, hi = max(off, c0), min(off + cnt, c1)
            if hi > lo:
                segs.append((b, hi - lo))
        plans.append((c0, tuple(segs)))
    return plans


@functools.partial(jax.jit, static_argnames=("cfg", "plan"))
def ccm_block_bucketed(
    lib_block: jax.Array, ts_fut_sorted: jax.Array, cfg: EDMConfig, plan: BucketPlan
) -> jax.Array:
    """Bucketed rho rows: (B, L) -> (B, N), columns in plan-sorted order."""
    return jax.vmap(
        lambda x: ccm_library_row_bucketed(x, ts_fut_sorted, cfg, plan)
    )(lib_block)


# ------------------------------------------------- tiled phase 2 (DESIGN SS7)
def _block_tables_bucketed(lib_block: jax.Array, cfg: EDMConfig, plan: BucketPlan):
    return jax.vmap(lambda x: ccm_row_tables_bucketed(x, cfg, plan))(lib_block)


def _block_tile_bucketed(idx, w, fut_tile, cfg: EDMConfig, seg_plan):
    return jax.vmap(
        lambda i, ww: ccm_row_lookup_bucketed(i, ww, fut_tile, cfg, seg_plan)
    )(idx, w)


@functools.partial(jax.jit, static_argnames=("cfg", "plan"))
def ccm_block_tables_bucketed(lib_block: jax.Array, cfg: EDMConfig, plan: BucketPlan):
    """Bucketed tables for a block: (B, L) -> (idx, w) each
    (B, len(buckets), Lp, k).  Built ONCE per row chunk and reused by
    every :func:`ccm_block_tile_bucketed` call of that chunk."""
    return _block_tables_bucketed(lib_block, cfg, plan)


@functools.partial(jax.jit, static_argnames=("cfg", "seg_plan"))
def ccm_block_tile_bucketed(idx, w, fut_tile, cfg: EDMConfig, seg_plan):
    """One (row-chunk x col-tile) rho block, bucketed layout; columns in
    plan-sorted order, seg_plan from :func:`make_tile_plans`."""
    return _block_tile_bucketed(idx, w, fut_tile, cfg, seg_plan)


@functools.partial(jax.jit, static_argnames=("cfg",))
def all_futures(ts: jax.Array, cfg: EDMConfig) -> jax.Array:
    """(N, L) -> (N, Lp) future-value arrays used as cross-map targets."""
    N, L = ts.shape
    Lp = cfg.n_points(L)
    return jax.vmap(
        lambda x: embedding.future_values(x, cfg.E_max, cfg.tau, cfg.Tp, Lp)
    )(ts)


def ccm_matrix(ts: jax.Array, optE: jax.Array, cfg: EDMConfig) -> jax.Array:
    """Full (N, N) causal map on one device (small problems / tests).

    Dispatches on cfg.target_tile; the bucket permutation is undone on the
    columns before returning, and tiles are reassembled in column order.
    """
    ts_fut = all_futures(ts, cfg)
    if cfg.target_tile:
        return _ccm_matrix_tiled(ts, ts_fut, optE, cfg)
    plan, order = make_bucket_plan(np.asarray(optE))
    order_j = jnp.asarray(order)
    rho_sorted = ccm_block_bucketed(ts, ts_fut[order_j], cfg, plan)
    inv = jnp.asarray(np.argsort(order))
    return rho_sorted[:, inv]


def _ccm_matrix_tiled(
    ts: jax.Array, ts_fut: jax.Array, optE: jax.Array, cfg: EDMConfig
) -> jax.Array:
    """Single-device tiled phase 2: tables once, targets in column tiles."""
    N = ts.shape[0]
    T = cfg.target_tile
    plan, order = make_bucket_plan(np.asarray(optE))
    idx, w = ccm_block_tables_bucketed(ts, cfg, plan)
    ts_fut_sorted = ts_fut[jnp.asarray(order)]
    cols = [
        ccm_block_tile_bucketed(
            idx, w,
            jax.lax.slice_in_dim(ts_fut_sorted, c0, min(c0 + T, N)),
            cfg, seg_plan,
        )
        for c0, seg_plan in make_tile_plans(plan, T)
    ]
    rho_sorted = jnp.concatenate(cols, axis=1)
    inv = jnp.asarray(np.argsort(order))
    return rho_sorted[:, inv]


def ccm_convergence(
    x: jax.Array,
    y: jax.Array,
    E: int,
    lib_sizes: tuple[int, ...],
    cfg: EDMConfig,
    key: jax.Array,
) -> jax.Array:
    """DEPRECATED: convergence diagnostic, kept as a thin same-signature
    wrapper over the batched prefix-snapshot path
    (:func:`repro.inference.convergence.ccm_convergence_pair`).

    The old body rebuilt a full kNN table per library size (S full
    sweeps per pair); the new path snapshots ONE candidate sweep at each
    prefix boundary (DESIGN.md SS9).  Libraries are now NESTED random
    subsamples — prefixes of the key-seeded permutation — instead of
    independent per-size draws, so per-size rho values differ from the
    old implementation while the convergence behaviour (rho increasing
    with library size under true causation) is unchanged.
    """
    import warnings

    warnings.warn(
        "ccm_convergence is deprecated; use "
        "repro.inference.convergence.ccm_convergence_pair (per-pair) or "
        "repro.inference.run_significance (whole-map) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.inference.convergence import ccm_convergence_pair

    return ccm_convergence_pair(x, y, E, tuple(lib_sizes), cfg, key)
