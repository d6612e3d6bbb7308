"""kNN table construction — the paper's hot spot (97% of cppEDM runtime).

Two paths:
  * pure-jnp (this file): cumulative-E scan + lax.top_k.  Oracle + CPU path.
  * Pallas (kernels/knn_topk): same math tiled for MXU/VMEM.  TPU path.

The cumulative-E recurrence (DESIGN.md SS2) builds the squared-distance
matrix for every embedding dimension E in one O(Lq*Lc) sweep per E:

    D_E(t, s) = D_{E-1}(t, s) + (V[E-1, t] - V[E-1, s])^2

where V = lag_matrix(x).  mpEDM recomputes each D_E from scratch
(O(Lq*Lc*E) each, O(Lq*Lc*E_max^2) total); the recurrence is an E_max/2 x
algorithmic saving on table construction, with identical results.

SELECTION is always STREAMING (DESIGN.md SS8): scan over candidate tiles
of width ``tile_c``, partial-sort each tile to its own top-k, and fold it
into a running sorted (Lq, k) table with the :func:`merge_topk_sorted`
comparator network — no O(Lq*Lc) array is ever built, the working set is
flat in Lc, and a tile covering the whole library degenerates to a single
direct selection, so small libraries pay nothing for the tiling.  The
historical dense distance-matrix layout survives only as the test/bench
oracle (:func:`knn_tables_dense`); ``calibrate_knn_tile`` replaces its
auto-threshold routing with a pure tile-width calibration
(EDMConfig.knn_tile_c = 0).

Bit-identity contract: streaming selection == ``lax.top_k`` over the full
candidate row (values AND tie order) for every k <= Lc and any tile
partition — see merge_topk_sorted / _knn_tables_streaming.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import embedding
from repro.core.stats import simplex_weights

# A numpy (not jnp) scalar: a module-scope device array would initialize
# the jax backend at import time, before runtime/platform.py can latch
# platform / XLA flags (DESIGN.md SS14).  jnp.where promotes it exactly
# like the old jnp.float32 constant.
INF = np.float32(np.inf)

# Ceiling of the per-program streaming working set the tile calibration
# aims for: the 16 MB TPU scoped-VMEM default.  Wide tiles amortize the
# per-tile selection+merge overhead; the KNN_TILE_MAX cap below is what
# keeps the kernels inside scoped VMEM.
KNN_TILE_BUDGET_BYTES = 16 * 2**20
# Lane-aligned bounds for calibrated candidate tiles: narrower than 128
# wastes VPU lanes.  Wider than 4096 breaks the v5e compiler's 16 MB
# scoped-VMEM limit: the Mosaic kernels hold about five tile-sized
# 32-bit temporaries per 128 query rows (distances, masked keys,
# knocked-out keys, column iota, selects) — the prefix kernel at 8192
# wide asked for 19.3 MB and was refused.
KNN_TILE_MIN, KNN_TILE_MAX = 128, 4096
# Host (pure-jnp) streaming profile: the working set targets the CPU
# last-level cache, not VMEM, and XLA:CPU's top_k carries a ~1.5 ms
# fixed cost PER CALL (measured at 128 rows; two 8192-wide calls lose to
# one 16384-wide call), so the host path calibrates against a wider
# budget and cap — paper-scale libraries (L <= 16384) become a single
# direct-selection tile on the reference engine.
KNN_TILE_BUDGET_BYTES_HOST = 32 * 2**20
KNN_TILE_MAX_HOST = 16384


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def streaming_bytes(
    Lq: int, k: int, tile_c: int, n_sel: int, dist_dtype=jnp.float32
) -> int:
    """Peak distance-working-set bytes of the streaming selection path:
    one (Lq, tile_c) tile (dist_dtype accumulator + i32 candidate ids),
    the tile's own (Lq, k) partial top-k, the DOUBLED (Lq, 2*K) merge
    -network buffers (dist f32 + id i32 + rank i32, K = next pow2 >= k),
    and the (n_sel, Lq, k) running tables.  Independent of Lc — the
    streaming scaling guarantee (DESIGN.md SS8)."""
    it = jnp.dtype(dist_dtype).itemsize
    K = _next_pow2(k)
    tile = Lq * tile_c * (it + 4)  # dist accumulator + i32 ids
    tile_topk = Lq * k * (4 + 4)  # per-tile partial sort output
    merge = Lq * 2 * K * (4 + 4 + 4)  # network: f32 dist + i32 id + i32 rank
    carry = n_sel * Lq * k * (4 + 4)
    return tile + tile_topk + merge + carry


@functools.lru_cache(maxsize=None)
def calibrate_knn_tile(
    Lc: int,
    E_max: int = 20,
    k: int = 21,
    block_q: int = 128,
    dist_dtype: str = "float32",
    budget_bytes: int = KNN_TILE_BUDGET_BYTES,
    tile_max: int = KNN_TILE_MAX,
) -> int:
    """One-shot candidate-tile-width calibration (EDMConfig.knn_tile_c=0).

    Streaming with a tile covering the whole library IS the direct dense
    selection (one tile, no merges), so the widest tile that fits the
    working-set budget is optimal at every Lc: small libraries get the
    single-tile fast case, large ones the flat-memory scan.  Picks the
    largest power-of-two width in [KNN_TILE_MIN, KNN_TILE_MAX] not
    exceeding ``budget_bytes`` under the :func:`streaming_bytes` model
    (evaluated at one ``block_q`` query block — the Pallas per-program
    shape), stopping early once the tile covers Lc.  Pure shape
    arithmetic: no timing runs, stable across processes, cacheable.
    """
    if Lc < 1:
        raise ValueError(f"Lc={Lc} must be positive")
    tile = KNN_TILE_MIN
    while tile < Lc and tile < tile_max:
        nxt = tile * 2
        if streaming_bytes(block_q, k, nxt, E_max, dist_dtype) > budget_bytes:
            break
        tile = nxt
    return tile


def resolve_stream_tile(Lc: int, cfg, profile: str = "vmem") -> int:
    """EDMConfig.knn_tile_c semantics, shared by every engine: > 0 forces
    that candidate-tile width, 0 auto-calibrates via
    :func:`calibrate_knn_tile`.  -1 — the deleted dense distance-matrix
    route — raises instead of silently selecting a layout that no longer
    exists (EDMConfig construction already rejects it; this guards
    config-like ducks).

    ``profile`` picks the calibration budget for knn_tile_c=0: "vmem"
    (default, safe on every backend) models the 16 MB Pallas per-program
    footprint; "host" models the CPU cache for pure-jnp call sites,
    allowing the wider tiles that amortize XLA:CPU's per-top_k-call
    cost."""
    if cfg.knn_tile_c > 0:
        return cfg.knn_tile_c
    if cfg.knn_tile_c < 0:
        raise ValueError(
            "knn_tile_c=-1 (the removed dense distance-matrix selection "
            "path) is deprecated: selection is always streaming; use 0 "
            "(auto-calibrated tile width) or a positive tile width"
        )
    budget, tile_max = (
        (KNN_TILE_BUDGET_BYTES_HOST, KNN_TILE_MAX_HOST)
        if profile == "host"
        else (KNN_TILE_BUDGET_BYTES, KNN_TILE_MAX)
    )
    tile = calibrate_knn_tile(
        Lc, E_max=cfg.E_max, k=cfg.k_max, dist_dtype=cfg.dist_dtype,
        budget_bytes=budget, tile_max=tile_max,
    )
    _emit_calibration(Lc, tile, profile, cfg)
    return tile


# Calibration results are pure shape arithmetic — emit each distinct
# (Lc, tile, profile) once per process, not once per chunk.
_calibration_seen: set = set()


def _emit_calibration(Lc: int, tile: int, profile: str, cfg) -> None:
    from repro.runtime import telemetry  # lazy: knn is a leaf module

    if not telemetry.enabled():
        return
    key = (Lc, tile, profile)
    if key in _calibration_seen:
        return
    _calibration_seen.add(key)
    telemetry.counter(
        "engine", "knn_tile", float(tile), Lc=Lc, profile=profile,
        working_set_bytes=streaming_bytes(128, cfg.k_max, tile, cfg.E_max,
                                          cfg.dist_dtype),
    )


def merge_topk_sorted(run_i, run_d, new_i, new_d, k: int):
    """Bitonic partial merge network for two sorted top-k lists.

    run_i/run_d: (..., k) running top-k, ascending by (distance, arrival
    order); new_i/new_d: (..., m <= k) incoming tile top-k, ascending in
    its own arrival order.  Returns (idx, dist), each (..., k): the top-k
    of the union, ascending, ties resolved running-before-new and
    earlier-position-first within each list — exactly the
    ``lax.top_k(concat([running, tile]))`` rule of the old merge, but as
    a fixed O(k log k) comparator network instead of an O((k + tile)
    log(k + tile))-class selection over the whole buffer.

    Mechanics: pad both lists to K = next_pow2(k) with (+inf, id 2^31-1)
    sentinels, attach explicit arrival ranks (running 0..K-1, new
    K..2K-1) so the comparator key (distance, rank) is a strict total
    order, lay out [running | reverse(new)] — ascending then descending,
    i.e. bitonic — and run the log2(2K) halving compare-exchange stages.
    (dist, id, rank) triples travel together through every exchange, so
    the output order is deterministic and partition-independent; padding
    sentinels order strictly after every real entry and can only surface
    in the k > (real candidates) cases the builders reject.  The Pallas
    kernels merge under the same total order with a lane-friendly
    two-pointer form (kernels/knn_topk._merge_sorted), so both give the
    same tables bit-for-bit.
    """
    K = _next_pow2(k)

    def _pad(i, d, rank0):
        w = d.shape[-1]
        pad = K - w
        if pad:
            shp = d.shape[:-1] + (pad,)
            d = jnp.concatenate(
                [d, jnp.full(shp, jnp.inf, jnp.float32)], axis=-1
            )
            i = jnp.concatenate(
                [i, jnp.full(shp, 2147483647, jnp.int32)], axis=-1
            )
        pos = jax.lax.broadcasted_iota(jnp.int32, d.shape, d.ndim - 1)
        r = rank0 + pos
        if pad:
            # Padding sentinels rank after EVERY real entry of BOTH lists
            # (2K offset), not just after their own list: a real entry can
            # legitimately carry dist=+inf (masked self / shard-padding
            # column, k == Lc), and the (dist, rank) key must still order
            # it before synthetic padding — the shard-merge tree (SS14)
            # feeds such lists; interior sentinels ranking between the two
            # lists would beat the new list's genuine +inf entries and
            # break the lax.top_k (distance, id) tie contract.
            r = jnp.where(pos >= w, r + 2 * K, r)
        return i, d, r

    ai, ad, ar = _pad(run_i, run_d, 0)
    bi, bd, br = _pad(new_i, new_d, K)
    d = jnp.concatenate([ad, bd[..., ::-1]], axis=-1)
    i = jnp.concatenate([ai, bi[..., ::-1]], axis=-1)
    r = jnp.concatenate([ar, br[..., ::-1]], axis=-1)
    lead = d.shape[:-1]
    s = K
    while s >= 1:
        shape = lead + (K // s, 2, s)
        dv = d.reshape(shape)
        d_lo, d_hi = dv[..., 0, :], dv[..., 1, :]
        iv = i.reshape(shape)
        i_lo, i_hi = iv[..., 0, :], iv[..., 1, :]
        rv = r.reshape(shape)
        r_lo, r_hi = rv[..., 0, :], rv[..., 1, :]
        sw = (d_lo > d_hi) | ((d_lo == d_hi) & (r_lo > r_hi))

        def _apply(lo, hi, sw=sw, shape=shape, lead=lead):
            return jnp.stack(
                [jnp.where(sw, hi, lo), jnp.where(sw, lo, hi)], axis=-2
            ).reshape(lead + (2 * K,))

        d = _apply(d_lo, d_hi)
        i = _apply(i_lo, i_hi)
        r = _apply(r_lo, r_hi)
        s //= 2
    return i[..., :k], d[..., :k]

# Trace-time instrumentation: total (Lq, k) table rows selected by the
# builders below, keyed by builder kind.  jit caches traces, so tests that
# assert on these counters must use fresh shapes/configs (or call the
# builders un-jitted); see tests/test_engine.py.
TABLE_ROWS_BUILT = {"all_E": 0, "bucketed": 0}


def reset_table_counters() -> None:
    for k in TABLE_ROWS_BUILT:
        TABLE_ROWS_BUILT[k] = 0


def _acc_sq(D: jax.Array, vq: jax.Array, vc: jax.Array, dist_dtype) -> jax.Array:
    """One cumulative-E distance update with PINNED square-then-add rounding.

    LLVM contracts ``D + (vq - vc)**2`` into an FMA inside some XLA:CPU
    fusions but not others (scan body vs unrolled, dense vs tile shapes),
    shifting results by 1 ulp and breaking the dense==streaming
    bit-identity contract (DESIGN.md SS8).  The ``maximum(sq, 0)`` guard —
    numerically exact, squares are non-negative — sits between the
    multiply and the add, so no context can contract them; every
    cumulative builder (dense oracle, bucketed, streaming, single-E)
    therefore runs the identical square-then-add float sequence.  ``optimization_barrier`` does NOT
    work here: it is dropped before the fusion/codegen stage that decides
    contraction, and ``abs`` is folded by the algebraic simplifier.
    """
    return _acc_sq_cols(D, vq[:, None], vc[None, :], dist_dtype)


def _acc_sq_cols(D, q_col, c_row, dist_dtype):
    """:func:`_acc_sq` on operands already shaped for broadcasting: a
    (rows, 1) query column against a (1, cols) candidate row — the form
    the Pallas kernels read straight from their VMEM blocks.  The one
    definition of the float sequence."""
    sq = jnp.square(q_col - c_row).astype(dist_dtype)
    return D + jnp.maximum(sq, jnp.zeros((), dist_dtype))


def knn_tables_dense(
    Vq: jax.Array,
    Vc: jax.Array,
    k_max: int,
    exclude_self: bool,
    impl: str = "scan",
    dist_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """DENSE ORACLE: kNN tables for every embedding dimension 1..E_max by
    materializing the full (Lq, Lc) distance matrix and lax.top_k-ing it
    per E.  No engine routes here any more — selection is always
    streaming — but this builder is the independent oracle the streaming
    bit-identity tests and the benchmark historical-reference column
    compare against, and the knn_impl A/B surface.

    Vq: (E_max, Lq) query lag matrix; Vc: (E_max, Lc) candidate lag matrix.
    Returns (indices, sq_dists), each (E_max, Lq, k_max); row e holds the
    k_max nearest candidates under the dimension-(e+1) embedding distance.
    exclude_self requires Vq and Vc to be the same point set (CCM tables).

    impl (SSPerf hillclimb #3 knobs):
      scan    — cumulative-E lax.scan over lag increments (baseline);
      unroll  — same recurrence, python loop: XLA fuses the D update with
                the following top_k read, cutting D HBM round-trips;
      rebuild — per-E from-scratch matmul-form distances (O(L^2 E) each):
                more MXU FLOPs, ~1/3 less D traffic — for compute-starved,
                memory-bound cells.
    dist_dtype: bfloat16 halves D traffic at ~1e-2 relative distance error
                (neighbour sets may differ on near-ties; opt-in).
    """
    E_max, Lq = Vq.shape
    Lc = Vc.shape[1]
    if exclude_self and Lq != Lc:
        raise ValueError("exclude_self requires query set == candidate set")
    if impl.startswith("blocked"):
        # fall back to fully-unrolled when the block size doesn't divide E_max
        g = int(impl.split(":")[1]) if ":" in impl else 4
        if E_max % g != 0:
            impl = "unroll"
    TABLE_ROWS_BUILT["all_E"] += E_max
    self_mask = (
        jnp.eye(Lq, dtype=bool) if exclude_self else jnp.zeros((Lq, Lc), bool)
    )

    def select(D):
        Dm = jnp.where(self_mask, INF, D.astype(jnp.float32))
        neg_d, idx = jax.lax.top_k(-Dm, k_max)
        return idx.astype(jnp.int32), -neg_d

    if impl == "rebuild":
        outs = [
            select(_matmul_sq_dists(Vq[:E], Vc[:E]).astype(dist_dtype))
            for E in range(1, E_max + 1)
        ]
        indices = jnp.stack([o[0] for o in outs])
        sq_dists = jnp.stack([o[1] for o in outs])
        return indices, sq_dists

    def step(D, vs):
        vq, vc = vs
        D = _acc_sq(D, vq, vc, dist_dtype)
        return D, select(D)

    D0 = jnp.zeros((Lq, Lc), dist_dtype)
    if impl == "unroll":
        outs = []
        D = D0
        for e in range(E_max):
            D, out = step(D, (Vq[e], Vc[e]))
            outs.append(out)
        indices = jnp.stack([o[0] for o in outs])
        sq_dists = jnp.stack([o[1] for o in outs])
        return indices, sq_dists
    if impl.startswith("blocked"):
        # scan over E-blocks of g unrolled steps: D-matrix HBM round-trips
        # drop ~g-fold (XLA fuses within a block) while only ~g distance
        # matrices stay live — the peak-vs-traffic frontier knob (HC3 #5).
        def block_step(D, vs_blk):
            vq_b, vc_b = vs_blk  # (g, Lq), (g, Lc)
            outs = []
            for e in range(g):
                D, out = step(D, (vq_b[e], vc_b[e]))
                outs.append(out)
            idx = jnp.stack([o[0] for o in outs])
            d = jnp.stack([o[1] for o in outs])
            return D, (idx, d)

        nb = E_max // g
        _, (indices, sq_dists) = jax.lax.scan(
            block_step,
            D0,
            (Vq.reshape(nb, g, Lq), Vc.reshape(nb, g, Lc)),
        )
        return indices.reshape(E_max, Lq, -1), sq_dists.reshape(E_max, Lq, -1)
    _, (indices, sq_dists) = jax.lax.scan(step, D0, (Vq, Vc))
    return indices, sq_dists


def knn_tables_bucketed_dense(
    Vq: jax.Array,
    Vc: jax.Array,
    k: int,
    exclude_self: bool,
    buckets: tuple[int, ...],
    impl: str = "unroll",
    dist_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """DENSE ORACLE, bucketed: tables only for the dimensions in
    ``buckets`` via the full (Lq, Lc) distance matrix.  Test/bench oracle
    only — every engine builds bucketed tables with the streaming merge
    network (:func:`knn_tables_bucketed_streaming`).

    Phase-2 CCM never reads a table row whose E is absent from optE, so
    building just the distinct-optE bucket set (DESIGN.md SS3) cuts both
    the top-k work and the stacked-table footprint by len(buckets)/E_max.
    The distance accumulation still sweeps e = 1..max(buckets) (the prefix
    recurrence needs every lag), but the O(Lq*Lc*k)-ish selection — the
    dominant term at paper k — runs only at bucket dimensions, and lags
    above max(buckets) are never touched.

    buckets: static ascending tuple of distinct E values (1-based).
    impl: "rebuild" builds each bucket's distances from scratch in matmul
    form (the knn_tables_dense "rebuild" numerics: near-ties may order
    differently); every other value uses the unrolled cumulative
    recurrence, whose sparse selection makes the scan/blocked sweep
    shapings moot.  Returns (idx, sq_dists), each (len(buckets), Lq, k);
    row b holds the table for embedding dimension buckets[b].  Cumulative
    numerics are bit-identical to the matching rows of the cumulative
    knn_tables_dense variants (same termwise-sequential accumulation
    order).
    """
    if not buckets or list(buckets) != sorted(set(buckets)):
        raise ValueError(f"buckets must be ascending and distinct: {buckets}")
    E_max, Lq = Vq.shape
    Lc = Vc.shape[1]
    if buckets[-1] > E_max:
        raise ValueError(f"bucket E {buckets[-1]} exceeds lag rows {E_max}")
    if exclude_self and Lq != Lc:
        raise ValueError("exclude_self requires query set == candidate set")
    TABLE_ROWS_BUILT["bucketed"] += len(buckets)
    self_mask = (
        jnp.eye(Lq, dtype=bool) if exclude_self else jnp.zeros((Lq, Lc), bool)
    )

    def select(D):
        Dm = jnp.where(self_mask, INF, D.astype(jnp.float32))
        neg_d, idx = jax.lax.top_k(-Dm, k)
        return idx.astype(jnp.int32), -neg_d

    if impl == "rebuild":
        outs = [
            select(_matmul_sq_dists(Vq[:E], Vc[:E]).astype(dist_dtype))
            for E in buckets
        ]
    else:
        want = set(buckets)
        outs = []
        D = jnp.zeros((Lq, Lc), dist_dtype)
        for e in range(buckets[-1]):
            D = _acc_sq(D, Vq[e], Vc[e], dist_dtype)
            if e + 1 in want:
                outs.append(select(D))
    indices = jnp.stack([o[0] for o in outs])
    sq_dists = jnp.stack([o[1] for o in outs])
    return indices, sq_dists


# ------------------------------------------- streaming candidate-tiled path
def _knn_tables_streaming(
    Vq: jax.Array,
    Vc: jax.Array,
    k: int,
    exclude_self: bool,
    tile_c: int,
    select_Es: tuple[int, ...],
    dist_dtype,
    col_offset=0,
    col_hi=None,
) -> tuple[jax.Array, jax.Array]:
    """Candidate-tiled kNN selection: no (Lq, Lc) distance matrix, ever.

    Scans candidate tiles of width ``tile_c``; within each tile the
    cumulative-E recurrence accumulates a (Lq, tile_c) distance block, and
    at every E in ``select_Es`` the tile is partial-sorted to its own
    top-k (lax.top_k over tile_c columns) and folded into the running
    sorted (Lq, k) table with the :func:`merge_topk_sorted` comparator
    network — O(k log k) per merge, independent of tile width.  The peak
    distance working set is O(Lq * tile_c) + the (n_sel, Lq, k) carry —
    independent of Lc (DESIGN.md SS8).

    BIT-IDENTITY with ``lax.top_k`` over the full candidate row — and
    hence with the CUMULATIVE dense-oracle impls (scan/unroll/blocked,
    NOT the matmul-form ``rebuild`` A/B shape, whose near-tie ordering
    already differs from them) — values AND tie order, argument:
    per-element distances accumulate lag terms in the same sequential
    order, so they are bit-equal to the dense oracle's; lax.top_k breaks
    value ties by lowest position; the running list is kept sorted by
    (distance, arrival), tile entries excluded from a tile's own top-k
    can never reach the union top-k, and the merge network's rank key
    orders running entries (globally earlier candidates, by induction —
    the first tile is selected directly with no synthetic carry) before
    tile entries and tile entries by ascending position — so equal
    distances always resolve to the lowest candidate id, exactly the
    lax.top_k rule.  Holds for every k <= Lc, including all-tied
    (dead/duplicate-neuron) rows, and for ANY tile partition.

    ``col_offset``/``col_hi`` (library sharding, DESIGN.md SS8): candidate
    column j of Vc is GLOBAL candidate ``col_offset + j``; columns at or
    beyond ``col_hi`` (default col_offset + Lc) are padding and masked to
    +inf.  ``exclude_self`` masks global column == query row.  Both may be
    traced scalars, so per-shard builds jit/shard_map with one trace.
    """
    if not select_Es or list(select_Es) != sorted(set(select_Es)):
        raise ValueError(f"select_Es must be ascending, distinct: {select_Es}")
    E_hi = select_Es[-1]
    E_rows, Lq = Vq.shape
    Lc = Vc.shape[1]
    if E_hi > E_rows:
        raise ValueError(f"selection E {E_hi} exceeds lag rows {E_rows}")
    if k > Lc:
        raise ValueError(f"k={k} exceeds candidate count Lc={Lc}")
    # First tile selects directly (no synthetic carry entries), so it must
    # be at least k wide; clamping also avoids over-padding tiny libraries.
    tile_c = max(k, min(tile_c, Lc))
    n_tiles = -(-Lc // tile_c)
    # Balance tile widths under the calibrated cap: the same number of
    # tiles, each ceil(Lc / n_tiles) wide, so the sweep pays at most
    # n_tiles - 1 padded columns instead of a whole ragged tail tile
    # (Lc=16000 under an 8192 cap -> 2 x 8000, zero padding).
    tile_c = max(k, -(-Lc // n_tiles))
    Vq = Vq[:E_hi]
    Vc = jnp.pad(Vc[:E_hi], ((0, 0), (0, n_tiles * tile_c - Lc)))
    tiles = Vc.reshape(E_hi, n_tiles, tile_c).transpose(1, 0, 2)
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * tile_c
    if col_hi is None:
        col_hi = col_offset + Lc
    want = set(select_Es)
    row_ids = jnp.arange(Lq, dtype=jnp.int32)[:, None]

    def tile_tables(run, vc_t, start):
        cols = col_offset + start + jnp.arange(tile_c, dtype=jnp.int32)[None, :]
        invalid = jnp.broadcast_to(cols >= col_hi, (Lq, tile_c))
        if exclude_self:
            invalid = invalid | (cols == row_ids)
        D = jnp.zeros((Lq, tile_c), dist_dtype)
        out_i, out_d = [], []
        for e in range(E_hi):
            D = _acc_sq(D, Vq[e], vc_t[e], dist_dtype)
            if e + 1 not in want:
                continue
            # Partial-sort the tile to its own top-k (sorted by distance,
            # then position; mask and negate in one pass — -inf marks
            # invalid columns, equivalent to +inf before negation).
            neg_d, pos = jax.lax.top_k(
                jnp.where(invalid, -INF, -D.astype(jnp.float32)), k
            )
            # tile ids are affine in position (col_offset + start + j for
            # every column, valid or masked), so the id gather is an add
            out_i.append((pos + start + col_offset).astype(jnp.int32))
            out_d.append(-neg_d)
        t_i, t_d = jnp.stack(out_i), jnp.stack(out_d)
        if run is None:
            return t_i, t_d
        # ONE comparator-network merge batched over every selected E —
        # same O(k log k) exchanges per row, 1/n_sel the op dispatches —
        # folding the tile top-ks into the sorted running lists; never a
        # (k + tile_c) buffer.
        return merge_topk_sorted(run[0], run[1], t_i, t_d, k)

    carry = tile_tables(None, tiles[0], starts[0])
    if n_tiles == 1:
        return carry

    def step(run, xs):
        return tile_tables(run, xs[0], xs[1]), None

    (idx, dist), _ = jax.lax.scan(step, carry, (tiles[1:], starts[1:]))
    return idx, dist


def knn_tables_all_E_streaming(
    Vq: jax.Array,
    Vc: jax.Array,
    k_max: int,
    exclude_self: bool,
    tile_c: int,
    dist_dtype=jnp.float32,
    col_offset=0,
    col_hi=None,
) -> tuple[jax.Array, jax.Array]:
    """All-E streaming tables — identical (idx, sq_dists) to the dense
    oracle :func:`knn_tables_dense` (cumulative impls), (E_max, Lq, k_max)
    each, built without ever materializing the (Lq, Lc) distance matrix
    (DESIGN.md SS8).  THE engine selection path for phase 1 / unbucketed
    phase 2."""
    E_max, Lq = Vq.shape
    unsharded = col_hi is None and isinstance(col_offset, int) and col_offset == 0
    if exclude_self and unsharded and Lq != Vc.shape[1]:
        raise ValueError("exclude_self requires query set == candidate set")
    TABLE_ROWS_BUILT["all_E"] += E_max
    return _knn_tables_streaming(
        Vq, Vc, k_max, exclude_self, tile_c,
        tuple(range(1, E_max + 1)), dist_dtype, col_offset, col_hi,
    )


def knn_tables_bucketed_streaming(
    Vq: jax.Array,
    Vc: jax.Array,
    k: int,
    exclude_self: bool,
    buckets: tuple[int, ...],
    tile_c: int,
    dist_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """Bucketed streaming tables — identical (len(buckets), Lq, k) tables
    to the dense oracle :func:`knn_tables_bucketed_dense`; the per-tile
    distance accumulation still sweeps e = 1..max(buckets) but selection
    (and the carry) exists only at bucket dimensions.  THE engine
    selection path for bucketed phase 2."""
    if not buckets or list(buckets) != sorted(set(buckets)):
        raise ValueError(f"buckets must be ascending and distinct: {buckets}")
    if exclude_self and Vq.shape[1] != Vc.shape[1]:
        raise ValueError("exclude_self requires query set == candidate set")
    TABLE_ROWS_BUILT["bucketed"] += len(buckets)
    return _knn_tables_streaming(
        Vq, Vc, k, exclude_self, tile_c, tuple(buckets), dist_dtype
    )


# --------------------------------------- prefix-snapshot path (DESIGN SS9)
def _check_prefix_args(
    Lq: int, Lc: int, k: int, exclude_self: bool,
    buckets: tuple[int, ...], lib_sizes: tuple[int, ...], E_rows: int,
    col_ids,
) -> None:
    if not buckets or list(buckets) != sorted(set(buckets)):
        raise ValueError(f"buckets must be ascending and distinct: {buckets}")
    if buckets[-1] > E_rows:
        raise ValueError(f"bucket E {buckets[-1]} exceeds lag rows {E_rows}")
    if not lib_sizes or list(lib_sizes) != sorted(set(lib_sizes)):
        raise ValueError(
            f"lib_sizes must be ascending and distinct: {lib_sizes}"
        )
    if lib_sizes[-1] > Lc:
        raise ValueError(
            f"lib_sizes[-1]={lib_sizes[-1]} exceeds candidate count Lc={Lc}"
        )
    # Every query row must find k REAL neighbours inside the smallest
    # library; with self-exclusion one prefix column may be the query
    # itself, so one extra candidate is required.
    need = k + 1 if exclude_self else k
    if lib_sizes[0] < need:
        raise ValueError(
            f"lib_sizes[0]={lib_sizes[0]} too small for k={k} neighbours"
            + (" with self-exclusion" if exclude_self else "")
            + "; raise the smallest library size or shrink k"
        )
    if exclude_self and col_ids is None and Lq != Lc:
        raise ValueError("exclude_self requires query set == candidate set")


def _prefix_tile_bounds(
    lib_sizes: tuple[int, ...], tile_c: int
) -> list[tuple[int, int]]:
    """Candidate-tile [start, stop) spans covering [0, lib_sizes[-1]) that
    never CROSS a library-size boundary, so the running carry after the
    tile ending at each boundary IS that prefix's table."""
    bounds = []
    lo = 0
    for hi in lib_sizes:
        for s in range(lo, hi, tile_c):
            bounds.append((s, min(s + tile_c, hi)))
        lo = hi
    return bounds


def knn_tables_prefix_streaming(
    Vq: jax.Array,
    Vc: jax.Array,
    k: int,
    exclude_self: bool,
    buckets: tuple[int, ...],
    lib_sizes: tuple[int, ...],
    tile_c: int,
    dist_dtype=jnp.float32,
    col_ids: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """ONE-sweep prefix-snapshot kNN tables (DESIGN.md SS9).

    Returns (idx, sq_dists), each (S, len(buckets), Lq, k) where
    S = len(lib_sizes): slice s holds, for every bucket dimension, the
    top-k table restricted to candidate COLUMNS [0, lib_sizes[s]) — the
    nested library prefixes of the CCM convergence diagnostic — built in
    a single candidate sweep by snapshotting the streaming running carry
    at each prefix boundary (vs S full per-size rebuilds).

    Tiles are the streaming merge of SS8 with boundaries clipped so no
    tile crosses a prefix edge; the carry after the tile ending at
    lib_sizes[s] is exactly the table a from-scratch build over the first
    lib_sizes[s] columns produces (same per-element accumulation order,
    same lowest-position tie rule), so snapshots are BIT-IDENTICAL to
    independently built per-size tables (:func:`knn_tables_prefix_rebuild`).

    ``col_ids``: optional (Lc,) int32 candidate PERMUTATION: position j
    of the sweep order holds candidate COLUMN col_ids[j] of Vc, so the
    size-Ls library is the random subset {col_ids[0], ..., col_ids[Ls-1]}
    — the seeded nested subsampling of the convergence diagnostic.  The
    builder gathers the permuted columns tile by tile; emitted indices
    are ORIGINAL candidate ids, directly usable against unpermuted
    target futures, and ``exclude_self`` masks col_ids[j] == query row.
    None = natural order (ids = positions).
    """
    E_rows, Lq = Vq.shape
    Lc = Vc.shape[1]
    _check_prefix_args(
        Lq, Lc, k, exclude_self, buckets, lib_sizes, E_rows, col_ids
    )
    E_hi = buckets[-1]
    # The first tile selects directly (no carry), so it must be at least k
    # wide; its width is min(tile_c, lib_sizes[0]) and lib_sizes[0] >= k is
    # validated above, hence clamping tile_c up to k suffices.  tile_c is
    # deliberately NOT clamped down to lib_sizes[0]: segments between
    # boundaries should stay whole (one merge per snapshot gap) whenever
    # they fit a tile — splitting them only adds merge overhead.
    tile_c = max(k + 1 if exclude_self else k, tile_c)
    want = set(buckets)
    Vq = Vq[:E_hi]
    row_ids = jnp.arange(Lq, dtype=jnp.int32)[:, None]
    boundary = set(lib_sizes)

    run_i = run_d = None
    snaps_i, snaps_d = [], []
    for start, stop in _prefix_tile_bounds(lib_sizes, tile_c):
        width = stop - start
        if col_ids is None:
            vc_t = jax.lax.slice(Vc, (0, start), (E_hi, stop))
            ids = start + jnp.arange(width, dtype=jnp.int32)
        else:
            ids = jax.lax.slice_in_dim(col_ids, start, stop).astype(jnp.int32)
            vc_t = jnp.take(Vc[:E_hi], ids, axis=1)
        ids_b = jnp.broadcast_to(ids[None, :], (Lq, width))
        invalid = (ids_b == row_ids) if exclude_self else None
        D = jnp.zeros((Lq, width), dist_dtype)
        dms = []
        for e in range(E_hi):
            D = _acc_sq(D, Vq[e], vc_t[e], dist_dtype)
            if e + 1 not in want:
                continue
            Dm = D.astype(jnp.float32)
            if invalid is not None:
                Dm = jnp.where(invalid, INF, Dm)
            dms.append(Dm)
        # ONE batched tile partial-sort + merge network per tile across
        # all bucket dimensions (top_k and the comparator network batch
        # over leading axes) — bit-identical to per-bucket merges but
        # with len(buckets) x fewer host-visible ops, which is what
        # keeps the per-tile constant below a from-scratch rebuild's.
        # Clipped boundary tiles can be narrower than k: the tile's own
        # top-k is then just its full sorted width, padded to k with
        # +inf sentinels inside merge_topk_sorted.
        Dsel = jnp.stack(dms)  # (nb, Lq, width)
        ids_nb = jnp.broadcast_to(ids_b, Dsel.shape)
        neg_d, pos = jax.lax.top_k(-Dsel, min(k, width))
        t_i = jnp.take_along_axis(ids_nb, pos, axis=-1)
        t_d = -neg_d
        if run_i is None:
            run_i, run_d = t_i, t_d  # first tile is >= k wide (validated)
        else:
            run_i, run_d = merge_topk_sorted(run_i, run_d, t_i, t_d, k)
        if stop in boundary:
            snaps_i.append(run_i)
            snaps_d.append(run_d)
    return jnp.stack(snaps_i), jnp.stack(snaps_d)


def knn_tables_prefix_rebuild(
    Vq: jax.Array,
    Vc: jax.Array,
    k: int,
    exclude_self: bool,
    buckets: tuple[int, ...],
    lib_sizes: tuple[int, ...],
    tile_c: int,
    dist_dtype=jnp.float32,
    col_ids: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Old-style per-size convergence tables: S INDEPENDENT sweeps, one per
    library size (what every path did before the prefix-snapshot builder).

    Same contract and bit-identical output to
    :func:`knn_tables_prefix_streaming`; kept as the engine base-class
    fallback and the A/B baseline of ``benchmarks/run.py significance``.
    """
    _check_prefix_args(  # validate the FULL size tuple, not just each Ls
        Vq.shape[1], Vc.shape[1], k, exclude_self, buckets, lib_sizes,
        Vq.shape[0], col_ids,
    )
    outs = [
        knn_tables_prefix_streaming(
            Vq, Vc, k, exclude_self, buckets, (Ls,), tile_c, dist_dtype,
            col_ids,
        )
        for Ls in lib_sizes
    ]
    return (
        jnp.concatenate([o[0] for o in outs]),
        jnp.concatenate([o[1] for o in outs]),
    )


def merge_topk_tree(idx_parts, dist_parts, k: int):
    """Device-side tree reduction of per-candidate-shard top-k tables to
    the global top-k (DESIGN.md SS14) — the jnp replacement for the host
    :func:`merge_shard_tables` oracle.

    idx_parts / dist_parts: sequences of (..., Lq, k_s) shard tables in
    ASCENDING ``col_offset`` order, indices GLOBAL candidate ids.  Folds
    contiguous pairs through :func:`merge_topk_sorted` (the PR-6 bitonic
    partial merge network), so the whole reduction is O(log S) merge
    levels of fixed comparator networks — no sorts, no host round-trip.

    Tie rule (proof sketch, expanded in DESIGN.md SS14): the network
    resolves distance ties running-before-new; pairs are always
    contiguous ascending shard blocks, and every id in a left block is
    strictly smaller than every id in a right block, so
    running-before-new IS the (distance, id) lexicographic key of
    lax.top_k / :func:`merge_shard_tables` — bit-for-bit, ties included.
    Each level merges to width ``min(k, w_a + w_b)`` rather than k so no
    +inf/id-2^31-1 padding sentinel is ever introduced: a sentinel
    carries an arrival rank, not a global id, and could otherwise
    outrank a later shard's genuine masked entry in the k == Lc
    exclude-self edge case.
    """
    parts = list(zip(list(idx_parts), list(dist_parts)))
    if not parts:
        raise ValueError("merge_topk_tree needs at least one shard table")
    while len(parts) > 1:
        nxt = []
        for a in range(0, len(parts) - 1, 2):
            (ia, da), (ib, db) = parts[a], parts[a + 1]
            kk = min(k, ia.shape[-1] + ib.shape[-1])
            nxt.append(merge_topk_sorted(ia, da, ib, db, kk))
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    idx, dist = parts[0]
    return idx[..., :k], dist[..., :k]


def merge_topk_collective(idx, dist, k: int, axis_name: str):
    """Collective shard-table merge INSIDE a shard_map (DESIGN.md SS14).

    idx / dist: this device's (..., Lq, k_s) candidate-shard top-k table
    (global ids via ``col_offset``), where device i along ``axis_name``
    holds the i-th contiguous candidate shard.  Returns the GLOBAL
    (..., Lq, k) top-k, replicated on every device — the paper-scale
    all-reduce that keeps the reduction on the interconnect instead of
    funnelling every shard through the host.

    Power-of-two axis: a ppermute butterfly — round r exchanges tables
    with partner ``i XOR 2^r``, each device keeps the merged top-k of
    its aligned 2^(r+1)-shard block, log2(W) rounds total, per-round
    traffic one table.  The XOR partner of an aligned block is always
    the adjacent block of the same size, so run/new assignment by block
    side preserves the ascending-contiguous invariant that makes
    running-before-new equal the (distance, id) tie rule (see
    :func:`merge_topk_tree`).  Other axis sizes: one all_gather + the
    same contiguous tree fold on every device.
    """
    W = jax.lax.psum(1, axis_name)
    if W == 1:
        return idx[..., :k], dist[..., :k]
    if W & (W - 1) == 0:
        me = jax.lax.axis_index(axis_name)
        step = 1
        while step < W:
            perm = [(i, i ^ step) for i in range(W)]
            oi = jax.lax.ppermute(idx, axis_name, perm)
            od = jax.lax.ppermute(dist, axis_name, perm)
            left = (me & step) == 0
            kk = min(k, idx.shape[-1] + oi.shape[-1])
            idx, dist = merge_topk_sorted(
                jnp.where(left, idx, oi),
                jnp.where(left, dist, od),
                jnp.where(left, oi, idx),
                jnp.where(left, od, dist),
                kk,
            )
            step *= 2
        return idx[..., :k], dist[..., :k]
    gi = jax.lax.all_gather(idx, axis_name)
    gd = jax.lax.all_gather(dist, axis_name)
    return merge_topk_tree(list(gi), list(gd), k)


def merge_shard_tables(
    idx_parts, dist_parts, k: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side reduction of per-candidate-shard top-k tables to the
    global top-k (DESIGN.md SS8/SS14).

    .. deprecated:: PR 10
        The pipeline now merges on-device (:func:`merge_topk_tree` /
        :func:`merge_topk_collective`); this np.lexsort path is kept as
        the ORACLE the device collective is bit-checked against (and for
        host-only tooling/tests).  New code should not call it on the
        hot path.

    idx_parts / dist_parts: sequences of (..., Lq, k_s) tables whose
    indices are GLOBAL candidate ids (each shard selected over its own
    candidate slice via ``col_offset``).  The merge key is
    (distance ascending, id ascending) — exactly lax.top_k's tie rule —
    so merging shard tables reproduces the unsharded streaming table
    bit-for-bit whenever k <= the global candidate count.
    """
    idx = np.concatenate([np.asarray(p) for p in idx_parts], axis=-1)
    dist = np.concatenate([np.asarray(p) for p in dist_parts], axis=-1)
    if k is None:
        k = min(np.asarray(p).shape[-1] for p in idx_parts)
    order = np.lexsort((idx, dist))[..., :k]
    return (
        np.take_along_axis(idx, order, axis=-1),
        np.take_along_axis(dist, order, axis=-1),
    )


def _matmul_sq_dists(dq: jax.Array, dc: jax.Array) -> jax.Array:
    """|q - c|^2 = |q|^2 + |c|^2 - 2 q.c — the MXU form."""
    D = (
        jnp.sum(dq * dq, axis=0)[:, None]
        + jnp.sum(dc * dc, axis=0)[None, :]
        - 2.0 * (dq.T @ dc)
    )
    return jnp.maximum(D, 0.0)


def knn_table_single_E(
    Vq: jax.Array,
    Vc: jax.Array,
    E: int,
    k: int,
    exclude_self: bool,
    *,
    matmul_form: bool = False,
    candidate_mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Single-E kNN table, computed from scratch (cppEDM / Alg. 3 semantics).

    Used by the naive baseline and as an oracle for the Pallas kernel.

    matmul_form=False accumulates lag terms sequentially — bit-identical to
    the cumulative scan in knn_tables_dense, so naive vs improved equivalence
    tests are exact.  matmul_form=True uses |q|^2 + |c|^2 - 2 q.c, the
    MXU-friendly form the Pallas kernel implements.
    candidate_mask: optional (Lc,) bool — library subsampling for the CCM
    convergence diagnostic; excluded candidates get +inf distance.
    """
    dq = Vq[:E]  # (E, Lq)
    dc = Vc[:E]
    if matmul_form:
        D = _matmul_sq_dists(dq, dc)
    else:
        D = jnp.zeros((Vq.shape[1], Vc.shape[1]), jnp.float32)
        for e in range(E):  # sequential, same fp order as the scan
            D = _acc_sq(D, dq[e], dc[e], jnp.float32)
    if exclude_self:
        D = jnp.where(jnp.eye(Vq.shape[1], dtype=bool), INF, D)
    if candidate_mask is not None:
        D = jnp.where(candidate_mask[None, :], D, INF)
    neg_d, idx = jax.lax.top_k(-D, k)
    return idx.astype(jnp.int32), -neg_d


def tables_with_weights(
    indices: jax.Array, sq_dists: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Convert stacked per-E tables to (indices, normalized weights).

    For table e (embedding dimension E = e+1), only the first E+1 neighbours
    carry weight; the padding lets all E share one array shape.
    """
    E_max = indices.shape[0]
    k_valid = jnp.arange(1, E_max + 1)[:, None, None] + 1  # (E_max, 1, 1)
    w = simplex_weights(sq_dists, k_valid)
    return indices, w


def tables_with_weights_bucketed(
    indices: jax.Array, sq_dists: jax.Array, buckets: tuple[int, ...]
) -> tuple[jax.Array, jax.Array]:
    """tables_with_weights for a bucketed table stack (DESIGN.md SS3).

    Row b is the table for embedding dimension buckets[b], so its valid
    neighbour count is buckets[b] + 1 (instead of the dense row index + 2).
    """
    k_valid = jnp.asarray(buckets, jnp.int32)[:, None, None] + 1
    return indices, simplex_weights(sq_dists, k_valid)


def simplex_forecast(idx: jax.Array, w: jax.Array, fut_c: jax.Array) -> jax.Array:
    """lookup (paper Alg. 5): weighted average of candidate futures.

    idx, w: (..., Lq, k); fut_c: (Lc,) candidate future values.
    Returns predictions (..., Lq).
    """
    return jnp.sum(w * fut_c[idx], axis=-1)
