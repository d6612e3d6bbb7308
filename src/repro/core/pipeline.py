"""Distributed causal-inference pipeline — the paper's system layer on TPU.

Replaces the MPI master-worker (paper SSIII-C) with SPMD shard_map over
library-series blocks on the FLAT device grid (pod x data x model treated
as one worker axis, matching the paper's 512 flat workers):

  phase 1 (simplex projection): series sharded across workers, optE
    gathered to host (N int32 — the paper's single broadcast);
  phase 2 (CCM): double-buffered loop over row CHUNKS (chunk = workers x
    lib_block); each chunk is one or more jit'd shard_map calls with zero
    internal collectives.  Targets are grouped by distinct optE so each
    chunk builds kNN tables only for the bucket set (DESIGN.md SS3).
    With cfg.target_tile > 0 phase 2
    gains a SECOND tiling dimension (DESIGN.md SS7): kNN tables are
    built once per chunk (they depend only on the library rows) and the
    targets stream through in column tiles — only the live (tile, Lp)
    slice of ts_fut is resident per device, killing the full (N, Lp)
    replication, and rho is emitted as (row-chunk x col-tile) blocks.
    Completed blocks stream through a ChunkStreamer (runtime/stream.py):
    the next dispatch is queued while older blocks' device->host copies
    and TileWriter writes (sequential block writes — the BeeOND design
    point) drain, so the streaming store is off the critical path.  The
    writer doubles as the RESUME manifest; when it is active no dense
    (N, N) host array is ever allocated — the causal map is assembled
    into a memmap, so phase 2's own host working set is O(chunk x tile)
    on top of the O(N x L) inputs (ts, ts_fut) it reads.

Fault tolerance: kill the process at any point; rerun resumes at the first
uncovered row, on any mesh size (elastic — coverage is tracked per row).
Self-scheduling is unnecessary: after the mpEDM algorithmic improvement all
per-series tasks cost the same FLOPs (DESIGN.md SS2), so static balanced
decomposition is optimal.
"""
from __future__ import annotations

import functools
from time import perf_counter as _perf
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import engine as engines
from repro.core import ccm, knn, simplex
from repro.core.types import CausalMap, EDMConfig
from repro.data.store import TileWriter
from repro.runtime import telemetry
from repro.runtime.stream import ChunkStreamer


def _flat(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def default_mesh():
    """The FLAT all-local-devices worker mesh every driver defaults to
    (paper's 512 flat workers); shared by phase 1/2 and the significance
    subsystem so one process always decomposes work the same way."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("workers",))


def make_simplex_fn(mesh, cfg: EDMConfig):
    """(chunk, L) sharded on rows -> (rhos (chunk, E_max), optE (chunk,))."""
    axes = _flat(mesh)

    def local(ts_rows):
        return simplex.simplex_batch(ts_rows, cfg)

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axes, None),),
            out_specs=(P(axes, None), P(axes)),
            check_vma=False,
        )
    )


def make_ccm_chunk_fn_bucketed(mesh, cfg: EDMConfig, plan: "ccm.BucketPlan"):
    """Bucketed variant: (lib_rows sharded, ts_fut_sorted repl) -> rho rows
    (chunk, N) sharded, columns in plan-sorted target order."""
    axes = _flat(mesh)

    def local(lib_rows, ts_fut_sorted):
        return ccm.ccm_block_bucketed(lib_rows, ts_fut_sorted, cfg, plan)

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axes, None), P(None, None)),
            out_specs=P(axes, None),
            check_vma=False,
        )
    )


# --------------------------------------------- tiled phase 2 (DESIGN.md SS7)
def make_ccm_tables_fn_bucketed(mesh, cfg: EDMConfig, plan: "ccm.BucketPlan"):
    """Bucketed tables variant: (chunk, L) sharded -> (idx, w) sharded."""
    axes = _flat(mesh)
    tspec = P(axes, None, None, None)
    return jax.jit(
        shard_map(
            lambda rows: ccm._block_tables_bucketed(rows, cfg, plan),
            mesh=mesh,
            in_specs=(P(axes, None),),
            out_specs=(tspec, tspec),
            check_vma=False,
        )
    )


def make_ccm_tile_fn_bucketed(mesh, cfg: EDMConfig):
    """Returns seg_plan -> tile fn (memoized: distinct seg_plans are few —
    interior tiles of a bucket share one; see ccm.make_tile_plans)."""
    axes = _flat(mesh)
    tspec = P(axes, None, None, None)

    @functools.lru_cache(maxsize=None)
    def for_plan(seg_plan):
        def local(idx, w, fut_tile):
            return ccm._block_tile_bucketed(idx, w, fut_tile, cfg, seg_plan)

        return jax.jit(
            shard_map(
                local,
                mesh=mesh,
                in_specs=(tspec, tspec, P(None, None)),
                out_specs=P(axes, None),
                check_vma=False,
            )
        )

    return for_plan


# ---------------------------------- library-sharded kNN (DESIGN SS8, SS14)
def make_knn_shard_fn(mesh, cfg: EDMConfig, k: int, exclude_self: bool,
                      tile_c: int):
    """(Vq repl, Vc cols sharded, [lo, hi) bounds sharded) -> per-shard
    top-k tables stacked on a leading shard axis, (W, E_max, Lq, k) each.

    Every device runs the STREAMING builder over its own candidate shard
    with global column ids (``col_offset``/``col_hi``), so per-device
    memory is O(E_max x Lc/W + Lq x (k + tile)) and no device ever sees
    the full candidate axis — the paper-style multi-node library building
    block.  Zero collectives — this is the PER-SHARD half used by tests
    and the host-merge oracle; the production path is
    :func:`make_knn_shard_merge_fn`, which adds the on-device collective
    reduction (DESIGN.md SS14).
    """
    axes = _flat(mesh)

    def local(Vq, Vc_shard, bounds):
        idx, d = knn.knn_tables_all_E_streaming(
            Vq, Vc_shard, k, exclude_self=exclude_self, tile_c=tile_c,
            dist_dtype=jnp.dtype(cfg.dist_dtype),
            col_offset=bounds[0, 0], col_hi=bounds[0, 1],
        )
        return idx[None], d[None]

    tspec = P(axes, None, None, None)
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(None, None), P(None, axes), P(axes, None)),
            out_specs=(tspec, tspec),
            check_vma=False,
        )
    )


def make_knn_shard_merge_fn(mesh, cfg: EDMConfig, k: int, k_s: int,
                            exclude_self: bool, tile_c: int):
    """(Vq repl, Vc cols sharded, [lo, hi) bounds sharded) -> GLOBAL
    (E_max, Lq, k) top-k tables, replicated — per-shard streaming build
    followed by :func:`repro.core.knn.merge_topk_collective` (DESIGN.md
    SS14), all inside one shard_map so the reduction runs on the device
    interconnect (ppermute butterfly / all_gather tree) and the tables
    never round-trip through the host.
    """
    axes = _flat(mesh)

    def local(Vq, Vc_shard, bounds):
        idx, d = knn.knn_tables_all_E_streaming(
            Vq, Vc_shard, k_s, exclude_self=exclude_self, tile_c=tile_c,
            dist_dtype=jnp.dtype(cfg.dist_dtype),
            col_offset=bounds[0, 0], col_hi=bounds[0, 1],
        )
        return knn.merge_topk_collective(idx, d, k, axes[0])

    rspec = P(None, None, None)
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(None, None), P(None, axes), P(axes, None)),
            out_specs=(rspec, rspec),
            check_vma=False,
        )
    )


def _shard_bounds(Lc: int, W: int) -> tuple[int, np.ndarray]:
    """Contiguous candidate-shard geometry: (slab width, (W, 2) [lo, hi))."""
    shard = -(-Lc // W)
    lo = np.arange(W, dtype=np.int32) * shard
    return shard, np.stack([lo, np.minimum(lo + shard, Lc)], axis=1)


def knn_tables_library_sharded(
    Vq, Vc, k: int, cfg: EDMConfig, *, exclude_self: bool, mesh=None
) -> tuple[jax.Array, jax.Array]:
    """kNN tables with the CANDIDATE (library) axis sharded across devices.

    Each device selects top-k over its candidate shard (streaming
    builders, global column ids), then the shard tables are reduced
    ON-DEVICE by the collective bitonic merge (DESIGN.md SS14) whose
    (distance, id) tie rule matches lax.top_k — the result is
    bit-identical to the single-device streaming table whenever k <= Lc.
    Returns DEVICE (idx, sq_dists), each (E_max, Lq, k), replicated
    across the mesh: callers feeding downstream device code (CCM
    lookups, weights) pay no host round-trip; host consumers can
    np.asarray at their own boundary.
    """
    if mesh is None:
        mesh = default_mesh()
    W = mesh.size
    Lc = Vc.shape[1]
    if k > Lc:
        raise ValueError(f"k={k} exceeds candidate count Lc={Lc}")
    shard, bounds = _shard_bounds(Lc, W)
    Vc_p = jnp.pad(jnp.asarray(Vc), ((0, 0), (0, shard * W - Lc)))
    tile_c = knn.resolve_stream_tile(shard, cfg, profile="host")
    # A shard narrower than k still contributes all its candidates; the
    # global top-k can draw at most min(k, shard) entries from one shard.
    k_s = min(k, shard)
    fn = make_knn_shard_merge_fn(mesh, cfg, k, k_s, exclude_self, tile_c)
    return fn(jnp.asarray(Vq), Vc_p, jnp.asarray(bounds))


def knn_tables_library_sharded_sim(
    Vq, Vc, k: int, cfg: EDMConfig, *, exclude_self: bool, shards: int
) -> tuple[jax.Array, jax.Array]:
    """SIMULATED library sharding on however few devices are present:
    builds the ``shards`` per-shard streaming tables sequentially (same
    ``col_offset`` geometry as the real mesh path) and reduces them with
    the device-side tree merge (DESIGN.md SS14).  Exercises the exact
    collective merge arithmetic — bit-identical to both the unsharded
    table and the real multi-device path — so scaling benchmarks and CI
    can sweep shard counts beyond the local device count.
    """
    Lc = Vc.shape[1]
    if k > Lc:
        raise ValueError(f"k={k} exceeds candidate count Lc={Lc}")
    shard, bounds = _shard_bounds(Lc, shards)
    Vc_p = jnp.pad(jnp.asarray(Vc), ((0, 0), (0, shard * shards - Lc)))
    tile_c = knn.resolve_stream_tile(shard, cfg, profile="host")
    k_s = min(k, shard)
    idx_parts, d_parts = [], []
    for s in range(shards):
        lo, hi = int(bounds[s, 0]), int(bounds[s, 1])
        idx, d = knn.knn_tables_all_E_streaming(
            jnp.asarray(Vq), Vc_p[:, s * shard : (s + 1) * shard], k_s,
            exclude_self=exclude_self, tile_c=tile_c,
            dist_dtype=jnp.dtype(cfg.dist_dtype),
            col_offset=lo, col_hi=hi,
        )
        idx_parts.append(idx)
        d_parts.append(d)
    return knn.merge_topk_tree(idx_parts, d_parts, k)


def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    if a.shape[0] == rows:
        return a
    pad = np.zeros((rows - a.shape[0],) + a.shape[1:], a.dtype)
    return np.concatenate([a, pad], axis=0)


def _dispatch(unit: dict, row0: int, fn, *args, stage: str = "phase2",
              **attrs):
    """``fn(*args)``, a jitted call, under a ``<stage>/dispatch`` span.  While
    the unit's first chunk runs, its time adds to the unit's
    ``first_dispatch_s``: trace, lower and compile, or cache load."""
    with telemetry.span(stage, "dispatch", row0=row0, **attrs):
        t0 = _perf()
        out = fn(*args)
        if unit["chunks"] == 0:
            unit["first_dispatch_s"] += _perf() - t0
    return out


def _phase2_untiled(
    ts, ts_fut, optE, cfg, mesh, chunk, chunk_plan, writer, rho, progress,
    unit, on_chunk=None,
):
    """Single-tile phase 2: full-width (chunk, N) row blocks, their
    columns un-sorted from bucket order on the host."""
    N = ts.shape[0]
    t0 = _perf()
    plan, order = ccm.make_bucket_plan(optE)
    inv = np.argsort(order)
    chunk_fn = make_ccm_chunk_fn_bucketed(mesh, cfg, plan)
    ts_fut_j = jnp.asarray(ts_fut[order])
    unit["prep_s"] = _perf() - t0
    unit["futures_bytes"] = int(ts_fut_j.nbytes)

    def drain(tag, rho_rows):
        row0, valid = tag
        with telemetry.span("phase2", "unsort", row0=row0):
            rho_rows = rho_rows[:, inv]
        rows_np = rho_rows[:valid]
        if writer is not None:
            writer.write_block(row0, rows_np)
        else:
            rho[row0 : row0 + valid] = rows_np
        if progress:
            print(f"ccm rows {row0}..{row0 + valid} / {N}")

    with ChunkStreamer(drain, depth=cfg.stream_depth,
                       stage="phase2") as streamer:
        for row0, valid in chunk_plan:
            if on_chunk is not None:
                on_chunk(row0)
            with telemetry.span("phase2", "chunk", row0=row0,
                                rows=valid, tiled=False) as t:
                with telemetry.span("phase2", "device_put", row0=row0):
                    rows = jnp.asarray(_pad_rows(ts[row0 : row0 + chunk], chunk))
                dev = _dispatch(unit, row0, chunk_fn, rows, ts_fut_j)
                t["chunk_rows"] = chunk
            unit["chunks"] += 1
            unit["rows"] += valid
            streamer.submit((row0, valid), dev)


def _phase2_tiled(
    ts, ts_fut, optE, cfg, mesh, chunk, chunk_plan, writer, rho, progress,
    unit, on_chunk=None,
):
    """2D (row-chunk x col-tile) phase 2: tables once per chunk, targets in
    column tiles of cfg.target_tile, blocks streamed with
    (row0, col0, valid) tags."""
    N = ts.shape[0]
    T = cfg.target_tile
    t0 = _perf()
    plan, order = ccm.make_bucket_plan(optE)
    tables_fn = make_ccm_tables_fn_bucketed(mesh, cfg, plan)
    tile_fn_for = make_ccm_tile_fn_bucketed(mesh, cfg)
    tile_plans = ccm.make_tile_plans(plan, T)
    if writer is not None:
        writer.ensure_col_order(order)
    unit["prep_s"] = _perf() - t0
    unit["futures_bytes"] = 0  # tiles go up per chunk, counted below

    def drain(tag, block):
        row0, col0, valid = tag
        blk = block[:valid]
        last_tile = col0 + blk.shape[1] >= N
        if writer is not None:
            # On-disk (col_order) layout.  The manifest commit is batched
            # to once per row chunk (drains are ordered, so when the last
            # tile lands every earlier tile of the chunk is durable).
            writer.write_tile(row0, col0, blk, commit=last_tile)
        else:
            rho[row0 : row0 + valid][:, order[col0 : col0 + blk.shape[1]]] = blk
        if progress and last_tile:
            print(f"ccm rows {row0}..{row0 + valid} / {N} (tiles of {T})")

    with ChunkStreamer(drain, depth=cfg.stream_depth,
                       stage="phase2") as streamer:
        for row0, valid in chunk_plan:
            if on_chunk is not None:
                on_chunk(row0)
            with telemetry.span("phase2", "chunk", row0=row0, rows=valid,
                                tiled=True, tile=T,
                                n_tiles=len(tile_plans)) as t:
                with telemetry.span("phase2", "device_put", row0=row0):
                    rows = jnp.asarray(_pad_rows(ts[row0 : row0 + chunk], chunk))
                idx, w = _dispatch(unit, row0, tables_fn, rows)  # once per chunk
                for c0, seg_plan in tile_plans:
                    c1 = min(c0 + T, N)
                    # per-tile slice only — a gather through `order`, so
                    # NO second (N, Lp) sorted host copy
                    fut_tile = jnp.asarray(ts_fut[order[c0:c1]])
                    unit["futures_bytes"] += int(fut_tile.nbytes)
                    block = _dispatch(unit, row0, tile_fn_for(seg_plan),
                                      idx, w, fut_tile)
                    streamer.submit((row0, c0, valid), block)
                t["chunk_rows"] = chunk
            unit["chunks"] += 1
            unit["rows"] += valid
    if writer is not None:
        writer.commit()  # defensive: deferred entries are never left behind


def run_phase1(
    ts: np.ndarray, cfg: EDMConfig, mesh=None, on_chunk=None
) -> tuple[np.ndarray, np.ndarray]:
    """Phase 1 (simplex projection) alone: (simplex_rhos (N, E_max),
    optE (N,) int32).  Fleet workers call this under the ``phase1`` work
    unit; the result is the one whole-run broadcast the paper's design
    allows (SSIII-C), persisted to the shared store for every other
    worker to load.  on_chunk(row0) fires before each chunk dispatch —
    fleet workers renew their unit lease there (the whole-run phase-1
    unit can outlive a TTL on cold compile caches)."""
    if mesh is None:
        mesh = default_mesh()
    N = ts.shape[0]
    chunk = mesh.size * cfg.lib_block
    simplex_fn = make_simplex_fn(mesh, cfg)
    rhos_parts, optE_parts = [], []
    cache0 = telemetry.compile_cache_entries()
    for row0 in range(0, N, chunk):
        if on_chunk is not None:
            on_chunk(row0)
        with telemetry.span("phase1", "chunk", row0=row0,
                            chunk_rows=chunk) as t:
            with telemetry.span("phase1", "device_put", row0=row0):
                rows = jnp.asarray(_pad_rows(ts[row0 : row0 + chunk], chunk))
            rhos_c, optE_c = simplex_fn(rows)
            t0 = _perf()
            rhos_parts.append(np.asarray(rhos_c))
            optE_parts.append(np.asarray(optE_c))
            t["gather_s"] = _perf() - t0
    telemetry.emit_compile_cache("phase1", cache0)
    simplex_rhos = np.concatenate(rhos_parts)[:N]
    optE = np.concatenate(optE_parts)[:N].astype(np.int32)
    return simplex_rhos, optE


def run_phase2_chunks(
    ts: np.ndarray,
    ts_fut: np.ndarray,
    optE: np.ndarray,
    cfg: EDMConfig,
    mesh,
    chunk_plan: list[tuple[int, int]],
    writer: Optional[TileWriter] = None,
    rho: Optional[np.ndarray] = None,
    progress: bool = False,
    on_chunk=None,
) -> None:
    """Phase 2 over an EXPLICIT (row0, nrows) chunk plan — the claimable
    compute unit of the work queue (DESIGN.md SS10).

    Values are geometry-independent (kNN tables are per library row,
    targets per column), so any partition of the rows across calls —
    or across worker processes writing through writer_id-sharded
    TileWriters — produces bit-identical blocks.  ``writer`` streams
    blocks to the store; with ``rho`` they land in a host map instead.
    ``on_chunk(row0)`` fires before each chunk dispatch — fleet workers
    renew their unit lease there, same contract as :func:`run_phase1`.

    The call is one ``phase2/unit`` span with the unit's set-up costs:
    ``prep_s`` (bucket plan, futures gathered, their upload started),
    ``futures_bytes``, ``first_dispatch_s`` (the first chunk's jitted
    calls), ``chunks`` and ``rows``; and, where the engine runs a lookup
    kernel, ``lookup_sublanes``: the sublanes of the target tile its
    full ``target_block`` lookups add per neighbour step.
    """
    chunk = mesh.size * cfg.lib_block
    phase2 = _phase2_tiled if cfg.target_tile else _phase2_untiled
    cache0 = telemetry.compile_cache_entries()
    sublanes = engines.get_engine(cfg.engine).lookup_sublanes(
        min(cfg.target_block, cfg.target_tile or ts.shape[0]),
        ts_fut.shape[1],
    )
    with telemetry.span("phase2", "unit") as unit:
        unit.update(chunks=0, rows=0, first_dispatch_s=0.0)
        if sublanes is not None:
            unit["lookup_sublanes"] = sublanes
        phase2(ts, ts_fut, optE, cfg, mesh, chunk, chunk_plan, writer, rho,
               progress, unit, on_chunk=on_chunk)
    telemetry.emit_compile_cache("phase2", cache0)


def run_causal_inference(
    ts: np.ndarray,
    cfg: EDMConfig,
    mesh=None,
    out_dir: Optional[str] = None,
    progress: bool = False,
) -> CausalMap:
    """Full pipeline on the given mesh (defaults to all local devices).

    With ``out_dir`` set, phase-2 blocks stream to a :class:`TileWriter`
    and the returned causal map is a disk-backed memmap
    (<out_dir>/causal_map/data.npy) — no dense (N, N) host array is
    allocated at any point.  The store is fingerprint-stamped on first
    write and checked on every resume: tiles computed from different
    data or a different config can never silently mix (DESIGN.md SS12).
    """
    if mesh is None:
        mesh = default_mesh()
    N, L = ts.shape
    chunk = mesh.size * cfg.lib_block

    if out_dir is not None:
        from repro.runtime import integrity

        integrity.stamp_fingerprint(
            out_dir, integrity.fingerprint_of(np.asarray(ts, np.float32), cfg)
        )

    # ---- phase 1: simplex projection -> optE --------------------------
    simplex_rhos, optE = run_phase1(ts, cfg, mesh)

    # ---- phase 2: all-to-all CCM, streamed (row-chunk x col-tile) ------
    ts_fut = np.asarray(ccm.all_futures(jnp.asarray(ts), cfg))
    writer = TileWriter(out_dir, N) if out_dir else None
    # The dense host map exists ONLY when there is no streaming store;
    # with --out the blocks go straight to disk (O(chunk x tile) host).
    rho = None if writer is not None else np.zeros((N, N), np.float32)

    if writer is not None:
        chunk_plan = writer.chunk_plan(chunk)
    else:
        chunk_plan = [(r, min(chunk, N - r)) for r in range(0, N, chunk)]

    run_phase2_chunks(
        ts, ts_fut, optE, cfg, mesh, chunk_plan, writer, rho, progress
    )

    if writer is not None:
        with telemetry.span("assemble", "causal_map", N=N):
            rho = writer.assemble(
                mmap_path=writer.dir / "causal_map" / "data.npy"
            )
        # In-process finalize path: record the run summary into the
        # history store (no-op with telemetry off and EDM_HISTORY unset;
        # a later significance finalize REPLACES it — same run identity).
        from repro.runtime import history

        history.record_run(out_dir)
    return CausalMap(rho=rho, optE=optE, simplex_rho=simplex_rhos)
