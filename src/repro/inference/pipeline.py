"""Streaming significance driver: rho maps -> validated causal graphs.

Runs the two statistical stages of DESIGN.md SS9 over the same
(row-chunk x col-tile) decomposition as phase 2, sharing its meshes,
ChunkStreamer, and TileWriter store:

  * CONVERGENCE — per row chunk, ONE prefix-snapshot table build yields
    bucketed kNN tables for every library size (nested random prefixes
    of the seeded subsampling permutation); per column tile the
    rho-vs-library-size curves reduce on device to the drho and
    monotonic-trend maps.
  * SURROGATE NULLS — per row chunk the full-library tables are rebuilt
    once (exactly phase 2's tables, so the null matches the observed
    statistic); per column tile every target contributes m surrogate
    futures batched along the target axis, and the per-pair empirical
    p-value (1 + #{null >= obs}) / (m + 1) is computed on device.
  * FDR + ASSEMBLY — empirical p-values take only m+1 distinct values,
    so the Benjamini–Hochberg threshold is computed EXACTLY from
    streamed per-value counts (no sort, no dense p array), and the
    significance-masked edge list is assembled row-streamed from the
    (memmapped) maps.

With ``out_dir`` set, blocks stream through TileWriters into the new
store artifacts ``rho_conv/`` (drho; trend.npy rides in the same dir),
``pvals/``, and ``edges/`` — no dense (N, N) host allocation beyond the
existing memmap assembly, and killed runs RESUME at the first chunk any
artifact is missing.
"""
from __future__ import annotations

import functools
from time import perf_counter as _perf
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import ccm
from repro.core.pipeline import (
    _dispatch,
    _flat,
    _pad_rows,
    default_mesh,
    make_ccm_tables_fn_bucketed,
)
from repro.core.types import EDMConfig
from repro.data import store
from repro.data.store import TileWriter
from repro.inference import convergence, significance, surrogates
from repro.inference.types import SignificanceConfig, SignificanceResult
from repro.runtime import history, telemetry
from repro.runtime.stream import ChunkStreamer


# ------------------------------------------------- shard_map'd chunk/tile fns
def make_conv_tables_fn(mesh, cfg: EDMConfig, plan, lib_sizes):
    """(chunk, L) sharded + subsampling permutation repl -> prefix tables
    (idx, w) each (chunk, S, nb, Lp, k) sharded on rows."""
    axes = _flat(mesh)
    tspec = P(axes, None, None, None, None)

    def local(rows, col_ids):
        return convergence.conv_block_tables(rows, cfg, plan, lib_sizes, col_ids)

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axes, None), P(None)),
            out_specs=(tspec, tspec),
            check_vma=False,
        )
    )


def make_conv_tile_fn(mesh, cfg: EDMConfig):
    """seg_plan -> tile fn (memoized like make_ccm_tile_fn_bucketed):
    (prefix tables sharded; fut_tile repl) -> stacked (2, chunk, t)
    [drho; trend] sharded on rows."""
    axes = _flat(mesh)
    tspec = P(axes, None, None, None, None)

    @functools.lru_cache(maxsize=None)
    def for_plan(seg_plan):
        def local(idx, w, fut_tile):
            drho, trend = convergence.conv_block_tile(
                idx, w, fut_tile, cfg, seg_plan
            )
            return jnp.stack([drho, trend])

        return jax.jit(
            shard_map(
                local,
                mesh=mesh,
                in_specs=(tspec, tspec, P(None, None)),
                out_specs=P(None, axes, None),
                check_vma=False,
            )
        )

    return for_plan


def make_null_tile_fn(mesh, cfg: EDMConfig, m: int):
    """seg_plan -> tile fn: (full-library tables sharded; surrogate
    futures repl; observed rho block sharded) -> pvals (chunk, t)."""
    axes = _flat(mesh)
    tspec = P(axes, None, None, None)

    @functools.lru_cache(maxsize=None)
    def for_plan(seg_plan):
        seg_plan_m = tuple((b, cnt * m) for b, cnt in seg_plan)

        def local(idx, w, fut_surr, rho_obs):
            return significance.null_block_pvals(
                idx, w, fut_surr, rho_obs, cfg, seg_plan_m, m
            )

        return jax.jit(
            shard_map(
                local,
                mesh=mesh,
                in_specs=(tspec, tspec, P(None, None), P(axes, None)),
                out_specs=P(axes, None),
                check_vma=False,
            )
        )

    return for_plan


# ------------------------------------------------------------- null tile
#: share of the device's free memory the sig stage's tile may plan for;
#: the rest is left to the allocator's fragmentation and XLA's scratch.
HBM_PLAN_SHARE = 0.8


def sig_tile_bytes(t: int, *, m: int, L: int, Lp: int, rows: int, k: int,
                   n_buckets: int, n_sizes: int, cfg: EDMConfig) -> int:
    """Device bytes one (row-chunk x column-tile) step of the sig stage
    holds at its peak, for a tile of ``t`` targets on one device.

    Per target: its m surrogate futures (m x Lp float32), held by up to
    ``stream_depth`` dispatched tiles and copied once more where the
    lookup pads a bucket segment to whole ``target_block`` blocks; the
    FFT's working set for its m surrogates (random bits and phases, the
    complex spectrum and its rotation, the irfft output, nf = L // 2 + 1
    bins); the raw series uploaded and the convergence stage's futures;
    the observed, null and curve blocks of the chunk's ``rows``.  Per
    tile: the chunk's kNN tables (full library and every library size)
    and three (rows, target_block, Lp) prediction blocks of the lookups
    and Pearson's centred copies.
    """
    f32, c64 = 4, 8
    nf = L // 2 + 1
    surr = m * Lp * f32
    fft = m * (2 * nf * f32 + 2 * nf * c64 + L * f32)
    per_target = ((cfg.stream_depth + 1) * surr + fft + (L + Lp) * f32
                  + rows * (2 * m + 1 + n_sizes) * f32)
    tables = rows * (1 + n_sizes) * n_buckets * Lp * k * (4 + 4)
    preds = 3 * rows * cfg.target_block * Lp * f32
    return t * per_target + tables + preds


def sig_null_tile(budget: int | None, cap: int, **shape) -> int:
    """The largest tile (<= ``cap``) whose :func:`sig_tile_bytes` fit in
    ``budget`` bytes; ``cap`` where no budget is known (a backend
    without memory counters), at least 1."""
    if budget is None:
        return cap
    fixed = sig_tile_bytes(0, **shape)
    per_target = sig_tile_bytes(1, **shape) - fixed
    return int(max(1, min(cap, (budget - fixed) // per_target)))


def _device_budget(mesh) -> int | None:
    """Bytes the sig stage may plan for on the fullest device of
    ``mesh``: :data:`HBM_PLAN_SHARE` of ``bytes_limit`` less what is in
    use there now; None where the backend gives no ``bytes_limit``."""
    free = []
    for d in mesh.devices.flat:
        stats = d.memory_stats() or {}
        if "bytes_limit" not in stats:
            return None
        free.append(int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0)))
    return int(HBM_PLAN_SHARE * min(free))


# ----------------------------------------------------- chunk-level compute
class SignificanceChunkRunner:
    """Compiled per-chunk significance compute — convergence tables and
    tile reductions, surrogate-null batches — decoupled from chunk
    PLANNING and finalization, so a fleet worker (DESIGN.md SS10) can
    run exactly the row chunks it claims from the work queue while the
    single-process driver runs them all.

    Everything that must agree across workers for bit-identity is
    derived here from shared inputs only: the bucket plan and column
    order from phase-1 optE, the subsampling permutation and surrogate
    keys from sig.seed (per-target fold_in — independent of chunk/tile
    geometry).  ``run`` then computes any subset of row chunks and
    drains blocks through the caller's sink.

    The column tile ``T`` of both stages comes from device memory and
    shapes (:func:`sig_null_tile`): the largest tile whose surrogate
    batch, FFT working set and lookups fit the device's free memory,
    capped by ``cfg.target_tile`` when set and by N.  Off a chip (no
    ``bytes_limit``) it is ``cfg.target_tile or N``.  Values do not
    depend on it.
    """

    def __init__(self, ts: np.ndarray, optE: np.ndarray, cfg: EDMConfig,
                 sig: SignificanceConfig, mesh=None):
        t0 = _perf()
        if mesh is None:
            mesh = default_mesh()
        self.mesh, self.cfg, self.sig = mesh, cfg, sig
        N, L = ts.shape
        self.N = N
        Lp = cfg.n_points(L)
        self.do_conv = bool(sig.lib_sizes)
        self.do_null = sig.n_surrogates > 0
        if self.do_conv and sig.lib_sizes[-1] > Lp:
            raise ValueError(
                f"lib_sizes[-1]={sig.lib_sizes[-1]} exceeds the {Lp} "
                f"embeddable library points of length-{L} series "
                f"(E_max={cfg.E_max}, tau={cfg.tau}, Tp={cfg.Tp})"
            )
        self.m = sig.n_surrogates
        self.chunk = mesh.size * cfg.lib_block
        self.ts = ts
        optE = np.asarray(optE, np.int32)
        self.plan, self.order = ccm.make_bucket_plan(optE)
        self.ts_fut = np.asarray(ccm.all_futures(jnp.asarray(ts), cfg))

        key = jax.random.PRNGKey(sig.seed)
        perm_key, self.surr_key = jax.random.split(key)
        self.col_ids = convergence.subsample_permutation(perm_key, Lp)
        # Set-up the unit span reports: bucket plan, futures, permutation.
        self._prep_s = _perf() - t0

        self.T = sig_null_tile(
            _device_budget(mesh), cfg.target_tile or N, m=self.m, L=L, Lp=Lp,
            rows=cfg.lib_block, k=ccm._bucket_k(cfg, self.plan),
            n_buckets=len(self.plan.buckets),
            n_sizes=len(sig.lib_sizes), cfg=cfg,
        )
        self.tile_plans = ccm.make_tile_plans(self.plan, self.T)
        self.surrogate_bytes = self.T * self.m * Lp * 4

        self.conv_tables_fn = self.conv_tile_for = None
        self.full_tables_fn = self.null_tile_for = None
        if self.do_conv:
            self.conv_tables_fn = make_conv_tables_fn(
                mesh, cfg, self.plan, sig.lib_sizes
            )
            self.conv_tile_for = make_conv_tile_fn(mesh, cfg)
        if self.do_null:
            self.full_tables_fn = make_ccm_tables_fn_bucketed(
                mesh, cfg, self.plan
            )
            self.null_tile_for = make_null_tile_fn(mesh, cfg, self.m)

    def run(self, plan_chunks, rho, drain, on_chunk=None) -> None:
        """Compute the given (row0, valid) chunks, draining ("conv"|
        "pval", row0, c0, valid)-tagged blocks in submission order.

        rho: the observed causal map (memmap fine; only read when the
        null stage is active).  on_chunk(row0) fires before each chunk's
        dispatch — fleet workers renew their unit lease there.

        The call is one ``sig/unit`` span: ``prep_s`` (the runner's
        set-up, reported by the first call only), ``first_dispatch_s``
        (the first chunk's jitted calls), ``null_tile``,
        ``surrogate_bytes`` (one tile's surrogate batch), ``chunks`` and
        ``rows``.
        """
        prep_s, self._prep_s = self._prep_s, 0.0
        cache0 = telemetry.compile_cache_entries()
        with telemetry.span("sig", "unit") as unit:
            unit.update(prep_s=prep_s, first_dispatch_s=0.0,
                        null_tile=self.T,
                        surrogate_bytes=self.surrogate_bytes, chunks=0,
                        rows=0)
            self._run(plan_chunks, rho, drain, on_chunk, unit)
        telemetry.emit_compile_cache("sig", cache0)

    def _run(self, plan_chunks, rho, drain, on_chunk, unit) -> None:
        N, T, m, sig, cfg = self.N, self.T, self.m, self.sig, self.cfg
        order, ts, ts_fut = self.order, self.ts, self.ts_fut

        def dispatch(row0, what, fn, *args):
            return _dispatch(unit, row0, fn, *args, stage="sig", what=what)

        with ChunkStreamer(drain, depth=cfg.stream_depth,
                           stage="sig") as streamer:
            for row0, valid in plan_chunks:
                if on_chunk is not None:
                    on_chunk(row0)
                with telemetry.span(
                    "sig", "chunk", row0=row0, rows=valid,
                    chunk_rows=self.chunk, tile=T,
                    conv=self.do_conv, null=self.do_null,
                ):
                    with telemetry.span("sig", "device_put", row0=row0):
                        rows = _pad_rows(
                            ts[row0 : row0 + self.chunk], self.chunk
                        )
                        rows_j = jnp.asarray(rows)
                    rho_chunk = (
                        np.asarray(rho[row0 : row0 + valid])
                        if self.do_null else None
                    )
                    if self.do_conv:
                        cidx, cw = dispatch(row0, "conv_tables",
                                            self.conv_tables_fn, rows_j,
                                            self.col_ids)
                    if self.do_null:
                        fidx, fw = dispatch(row0, "full_tables",
                                            self.full_tables_fn, rows_j)
                    for c0, seg_plan in self.tile_plans:
                        orig = order[c0 : min(c0 + T, N)]
                        # Every program of the tile is dispatched before
                        # the streamer drains the previous tile's blocks:
                        # the device then has this tile's work queued
                        # while the host waits, writes and gathers.
                        blocks = []
                        if self.do_conv:
                            blocks.append((
                                ("conv", row0, c0, valid),
                                dispatch(row0, "conv_tile",
                                         self.conv_tile_for(seg_plan), cidx,
                                         cw, jnp.asarray(ts_fut[orig])),
                            ))
                        if self.do_null:
                            # Regenerated per (chunk, tile) like
                            # _phase2_tiled's fut_tile upload: keeping every
                            # tile's (t*m, Lp) surrogate batch resident would
                            # defeat the tiling at scale.  The batch is let
                            # go once dispatched, so it is freed as its
                            # null call finishes.
                            fut_surr = dispatch(
                                row0, "surrogates",
                                surrogates.surrogate_futures,
                                self.surr_key, jnp.asarray(ts[orig]),
                                jnp.asarray(orig.astype(np.int32)),
                                m, sig.surrogate, cfg,
                            )
                            blocks.append((
                                ("pval", row0, c0, valid),
                                dispatch(row0, "null_tile",
                                         self.null_tile_for(seg_plan), fidx,
                                         fw, fut_surr, jnp.asarray(_pad_rows(
                                             rho_chunk[:, orig], self.chunk))),
                            ))
                            del fut_surr
                        for tag, block in blocks:
                            streamer.submit(tag, block)
                unit["chunks"] += 1
                unit["rows"] += valid


def run_sig_chunks(runner: SignificanceChunkRunner, chunk_plan, rho,
                   conv_w: Optional[TileWriter], trend_w: Optional[TileWriter],
                   pv_w: Optional[TileWriter], on_chunk=None) -> None:
    """The significance stage over an EXPLICIT (row0, nrows) chunk plan —
    the claimable sig work unit of a fleet worker (DESIGN.md SS10), as
    :func:`repro.core.pipeline.run_phase2_chunks` is phase 2's.

    Blocks drain through :func:`make_store_drain` into the stage's
    writers (None for a stage the runner skips), whose manifests are
    committed once the chunks are done.  One ``sig/unit`` span
    (:meth:`SignificanceChunkRunner.run`).
    """
    writers = [w for w in (conv_w, trend_w, pv_w) if w is not None]
    runner.run(chunk_plan, rho,
               make_store_drain(runner.N, conv_w, trend_w, pv_w),
               on_chunk=on_chunk)
    for w in writers:
        w.commit()


# ------------------------------------------------------------------- driver
def _writer(
    out_dir, name: str, N: int, order, writer_id: str | None = None
) -> TileWriter:
    w = TileWriter(f"{out_dir}/{name}", N, writer_id=writer_id, stage="sig")
    w.ensure_col_order(order)
    return w


def make_store_drain(N: int, conv_w, trend_w, pv_w):
    """Tile-store sink for :meth:`SignificanceChunkRunner.run` blocks —
    the ONE place that knows the block routing (conv stacks [drho;
    trend], pval is flat) and the per-chunk commit-batching policy.
    Shared by the in-process driver and fleet workers so the on-disk
    layout can never diverge between them (W=1 ≡ W=4 byte-identity)."""

    def drain(tag, block):
        kind, row0, c0, valid = tag
        last = c0 + block.shape[-1] >= N
        if kind == "conv":
            conv_w.write_tile(row0, c0, block[0][:valid], commit=last)
            trend_w.write_tile(row0, c0, block[1][:valid], commit=last)
        else:
            pv_w.write_tile(row0, c0, block[:valid], commit=last)

    return drain


def _check_resume_config(out_dir, sig: SignificanceConfig) -> None:
    """Pin the null-model parameters of a store to its first run.

    Coverage is the only thing the resume path inspects, so without this
    guard a rerun with different surrogates/seed/lib_sizes would silently
    reuse blocks computed under the OLD parameters (and stamp the new
    ones into meta.json).  alpha is deliberately NOT pinned: it only
    enters the BH pass and edge mask, which are recomputed every run.
    """
    import json
    import pathlib

    f = pathlib.Path(out_dir) / "significance.json"
    want = {
        "lib_sizes": list(sig.lib_sizes),
        "n_surrogates": sig.n_surrogates,
        "surrogate": sig.surrogate,
        "seed": sig.seed,
    }
    if f.exists():
        have = json.loads(f.read_text())
        if have != want:
            raise ValueError(
                f"resume config mismatch in {out_dir}: store was written "
                f"with {have} but this run asks for {want}; use a fresh "
                "--out dir (only --fdr may change across resumes)"
            )
        return
    f.parent.mkdir(parents=True, exist_ok=True)
    # Atomic + idempotent: concurrent fleet workers write identical bytes.
    store.atomic_write_text(f, json.dumps(want))


def run_significance(
    ts: np.ndarray,
    optE: np.ndarray,
    rho: np.ndarray,
    cfg: EDMConfig,
    sig: SignificanceConfig,
    mesh=None,
    out_dir: Optional[str] = None,
    progress: bool = False,
) -> SignificanceResult:
    """Validate a causal map: convergence statistics, surrogate p-values,
    and the BH-FDR significance-masked edge list.

    ts: (N, L) series; optE: (N,) phase-1 optimal embeddings; rho: the
    (N, N) observed causal map (memmap fine — read O(chunk x N) at a
    time).  Stages run per sig.lib_sizes / sig.n_surrogates; with
    ``out_dir`` every artifact streams through a TileWriter (resumable)
    and the returned maps are disk-backed memmaps.
    """
    if not (sig.lib_sizes or sig.n_surrogates > 0):
        return SignificanceResult(None, None, None, None)
    runner = SignificanceChunkRunner(ts, optE, cfg, sig, mesh)
    N = runner.N
    do_conv, do_null = runner.do_conv, runner.do_null
    m, chunk, order = runner.m, runner.chunk, runner.order

    # ---- outputs: streaming writers or (small-N) dense host maps -------
    if out_dir is not None:
        from repro.runtime import integrity

        # Same stamp-or-verify as run_causal_inference: sig params are
        # pinned separately below, the fingerprint pins (data, cfg).
        integrity.stamp_fingerprint(
            out_dir, integrity.fingerprint_of(np.asarray(ts, np.float32), cfg)
        )
        _check_resume_config(out_dir, sig)
        conv_w = _writer(out_dir, "rho_conv", N, order) if do_conv else None
        trend_w = _writer(out_dir, "rho_trend", N, order) if do_conv else None
        pv_w = _writer(out_dir, "pvals", N, order) if do_null else None
        writers = [w for w in (conv_w, trend_w, pv_w) if w is not None]
        cov = writers[0].covered()
        for w in writers[1:]:
            cov &= w.covered()
        plan_chunks = writers[0].chunk_plan(chunk, covered=cov)
        drho_map = trend_map = pv_map = None
    else:
        conv_w = trend_w = pv_w = None
        drho_map = np.zeros((N, N), np.float32) if do_conv else None
        trend_map = np.zeros((N, N), np.float32) if do_conv else None
        pv_map = np.ones((N, N), np.float32) if do_null else None
        plan_chunks = [(r, min(chunk, N - r)) for r in range(0, N, chunk)]

    # Streaming BH inputs: empirical p-values take the m+1 discrete values
    # j/(m+1), so per-value counts (diagonal excluded) determine the BH
    # threshold exactly — no dense p array, no sort (DESIGN.md SS9).
    p_counts = np.zeros(m + 1, np.int64)

    store_drain = (
        make_store_drain(N, conv_w, trend_w, pv_w) if out_dir is not None
        else None
    )

    def drain(tag, block):
        kind, row0, c0, valid = tag
        cols = order[c0 : c0 + block.shape[-1]]
        last = c0 + block.shape[-1] >= N
        if kind == "pval":
            pv_b = block[:valid]
            offdiag = cols[None, :] != (row0 + np.arange(valid))[:, None]
            p_counts[:] += np.bincount(
                np.rint(pv_b[offdiag] * (m + 1)).astype(np.int64) - 1,
                minlength=m + 1,
            )
        if store_drain is not None:
            store_drain(tag, block)
        elif kind == "conv":
            drho_map[row0 : row0 + valid, cols] = block[0][:valid]
            trend_map[row0 : row0 + valid, cols] = block[1][:valid]
        else:
            pv_map[row0 : row0 + valid, cols] = block[:valid]
        # One line per row chunk: the pval drain when the null stage runs
        # (it lands last), else the conv drain.
        if progress and last and (kind == "pval" or not do_null):
            print(f"significance rows {row0}..{row0 + valid} / {N}")

    resumed_rows = N - sum(v for _, v in plan_chunks)
    runner.run(plan_chunks, rho, drain)

    # ---- assembly ------------------------------------------------------
    if out_dir is not None:
        for w in writers:
            w.commit()
        # Chunks already durable from a prior run never re-drained, so
        # their p-value counts are recovered from the assembled map
        # (p_counts=None -> recount inside the finalizer).
        result = _finalize_store(
            cfg, sig, rho, conv_w=conv_w, trend_w=trend_w, pv_w=pv_w,
            p_counts=None if resumed_rows else p_counts, progress=progress,
        )
        # Run finished: append its summary to the run history (no-op
        # when telemetry is off and EDM_HISTORY unset; DESIGN.md SS13).
        history.record_run(out_dir)
        return result

    p_threshold, edges = 0.0, None
    n_tests = int(p_counts.sum())
    if do_null:
        p_threshold, p_cut = _bh_cut(p_counts, m, sig.alpha)
        edges = significance.assemble_edges(
            pv_map, rho, drho_map, trend_map, p_cut
        )
        if progress:
            print(
                f"BH-FDR alpha={sig.alpha}: p* = {p_threshold:.4g} over "
                f"{n_tests} tests -> {0 if edges is None else len(edges)} edges"
            )

    return SignificanceResult(
        drho=drho_map, trend=trend_map, pvals=pv_map, edges=edges,
        p_threshold=p_threshold, n_tests=n_tests,
    )


def _bh_cut(p_counts: np.ndarray, m: int, alpha: float) -> tuple[float, float]:
    """(p_threshold, edge cut).  p-values in the map are float32 of
    j/(m+1); the cut sits at the MIDPOINT between discrete levels so the
    threshold level itself is always included regardless of f32-vs-f64
    rounding of the quotient."""
    p_threshold, _ = significance.bh_threshold_discrete(p_counts, m, alpha)
    p_cut = p_threshold + 0.5 / (m + 1) if p_threshold > 0 else 0.0
    return p_threshold, p_cut


def _finalize_store(
    cfg: EDMConfig,
    sig: SignificanceConfig,
    rho: np.ndarray,
    *,
    conv_w: Optional[TileWriter],
    trend_w: Optional[TileWriter],
    pv_w: Optional[TileWriter],
    p_counts: Optional[np.ndarray] = None,
    progress: bool = False,
) -> SignificanceResult:
    """Assembly + exact discrete BH + edge list over store artifacts.

    Idempotent, and runnable by a process that computed NONE of the
    chunks (the fleet's ``finalize`` unit): with ``p_counts=None`` the
    per-value histogram is recovered by row-streaming the assembled
    p map — the recount-on-resume path, now also the recount-on-
    distributed-completion path (workers' streamed counts only ever
    cover their own chunks, so a fleet always recounts).
    """
    with telemetry.span("finalize", "store"):
        return _finalize_store_inner(
            cfg, sig, rho, conv_w=conv_w, trend_w=trend_w, pv_w=pv_w,
            p_counts=p_counts, progress=progress,
        )


def _finalize_store_inner(
    cfg: EDMConfig,
    sig: SignificanceConfig,
    rho: np.ndarray,
    *,
    conv_w: Optional[TileWriter],
    trend_w: Optional[TileWriter],
    pv_w: Optional[TileWriter],
    p_counts: Optional[np.ndarray] = None,
    progress: bool = False,
) -> SignificanceResult:
    m = sig.n_surrogates
    meta_common = {
        "lib_sizes": list(sig.lib_sizes),
        "n_surrogates": m,
        "surrogate": sig.surrogate,
        "seed": sig.seed,
    }
    drho_map = trend_map = pv_map = None
    if conv_w is not None:
        drho_map = conv_w.assemble(mmap_path=conv_w.dir / "data.npy")
        trend_map = trend_w.assemble(mmap_path=trend_w.dir / "data.npy")
        store.save_meta(
            conv_w.dir, drho_map.shape, drho_map.dtype,
            {**meta_common, "stat": "delta_rho", "trend": "../rho_trend"},
        )
        store.save_meta(
            trend_w.dir, trend_map.shape, trend_map.dtype,
            {**meta_common, "stat": "monotonic_trend"},
        )

    p_threshold, edges, n_tests = 0.0, None, 0
    if pv_w is not None:
        pv_map = pv_w.assemble(mmap_path=pv_w.dir / "data.npy")
        if p_counts is None:
            n_tests, p_counts = _recount_pvals(pv_map, m)
        else:
            n_tests = int(p_counts.sum())
        p_threshold, p_cut = _bh_cut(p_counts, m, sig.alpha)
        edges = significance.assemble_edges(
            pv_map, rho, drho_map, trend_map, p_cut
        )
        sig_meta = {**meta_common, "alpha": sig.alpha,
                    "p_threshold": p_threshold, "n_tests": n_tests}
        store.save_meta(pv_w.dir, pv_map.shape, pv_map.dtype, sig_meta)
        edir = pv_w.dir.parent / "edges"
        edir.mkdir(parents=True, exist_ok=True)
        store.save_npy_checksummed(edir / "data.npy", edges, fault="edges")
        store.save_meta(
            edir, edges.shape, edges.dtype.str,
            {**sig_meta, "n_edges": int(edges.shape[0]),
             "fields": list(edges.dtype.names)},
        )
        if progress:
            print(
                f"BH-FDR alpha={sig.alpha}: p* = {p_threshold:.4g} over "
                f"{n_tests} tests -> {len(edges)} edges"
            )

    return SignificanceResult(
        drho=drho_map, trend=trend_map, pvals=pv_map, edges=edges,
        p_threshold=p_threshold, n_tests=n_tests,
    )


def finalize_significance(
    out_dir: str,
    rho: np.ndarray,
    cfg: EDMConfig,
    sig: SignificanceConfig,
    progress: bool = False,
) -> SignificanceResult:
    """The fleet's ``finalize`` work unit (DESIGN.md SS10): assemble the
    (multi-writer) significance store, recount the p-value histogram,
    and write the BH-FDR edge list — by whichever worker claims the
    unit, none of whose own chunks need be among the blocks.  Idempotent
    (a finalizer crash just reruns it); raises if any artifact's
    coverage is still incomplete."""
    N = rho.shape[0]
    do_conv = bool(sig.lib_sizes)
    do_null = sig.n_surrogates > 0
    conv_w = TileWriter(f"{out_dir}/rho_conv", N) if do_conv else None
    trend_w = TileWriter(f"{out_dir}/rho_trend", N) if do_conv else None
    pv_w = TileWriter(f"{out_dir}/pvals", N) if do_null else None
    for w in (conv_w, trend_w, pv_w):
        if w is not None and not w.covered().all():
            raise ValueError(
                f"{w.dir} is incomplete ({int((~w.covered()).sum())} rows "
                "uncovered): finalize ran before every sig unit was done"
            )
    result = _finalize_store(
        cfg, sig, rho, conv_w=conv_w, trend_w=trend_w, pv_w=pv_w,
        p_counts=None, progress=progress,
    )
    # The finalize claimer is the run's single history writer: one
    # summary record per finished run, replaced (not duplicated) when an
    # elastic resume or heal re-finalizes (DESIGN.md SS13).
    history.record_run(out_dir)
    return result


def _recount_pvals(pv_map: np.ndarray, m: int) -> tuple[int, np.ndarray]:
    """Row-streamed per-value p counts (diagonal excluded) from a
    (memmapped) p-value map — the resume path of the discrete BH pass."""
    N = pv_map.shape[0]
    counts = np.zeros(m + 1, np.int64)
    for i in range(N):
        row = np.asarray(pv_map[i])
        idx = np.rint(np.delete(row, i) * (m + 1)).astype(np.int64) - 1
        counts += np.bincount(idx, minlength=m + 1)
    return int(counts.sum()), counts
