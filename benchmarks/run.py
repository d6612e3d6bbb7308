"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Scales are laptop-sized
(this container is 1 CPU core); every benchmark also reports the derived
quantity the paper's figure plots (speedup, scaling exponent, fraction),
and the complexity-model extrapolation to the paper's own dataset sizes.

  Table II  -> naive (cppEDM Alg.1) vs improved (mpEDM Alg.2) causal map
  Fig 3     -> strong scaling over fake-device worker counts (subprocess)
  Fig 6     -> runtime vs number of series N
  Fig 7     -> runtime vs series length L
  Fig 8     -> CCM phase breakdown: kNN tables vs lookup
  Fig 9     -> multi-E table construction: cumulative-E scan vs per-E
               rebuild (the TPU analogue of the paper's GPU-vs-CPU kernel)
  roofline  -> summary of the dry-run table (benchmarks/results/dryrun)

Regression gate: ``python benchmarks/run.py --check phase2 knn
significance`` reruns the named benches with their JSON output
redirected to benchmarks/results/fresh/ (CI uploads these as
artifacts), compares the gated timings against the COMMITTED repo-root
BENCH_*.json baselines, and exits nonzero on any >1.5x slowdown.
``--check knn`` additionally runs the knn-gate: streaming table builds
must stay at-or-below the slab historical baseline at EVERY benched Lc
on both engines (the contract that justified deleting the slab path).
Refresh a baseline by running the bench WITHOUT --check (writes the
repo-root JSON) and committing it.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import (  # noqa: E402
    EDMConfig,
    all_futures,
    ccm_block,
    ccm_matrix,
    ccm_pair_naive,
    knn_table_single_E,
    knn_tables_dense,
    lag_matrix,
    simplex_batch,
)
from repro.data.synthetic import dummy_brain  # noqa: E402

RESULTS = pathlib.Path(__file__).resolve().parent / "results"
REPO = pathlib.Path(__file__).resolve().parents[1]
# Where benches write their BENCH_*.json: the repo root by default
# (committed baselines), benchmarks/results/fresh/ under --check.
BENCH_DIR = REPO


def _write_bench(name: str, out: dict) -> None:
    BENCH_DIR.mkdir(parents=True, exist_ok=True)
    (BENCH_DIR / name).write_text(json.dumps(out, indent=2))


def _time(fn, *args, reps=3) -> float:
    """median wall time (s) with block_until_ready."""
    fn(*args)  # warmup/compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def row(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.1f},{derived}")


# ---------------------------------------------------------------- Table II
def table2_speedup():
    """Improved Alg.2 vs naive Alg.1 full causal map."""
    N, L = 24, 400
    cfg = EDMConfig(E_max=8)
    ts = jnp.asarray(dummy_brain(N, L))
    _, optE = simplex_batch(ts, cfg)
    ts_fut = all_futures(ts, cfg)

    t_improved = _time(lambda: jax.block_until_ready(ccm_matrix(ts, optE, cfg)))
    # naive cost = N^2 single-pair cross maps (measure one, multiply)
    E_med = int(np.median(np.asarray(optE)))
    t_pair = _time(lambda: ccm_pair_naive(ts[0], ts_fut[1], E_med, cfg), reps=5)
    t_naive = t_pair * N * N
    row("table2_improved_ccm", t_improved, f"N={N};L={L}")
    row("table2_naive_ccm_extrap", t_naive, f"pair={t_pair*1e6:.0f}us x N^2")
    row("table2_speedup", t_improved, f"speedup={t_naive / t_improved:.1f}x")
    # complexity-model speedup at the paper's Fish1_Normo scale
    for name, (Np, Lp_) in {"fish1": (53053, 1450), "subject11": (101729, 8528)}.items():
        E = 20
        naive = Np * Np * Lp_ * Lp_ * E
        improved = Np * Lp_ * Lp_ * E + Np * Np * Lp_ * E  # cumulative-E: E not E^2
        row(f"table2_model_{name}", 0.0, f"algorithmic_speedup={naive / improved:.0f}x")


# ------------------------------------------------------------------- Fig 3
def fig3_strong_scaling():
    """Pipeline wall time vs fake-device worker count (subprocess per point)."""
    N, L = 32, 300
    code = """
import time, numpy as np
import jax
from repro.core.pipeline import run_causal_inference
from repro.core.types import EDMConfig
from repro.data.synthetic import dummy_brain
ts = dummy_brain({N}, {L})
cfg = EDMConfig(E_max=5, lib_block=2)
run_causal_inference(ts[:4], cfg)  # warm compile caches
t0 = time.perf_counter()
run_causal_inference(ts, cfg)
print("TIME", time.perf_counter() - t0)
""".format(N=N, L=L)
    # NOTE: fake devices time-share ONE physical core, so wall time cannot
    # drop; what this measures is the SPMD partitioning OVERHEAD of the
    # worker decomposition (paper Fig 3's linearity comes from the same
    # zero-communication structure, whose overhead we bound here).
    base = None
    for w in (1, 2, 4, 8):
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={w}"
        env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] / "src")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, timeout=900)
        t = float([l for l in r.stdout.splitlines() if l.startswith("TIME")][0].split()[1])
        base = base or t
        row(f"fig3_workers_{w}", t, f"spmd_overhead={100 * (t - base) / base:.0f}%")


# ------------------------------------------------------------------- Fig 6/7
def fig6_scaling_N():
    L, cfg = 300, EDMConfig(E_max=5)
    times = {}
    for N in (8, 16, 32):
        ts = jnp.asarray(dummy_brain(N, L, seed=N))
        _, optE = simplex_batch(ts, cfg)
        times[N] = _time(lambda ts=ts, optE=optE: ccm_matrix(ts, optE, cfg))
        row(f"fig6_N{N}", times[N], f"L={L}")
    expo = np.polyfit(np.log(list(times)), np.log(list(times.values())), 1)[0]
    row("fig6_scaling_exponent", 0.0, f"O(N^{expo:.2f})_model_<=2")


def fig7_scaling_L():
    N, cfg = 12, EDMConfig(E_max=5)
    times = {}
    for L in (200, 400, 800):
        ts = jnp.asarray(dummy_brain(N, L, seed=L))
        _, optE = simplex_batch(ts, cfg)
        times[L] = _time(lambda ts=ts, optE=optE: ccm_matrix(ts, optE, cfg))
        row(f"fig7_L{L}", times[L], f"N={N}")
    expo = np.polyfit(np.log(list(times)), np.log(list(times.values())), 1)[0]
    row("fig7_scaling_exponent", 0.0, f"O(L^{expo:.2f})_model_<=2")


# ------------------------------------------------------------------- Fig 8
def fig8_breakdown():
    """CCM phase split: kNN table construction vs lookup (paper Fig 8)."""
    N, L = 32, 500
    cfg = EDMConfig(E_max=8)
    ts = jnp.asarray(dummy_brain(N, L))
    _, optE = simplex_batch(ts, cfg)
    ts_fut = all_futures(ts, cfg)
    Lp = cfg.n_points(L)
    V = lag_matrix(ts[0], cfg.E_max, cfg.tau, Lp)

    from repro.core.knn import (
        knn_tables_all_E_streaming,
        resolve_stream_tile,
        simplex_forecast,
        tables_with_weights,
    )

    tile = resolve_stream_tile(Lp, cfg, profile="host")
    build = jax.jit(
        lambda V: knn_tables_all_E_streaming(V, V, cfg.k_max, True, tile)
    )
    t_knn = _time(lambda: build(V))
    idx, sqd = build(V)
    idx, w = tables_with_weights(idx, sqd)

    def lookup_all():
        e = optE - 1
        return jax.vmap(lambda yf, ee: simplex_forecast(idx[ee], w[ee], yf))(
            ts_fut, e
        )

    t_lookup = _time(jax.jit(lookup_all))
    total = t_knn + t_lookup
    row("fig8_knn_per_series", t_knn, f"{100 * t_knn / total:.0f}%_of_ccm")
    row("fig8_lookup_per_series", t_lookup, f"{100 * t_lookup / total:.0f}%_of_ccm;N={N}")


# ------------------------------------------------------------------- Fig 9
def fig9_multiE_kernel():
    """Cumulative-E scan vs per-E rebuild — the beyond-paper algorithmic
    win on the paper's own hot spot (analogue of its GPU-kernel speedup)."""
    L, E_max = 800, 20
    cfg = EDMConfig(E_max=E_max)
    x = jnp.asarray(dummy_brain(1, L)[0])
    Lp = cfg.n_points(L)
    V = lag_matrix(x, E_max, cfg.tau, Lp)

    t_cum = _time(
        jax.jit(lambda V: knn_tables_dense(V, V, E_max + 1, False)), V
    )

    @jax.jit
    def per_E_rebuild(V):
        return [
            knn_table_single_E(V, V, E, E_max + 1, False, matmul_form=True)
            for E in range(1, E_max + 1)
        ]

    t_reb = _time(per_E_rebuild, V)
    row("fig9_cumulative_multiE", t_cum, f"L={L};E_max={E_max}")
    row("fig9_per_E_rebuild", t_reb, f"speedup={t_reb / t_cum:.1f}x")


def fig9b_knn_impl_variants():
    """Measured wall time of the kNN table-construction variants (SSPerf
    HC3): paper-faithful per-E rebuild vs cumulative-E scan/unroll/blocked.
    Primary evidence for the HC3 variant ordering (XLA cost_analysis cannot
    attribute scan bodies, so these are real timings)."""
    L, cfg = 2000, EDMConfig(E_max=20)
    x = jnp.asarray(dummy_brain(1, L)[0])
    V = lag_matrix(x, cfg.E_max, cfg.tau, cfg.n_points(L))
    times = {}
    for impl in ("rebuild", "scan", "unroll", "blocked:4", "blocked:2"):
        f = jax.jit(
            lambda V, impl=impl: knn_tables_dense(V, V, cfg.k_max, True, impl=impl)
        )
        times[impl] = _time(lambda: f(V))
    base = times["rebuild"]
    for impl, t in times.items():
        row(
            f"fig9b_knn_{impl.replace(':', '')}", t,
            f"vs_paper_faithful_rebuild={base / t:.2f}x",
        )


# ------------------------------------------------------- phase-2 engine bench
def phase2_engine_bench(N=128, L=1000, E_max=20, engine="reference", tile=32):
    """Phase-2 wall clock + host memory: seed path (all-E tables, dense
    host map, synchronous drain) vs optE-bucketed tables + double-buffered
    streaming (DESIGN.md SS3/SS6) vs the 2D target-tiled decomposition
    (DESIGN.md SS7: tables once per chunk + column tiles, NO dense host
    map), through the real pipeline loops including the TileWriter.
    Records engine name, bucket count, tile geometry, and per-variant
    host-allocation peaks (tracemalloc) + process peak RSS to
    BENCH_phase2.json so trajectories stay comparable across backends.
    """
    import resource
    import tempfile
    import tracemalloc

    import jax.numpy as jnp

    from repro.core import make_bucket_plan, make_tile_plans
    from repro.core.pipeline import (
        make_ccm_chunk_fn,
        make_ccm_chunk_fn_bucketed,
        make_ccm_tables_fn_bucketed,
        make_ccm_tile_fn_bucketed,
        _pad_rows,
    )
    from repro.data.store import TileWriter
    from repro.runtime.stream import ChunkStreamer

    mesh = jax.make_mesh((len(jax.devices()),), ("workers",))
    base = dict(E_max=E_max, engine=engine, lib_block=8)
    cfg_seed = EDMConfig(**base, bucketed=False, stream_depth=1)
    cfg_new = EDMConfig(**base, bucketed=True, stream_depth=2)
    cfg_tiled = EDMConfig(**base, bucketed=True, stream_depth=2, target_tile=tile)
    chunk = mesh.size * cfg_seed.lib_block

    ts = jnp.asarray(dummy_brain(N, L, seed=42))
    _, optE = simplex_batch(ts, cfg_new)
    optE_np = np.asarray(optE)
    plan, order = make_bucket_plan(optE_np)
    ts_fut = all_futures(ts, cfg_new)
    ts_np = np.asarray(ts)

    def run_loop(chunk_fn, args_of_rows, unsort, depth, out_dir):
        # seed-shaped loop: full-width row blocks into a DENSE host map
        writer = TileWriter(out_dir, N)
        rho = np.zeros((N, N), np.float32)

        def drain(tag, rows_dev):
            row0, valid = tag
            rows_np = unsort(rows_dev)[:valid]
            rho[row0 : row0 + valid] = rows_np
            writer.write_block(row0, rows_np)

        t0 = time.perf_counter()
        with ChunkStreamer(drain, depth=depth) as s:
            for row0 in range(0, N, chunk):
                valid = min(chunk, N - row0)
                rows = _pad_rows(ts_np[row0 : row0 + chunk], chunk)
                s.submit((row0, valid), chunk_fn(*args_of_rows(rows)))
        return time.perf_counter() - t0, rho

    inv = np.argsort(order)
    ts_fut_sorted = ts_fut[jnp.asarray(order)]  # hoisted, as in the pipeline
    ts_fut_sorted_np = np.asarray(ts_fut_sorted)
    tile_plans = make_tile_plans(plan, tile)
    tables_fn = make_ccm_tables_fn_bucketed(mesh, cfg_tiled, plan)
    tile_fn_for = make_ccm_tile_fn_bucketed(mesh, cfg_tiled)

    def run_loop_tiled(out_dir):
        # DESIGN SS7 loop: tables once per chunk, targets in column tiles,
        # blocks stream to the TileWriter — no dense (N, N) host array;
        # the map is assembled into a memmap afterwards (counted in time).
        writer = TileWriter(out_dir, N)
        writer.ensure_col_order(order)

        def drain(tag, block):
            row0, col0, valid = tag
            writer.write_tile(row0, col0, block[:valid])

        t0 = time.perf_counter()
        with ChunkStreamer(drain, depth=cfg_tiled.stream_depth) as s:
            for row0 in range(0, N, chunk):
                valid = min(chunk, N - row0)
                rows = _pad_rows(ts_np[row0 : row0 + chunk], chunk)
                idx, w = tables_fn(jnp.asarray(rows))
                for c0, seg_plan in tile_plans:
                    fut_tile = jnp.asarray(ts_fut_sorted_np[c0 : c0 + tile])
                    s.submit(
                        (row0, c0, valid), tile_fn_for(seg_plan)(idx, w, fut_tile)
                    )
        rho = writer.assemble(mmap_path=writer.dir / "causal_map" / "data.npy")
        return time.perf_counter() - t0, rho  # rho is a disk-backed memmap

    variants = {
        "seed_all_e_sync": (
            make_ccm_chunk_fn(mesh, cfg_seed),
            lambda rows: (jnp.asarray(rows), ts_fut, optE),
            lambda r: r,
            1,
        ),
        "bucketed_double_buffered": (
            make_ccm_chunk_fn_bucketed(mesh, cfg_new, plan),
            lambda rows: (jnp.asarray(rows), ts_fut_sorted),
            lambda r: r[:, inv],
            2,
        ),
    }
    times, rhos, host_peaks = {}, {}, {}
    for name, (fn, args_of_rows, unsort, depth) in variants.items():
        # warm the compile cache so we time steady-state phase 2
        jax.block_until_ready(fn(*args_of_rows(_pad_rows(ts_np[:chunk], chunk))))
        tracemalloc.start()
        with tempfile.TemporaryDirectory() as d:
            times[name], rhos[name] = run_loop(fn, args_of_rows, unsort, depth, d)
        host_peaks[name] = tracemalloc.get_traced_memory()[1]
        tracemalloc.stop()
        row(f"phase2_{name}", times[name], f"N={N};L={L};E_max={E_max}")

    # warm the tiled fns (tables + every distinct tile signature)
    idx_w, w_w = tables_fn(jnp.asarray(_pad_rows(ts_np[:chunk], chunk)))
    for c0, seg_plan in tile_plans:
        jax.block_until_ready(
            tile_fn_for(seg_plan)(
                idx_w, w_w, jnp.asarray(ts_fut_sorted_np[c0 : c0 + tile])
            )
        )
    tracemalloc.start()
    with tempfile.TemporaryDirectory() as d:
        times["bucketed_tiled"], rho_mm = run_loop_tiled(d)
        # peak captured BEFORE the dense comparison copy below — the copy
        # exists only so err_tiled can be computed after the tempdir (and
        # the memmap's backing file) are gone; it is not part of the
        # tiled path's own memory profile
        host_peaks["bucketed_tiled"] = tracemalloc.get_traced_memory()[1]
        tracemalloc.stop()
        rhos["bucketed_tiled"] = np.array(rho_mm)
    row(
        "phase2_bucketed_tiled", times["bucketed_tiled"],
        f"N={N};L={L};tile={tile};n_col_tiles={len(tile_plans)}",
    )

    err = float(
        np.abs(rhos["seed_all_e_sync"] - rhos["bucketed_double_buffered"]).max()
    )
    err_tiled = float(
        np.abs(rhos["bucketed_double_buffered"] - rhos["bucketed_tiled"]).max()
    )
    speedup = times["seed_all_e_sync"] / times["bucketed_double_buffered"]
    ru_maxrss_kb = int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
    row("phase2_speedup", 0.0, f"speedup={speedup:.2f}x;max_drho={err:.1e}")
    row(
        "phase2_tiled_host_peak", 0.0,
        f"host_peak_MiB={host_peaks['bucketed_tiled'] / 2**20:.1f};"
        f"dense_MiB={host_peaks['seed_all_e_sync'] / 2**20:.1f};"
        f"tiled_drho={err_tiled:.1e}",
    )

    out = {
        "bench": "phase2_engine",
        "workload": {"N": N, "L": L, "E_max": E_max},
        "engine": engine,
        "n_buckets": len(plan.buckets),
        "buckets": list(plan.buckets),
        "devices": mesh.size,
        "tile": {
            "target_tile": tile,
            "n_col_tiles": len(tile_plans),
            "n_tile_signatures": len({sp for _, sp in tile_plans}),
            "chunk_rows": chunk,
        },
        "seed_path": {
            "bucketed": False, "stream_depth": 1,
            "phase2_s": times["seed_all_e_sync"],
            "host_peak_bytes": host_peaks["seed_all_e_sync"],
        },
        "new_path": {
            "bucketed": True, "stream_depth": 2,
            "phase2_s": times["bucketed_double_buffered"],
            "host_peak_bytes": host_peaks["bucketed_double_buffered"],
        },
        "tiled_path": {
            "bucketed": True, "stream_depth": 2, "target_tile": tile,
            "phase2_s": times["bucketed_tiled"],
            "host_peak_bytes": host_peaks["bucketed_tiled"],
        },
        "ru_maxrss_kb": ru_maxrss_kb,
        "speedup": speedup,
        "max_abs_drho": err,
        "max_abs_drho_tiled": err_tiled,
    }
    _write_bench("BENCH_phase2.json", out)
    return out


# ----------------------------------------------------- kNN selection bench
def _slab_bytes(Lq: int, Lc: int) -> int:
    """Distance working set of the RETIRED slab layout: the full (Lq, Lc)
    f32 distance matrix plus its i32 candidate-id plane.  Lives only here
    — src/ no longer has a slab path — as the historical yardstick the
    streaming flat-memory column is plotted against."""
    return Lq * Lc * (4 + 4)


def _slab_knn_pallas(Vq, Vc, k, exclude_self, block_q=128):
    """Compact copy of the retired slab Pallas kernel (VMEM-resident
    (block_q, Lc) distance slab accumulated across E, k-pass top-k per E).

    Deleted from src/ by the streaming+merge-network rework; kept ONLY
    here so the knn bench's historical reference column times the layout
    each engine actually used before, on the same machine as the fresh
    streaming numbers the knn-gate compares against."""
    import functools

    from jax.experimental import pallas as pl

    from repro.core.knn import _acc_sq_cols
    from repro.kernels.knn_topk import knn_topk as ktk

    E_max, Lc = Vq.shape[0], Vc.shape[1]
    Lc_pad = pl.cdiv(Lc, 128) * 128
    Vc_p = jnp.pad(Vc, ((0, 0), (0, Lc_pad - Lc)))

    def kernel(vq_ref, vc_ref, idx_ref, dist_ref, *, bq, row0):
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (bq, Lc_pad), 1)
        invalid = col_ids >= Lc
        if exclude_self:
            row_ids = row0 + pl.program_id(0) * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, Lc_pad), 0
            )
            invalid = invalid | (col_ids == row_ids)
        D = jnp.zeros((bq, Lc_pad), jnp.float32)
        for e in range(E_max):
            D = _acc_sq_cols(D, vq_ref[:, e : e + 1], vc_ref[e : e + 1, :],
                             jnp.float32)
            Dm = jnp.where(invalid, ktk._BIG, D)
            idxs, dists = ktk._kpass_select(Dm, 0, k)
            idx_ref[e] = idxs
            dist_ref[e] = dists

    def call_split(VqT_p, row0, rows_pad, bq):
        return pl.pallas_call(
            functools.partial(kernel, bq=bq, row0=row0),
            grid=(rows_pad // bq,),
            in_specs=[
                pl.BlockSpec((bq, E_max), lambda i: (i, 0)),
                pl.BlockSpec((E_max, Lc_pad), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((E_max, bq, k), lambda i: (0, i, 0)),
                pl.BlockSpec((E_max, bq, k), lambda i: (0, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((E_max, rows_pad, k), jnp.int32),
                jax.ShapeDtypeStruct((E_max, rows_pad, k), jnp.float32),
            ],
            interpret=True,
        )(VqT_p, Vc_p)

    return ktk._over_query_splits(Vq, block_q, call_split)


def knn_selection_bench(Lc_sweep=(1000, 2000, 4000, 16000), Lq=128, N=128,
                        L_ref=1000, Lc_ref_extra=(64000,)):
    """BENCH_knn.json (DESIGN.md SS8): streaming kNN table construction
    (bitonic partial-merge network, one-shot calibrated tile) vs the
    RETIRED dense slab layout, for a FIXED 128-row query block against
    candidate libraries of growing length Lc, both engines.  The
    reference engine additionally sweeps ``Lc_ref_extra`` (paper-scale
    libraries the interpret-mode kernel would take too long on).

    Records, per engine and per Lc: the calibrated tile width, build
    wall time for both layouts (the slab column is a benchmark-local
    copy — :func:`_slab_knn_pallas` / the dense-oracle jnp builder —
    kept one last time as the historical reference), and the PEAK
    DISTANCE WORKING SET each needs — the slab grows linearly in Lc,
    streaming stays FLAT.  The ``--check knn`` knn-gate asserts
    stream_s <= slab_s at every benched Lc on both engines (streaming
    wins everywhere — the reason the slab could be deleted), plus the
    usual wall-time drift gate against the committed baseline.
    Bit-identity streaming-vs-dense-oracle is spot-checked on the
    cheapest cell (the full sweep lives in tests/test_knn_streaming.py).
    """
    from repro.core import knn
    from repro.engine import get_engine
    from repro.kernels.knn_topk.knn_topk import stream_vmem_bytes

    E_max, k = 20, 21
    out = {
        "bench": "knn_selection",
        "E_max": E_max,
        "k": k,
        "Lq": Lq,
        "merge": "bitonic_partial_merge_network",
        "tile_budget_bytes": knn.KNN_TILE_BUDGET_BYTES,
        "tile_budget_bytes_host": knn.KNN_TILE_BUDGET_BYTES_HOST,
        "engines": {},
        "phase1": {},
    }
    max_Lc = max(list(Lc_sweep) + list(Lc_ref_extra))
    pair = dummy_brain(2, max_Lc + E_max + 1, seed=3)
    checked = False
    for engine in ("reference", "pallas-interpret"):
        eng = get_engine(engine)
        cfg = EDMConfig(E_max=E_max, engine=engine)  # knn_tile_c=0: calibrated
        sweep = list(Lc_sweep)
        if engine == "reference":
            sweep += list(Lc_ref_extra)
        rows_d = {}
        for Lc in sweep:
            tile = eng.knn_selection_tile(Lc, cfg)  # per-engine profile
            Vq = lag_matrix(jnp.asarray(pair[0]), E_max, 1, Lq)
            Vc = lag_matrix(jnp.asarray(pair[1]), E_max, 1, Lc)
            f_stream = jax.jit(
                lambda Vq, Vc, c=cfg: eng.knn_tables(
                    Vq, Vc, k, exclude_self=False, cfg=c
                )
            )
            if engine == "reference":
                f_slab = jax.jit(
                    lambda Vq, Vc: knn_tables_dense(Vq, Vc, k, False)
                )
            else:
                f_slab = jax.jit(
                    lambda Vq, Vc: _slab_knn_pallas(Vq, Vc, k, False)
                )
            # interleave the two layouts' reps: the shared-runner clock
            # drifts on the seconds scale, which a paired A/B absorbs
            reps = 5 if Lc <= 4000 else 3
            jax.block_until_ready(f_stream(Vq, Vc))
            jax.block_until_ready(f_slab(Vq, Vc))
            obs = {"stream": [], "slab": []}
            for _ in range(reps):
                for name, f in (("stream", f_stream), ("slab", f_slab)):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(Vq, Vc))
                    obs[name].append(time.perf_counter() - t0)
            t_stream = float(np.median(obs["stream"]))
            t_slab = float(np.median(obs["slab"]))
            if not checked:  # bit-identity spot check on the cheapest cell
                a, b = f_slab(Vq, Vc), f_stream(Vq, Vc)
                assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
                assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
                checked = True
            # peak distance working set: the slab materializes (Lq, Lc);
            # streaming holds one tile + doubled merge buffers + running
            # tables (jnp path) or the per-program VMEM budget (pallas
            # path) — both INDEPENDENT of Lc
            eff_tile = min(tile, -(-Lc // 8) * 8)
            if engine == "reference":
                ws_stream = knn.streaming_bytes(Lq, k, eff_tile, E_max)
            else:
                ws_stream = stream_vmem_bytes(E_max, k, Lq, eff_tile)
            rows_d[str(Lc)] = {
                "Lc": Lc,
                "tile_c": tile,
                "stream_s": t_stream,
                "slab_s": t_slab,
                "slab_working_set_bytes": _slab_bytes(Lq, Lc),
                "stream_working_set_bytes": ws_stream,
            }
            row(
                f"knn_{engine}_Lc{Lc}", t_stream,
                f"slab_s={t_slab:.3f};tile_c={tile};slab_MiB="
                f"{_slab_bytes(Lq, Lc) / 2**20:.2f};"
                f"stream_MiB={ws_stream / 2**20:.2f}",
            )
        out["engines"][engine] = rows_d

    # ---- phase-1 wall clock at the reference workload -----------------
    # auto (knn_tile_c=0, one-shot calibration) vs a deliberately narrow
    # forced tile: the no-regression guard that calibration picks a tile
    # at least as good as any hand-forced one.
    ts = jnp.asarray(dummy_brain(N, L_ref, seed=1))
    forced = 512
    times = {}
    for name, cfg in {
        "auto": EDMConfig(E_max=E_max),
        "forced_tile": EDMConfig(E_max=E_max, knn_tile_c=forced),
    }.items():
        times[name] = _time(lambda c=cfg: simplex_batch(ts, c))
    out["phase1"] = {
        "workload": {"N": N, "L": L_ref},
        "auto_s": times["auto"],
        "auto_tile_c": knn.resolve_stream_tile(
            EDMConfig(E_max=E_max).n_points(L_ref), EDMConfig(E_max=E_max),
            profile="host",
        ),
        "forced_tile_s": times["forced_tile"],
        "forced_tile_c": forced,
        "auto_vs_forced": times["auto"] / times["forced_tile"],
    }
    row(
        "knn_phase1_ref", times["auto"],
        f"forced_tile_s={times['forced_tile']:.3f};"
        f"auto_vs_forced={times['auto'] / times['forced_tile']:.2f}x",
    )
    _write_bench("BENCH_knn.json", out)
    return out


# ------------------------------------------------- significance bench (SS9)
def significance_bench(N=128, L=1000, E_max=20, rows=8, n_sizes=6):
    """BENCH_significance.json (DESIGN.md SS9): ONE-sweep prefix-snapshot
    convergence table build vs the old-style per-size rebuild at the
    128x1000 reference workload.

    Times the convergence-table construction for one ``rows``-row library
    chunk (the pipeline's dispatch unit) with the REAL bucket set from
    phase 1 and a paper-style grid of ``n_sizes`` nested library sizes:
    the rebuild sweeps sum(lib_sizes) candidate columns, the one-sweep
    snapshot only max(lib_sizes) — the measured speedup should track
    that ratio.  Chunk times are extrapolated to the full N-row workload
    (both variants scale linearly in rows).
    """
    from repro.core import knn, lag_matrix, make_bucket_plan
    from repro.inference import subsample_permutation

    cfg = EDMConfig(E_max=E_max)
    ts = jnp.asarray(dummy_brain(N, L, seed=5))
    _, optE = simplex_batch(ts, cfg)
    plan, _ = make_bucket_plan(np.asarray(optE))
    Lp = cfg.n_points(L)
    kb = plan.buckets[-1] + 1
    lib_sizes = tuple(
        int(s) for s in np.linspace(max(kb + 1, Lp // 8), Lp, n_sizes)
    )
    perm = subsample_permutation(jax.random.PRNGKey(0), Lp)
    tile = knn.calibrate_knn_tile(
        Lp, E_max=E_max, k=kb,
        budget_bytes=knn.KNN_TILE_BUDGET_BYTES_HOST,
        tile_max=knn.KNN_TILE_MAX_HOST,
    )
    rows_j = ts[:rows]

    def build(fn):
        def per_row(x):
            V = lag_matrix(x, cfg.E_max, cfg.tau, Lp)
            return fn(
                V, V, kb, cfg.exclude_self, plan.buckets, lib_sizes, tile,
                jnp.float32, perm,
            )

        return jax.jit(jax.vmap(per_row))

    one_sweep = build(knn.knn_tables_prefix_streaming)
    rebuild = build(knn.knn_tables_prefix_rebuild)
    t_one = _time(lambda: one_sweep(rows_j), reps=1)
    t_reb = _time(lambda: rebuild(rows_j), reps=1)

    # identical tables is part of the contract the bench compares under
    a, b = one_sweep(rows_j), rebuild(rows_j)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))

    speedup = t_reb / t_one
    row("significance_one_sweep_chunk", t_one,
        f"N={N};L={L};rows={rows};S={n_sizes}")
    row("significance_rebuild_chunk", t_reb, f"speedup={speedup:.2f}x")
    out = {
        "bench": "significance_convergence_build",
        "workload": {"N": N, "L": L, "E_max": E_max, "Lp": Lp},
        "rows_timed": rows,
        "lib_sizes": list(lib_sizes),
        "n_buckets": len(plan.buckets),
        "k": kb,
        "tile_c": tile,
        "one_sweep_chunk_s": t_one,
        "rebuild_chunk_s": t_reb,
        "one_sweep_full_N_s": t_one * N / rows,
        "rebuild_full_N_s": t_reb * N / rows,
        "speedup": speedup,
        "candidate_cols_ratio": sum(lib_sizes) / lib_sizes[-1],
    }
    _write_bench("BENCH_significance.json", out)
    return out


# ------------------------------------------------------------------ roofline
def roofline_summary():
    d = RESULTS / "dryrun"
    if not d.exists():
        return
    for p in sorted(d.glob("*.json")):
        r = json.loads(p.read_text())
        if "skipped" in r:
            row(f"roofline_{r['arch']}_{r['cell']}_{r.get('mesh')}", 0.0, "SKIP")
            continue
        rl = r["roofline"]
        row(
            f"roofline_{r['arch']}_{r['cell']}_{r['mesh']}",
            rl["t_compute_s"] + 0.0,
            f"bottleneck={rl['bottleneck']};frac={rl['roofline_fraction']:.3f};"
            f"mem_GiB={r['memory']['peak_bytes_per_device'] / 2**30:.1f}",
        )


# ------------------------------------------------- paper-shape scaling
# Prior bench ceiling (fig6/fig7 topped out at 128 series x 1000 steps);
# the scale sweep below grows N*L 100x+ past it (DESIGN.md SS14).
PRIOR_CEILING_NL = 128 * 1000
SCALE_CELLS = ((512, 1000), (2048, 2048), (16384, 4096))


def scale_bench():
    """Synthetic scaling sweep toward the paper shape -> BENCH_scale.json
    (DESIGN.md SS14).

    Per cell (N series x L steps): time the per-series streaming kNN
    table build (the phase-1/phase-2 workhorse), the SHARDED build +
    device-side collective merge at several candidate-shard counts, and
    the merge alone device-vs-host — asserting sharded == unsharded
    BYTE-identity (idx and f32 dists) at every cell, the SS14 contract.
    N enters the recorded geometry and the extrapolations (per-series
    costs are N-independent after the mpEDM rework — DESIGN.md SS2), so
    the same harness runs unchanged at the paper's 100k-neuron scale on
    a real cluster; locally the largest cell is 16384 x 4096 = 524x the
    prior 128x1000 bench ceiling.

    EDM_SCALE_SMOKE=1 (CI's scale-smoke job, 2 spoofed devices): only
    the smallest cell and shard set — the identity gate without the
    wall-clock bill.
    """
    from repro.core import knn
    from repro.core.pipeline import (
        default_mesh,
        knn_tables_library_sharded,
        knn_tables_library_sharded_sim,
    )

    smoke = os.environ.get("EDM_SCALE_SMOKE") == "1"
    cells = SCALE_CELLS[:1] if smoke else SCALE_CELLS
    shard_counts = (2,) if smoke else (2, 4)
    W = len(jax.devices())
    mesh = default_mesh()
    out: dict = {
        "prior_ceiling_NL": PRIOR_CEILING_NL,
        "devices": W,
        "smoke": smoke,
        "cells": {},
    }
    for N, L in cells:
        cfg = EDMConfig(E_max=20)
        k = cfg.k_max
        # One representative series' lag matrix: per-series table cost is
        # N-independent, so one timed build extrapolates the whole brain.
        series = jnp.asarray(dummy_brain(1, L, seed=N)[0])
        Lp = cfg.n_points(L)
        V = lag_matrix(series, cfg.E_max, cfg.tau, Lp)
        tile_c = knn.resolve_stream_tile(Lp, cfg)
        reps = 1 if N * L > 10 * PRIOR_CEILING_NL else 3

        t_build = _time(
            lambda: knn.knn_tables_all_E_streaming(
                V, V, k, exclude_self=True, tile_c=tile_c
            ),
            reps=reps,
        )
        ref_i, ref_d = jax.block_until_ready(
            knn.knn_tables_all_E_streaming(V, V, k, exclude_self=True,
                                           tile_c=tile_c)
        )

        sharded: dict = {}
        # Real-mesh collective when this process has >1 device (CI's
        # scale-smoke spoofs 2); simulated shards cover the other counts.
        if W > 1:
            mi, md = jax.block_until_ready(
                knn_tables_library_sharded(V, V, k, cfg, exclude_self=True,
                                           mesh=mesh)
            )
            np.testing.assert_array_equal(np.asarray(mi), np.asarray(ref_i))
            np.testing.assert_array_equal(np.asarray(md), np.asarray(ref_d))
            t_mesh = _time(
                lambda: knn_tables_library_sharded(V, V, k, cfg,
                                                   exclude_self=True,
                                                   mesh=mesh),
                reps=reps,
            )
            sharded[f"mesh{W}"] = {"build_merge_s": t_mesh,
                                   "identical": True, "collective": True}
        for S in shard_counts:
            si, sd = jax.block_until_ready(
                knn_tables_library_sharded_sim(V, V, k, cfg,
                                               exclude_self=True, shards=S)
            )
            np.testing.assert_array_equal(np.asarray(si), np.asarray(ref_i))
            np.testing.assert_array_equal(np.asarray(sd), np.asarray(ref_d))
            t_sim = _time(
                lambda S=S: knn_tables_library_sharded_sim(
                    V, V, k, cfg, exclude_self=True, shards=S
                ),
                reps=reps,
            )
            sharded[f"sim{S}"] = {"build_merge_s": t_sim,
                                  "identical": True, "collective": False}

        # Merge-only, device tree vs host lexsort (+ the host round-trip
        # the SS14 bugfix removed): per-shard tables built once, reduced
        # both ways.
        S = shard_counts[-1]
        shard = -(-Lp // S)
        parts = [
            jax.block_until_ready(knn.knn_tables_all_E_streaming(
                V, V[:, s * shard : min((s + 1) * shard, Lp)],
                min(k, shard, Lp - s * shard), exclude_self=True,
                tile_c=tile_c, col_offset=s * shard,
                col_hi=min((s + 1) * shard, Lp),
            ))
            for s in range(S)
        ]
        idx_p = [p[0] for p in parts]
        d_p = [p[1] for p in parts]
        t_merge_dev = _time(lambda: knn.merge_topk_tree(idx_p, d_p, k),
                            reps=max(reps, 3))
        t0 = time.perf_counter()
        knn.merge_shard_tables([np.asarray(i) for i in idx_p],
                               [np.asarray(d) for d in d_p], k=k)
        t_merge_host = time.perf_counter() - t0

        cell = {
            "N": N, "L": L, "Lp": Lp, "E_max": cfg.E_max, "k": k,
            "NL": N * L, "ceiling_ratio": N * L / PRIOR_CEILING_NL,
            "tile_c": tile_c,
            "streaming_bytes": knn.streaming_bytes(
                Lp, k, tile_c, cfg.E_max),
            "knn_build_s": t_build,
            "sharded": sharded,
            "merge_device_s": t_merge_dev,
            "merge_host_s": t_merge_host,
            # Whole-brain extrapolations (per-series costs x N; the flat
            # worker grid divides them by the device count).
            "phase1_tables_extrapolated_s": t_build * N,
            "phase1_tables_per_512_workers_s": t_build * N / 512,
        }
        out["cells"][f"{N}x{L}"] = cell
        row(f"scale_{N}x{L}_knn_build", t_build,
            f"Lp={Lp};tile={tile_c};NL={N * L}"
            f";ceiling_x={cell['ceiling_ratio']:.0f}")
        for sk, sv in sharded.items():
            row(f"scale_{N}x{L}_sharded_{sk}", sv["build_merge_s"],
                "identical=True")
        row(f"scale_{N}x{L}_merge", t_merge_dev,
            f"host={t_merge_host * 1e6:.0f}us;"
            f"device_vs_host={t_merge_host / max(t_merge_dev, 1e-9):.1f}x")

    # Paper-shape model: per-series build scales as E_max * Lp^2 (the
    # streaming distance sweep); calibrate the constant on the largest
    # measured cell and project the paper's two headline datasets.
    big = out["cells"][f"{cells[-1][0]}x{cells[-1][1]}"]
    c0 = big["knn_build_s"] / (big["E_max"] * big["Lp"] ** 2)
    for name, (Np, Lraw) in {"fish1_normo": (53053, 1450),
                             "subject11": (101729, 8528)}.items():
        Lpp = Lraw - (20 - 1) - 1
        t_series = c0 * 20 * Lpp ** 2
        out[f"model_{name}"] = {
            "N": Np, "L": Lraw,
            "phase1_tables_s_1core": t_series * Np,
            "phase1_tables_s_512_workers": t_series * Np / 512,
        }
        row(f"scale_model_{name}", t_series * Np / 512,
            "per_512_workers_extrapolated")
    _write_bench("BENCH_scale.json", out)


BENCHES = {
    "table2": table2_speedup,
    "fig6": fig6_scaling_N,
    "fig7": fig7_scaling_L,
    "fig8": fig8_breakdown,
    "fig9": fig9_multiE_kernel,
    "fig9b": fig9b_knn_impl_variants,
    "fig3": fig3_strong_scaling,
    "phase2": phase2_engine_bench,
    "knn": knn_selection_bench,
    "significance": significance_bench,
    "roofline": roofline_summary,
    "scale": scale_bench,
}


# --------------------------------------------- bench regression gate (CI)
#: bench name -> (baseline JSON, gated timing fields as key paths).
#: Gated fields are WALL TIMES ONLY — derived ratios (speedups) divide
#: out machine speed and working-set bytes are deterministic, so a
#: straight fresh/baseline ratio on the timings is the regression signal.
GATES: dict[str, tuple[str, list[tuple[str, ...]]]] = {
    "phase2": (
        "BENCH_phase2.json",
        [("seed_path", "phase2_s"), ("new_path", "phase2_s"),
         ("tiled_path", "phase2_s")],
    ),
    "knn": (
        "BENCH_knn.json",
        [("phase1", "auto_s"),
         ("engines", "reference", "64000", "stream_s"),
         ("engines", "pallas-interpret", "16000", "stream_s")],
    ),
    "significance": (
        "BENCH_significance.json",
        [("one_sweep_chunk_s",), ("rebuild_chunk_s",)],
    ),
}
# Absolute wall-time gate (the committed contract).  Baselines are only
# meaningful for the machine class they were measured on: promote a
# bench-gate run's uploaded fresh JSONs to the committed baselines the
# first time the gate runs on a new runner class, rather than comparing
# a CI runner against a workstation.  BENCH_GATE_LIMIT overrides the
# ratio for machines with known constant offsets.
SLOWDOWN_LIMIT = float(os.environ.get("BENCH_GATE_LIMIT", "1.5"))
# knn-gate margin: streaming must stay at-or-below the slab baseline at
# EVERY benched Lc on both engines; the margin absorbs shared-runner
# timer noise on the cells where the two layouts are genuinely tied
# (single-tile small-Lc cells degenerate to the same computation).
KNN_STREAM_MARGIN = float(os.environ.get("KNN_STREAM_MARGIN", "1.15"))


def _dig(d: dict, path: tuple[str, ...]) -> float:
    for k in path:
        d = d[k]
    return float(d)


def _knn_stream_gate(base: dict, fresh: dict, floor: dict,
                     summary: list | None = None) -> bool:
    """The knn-gate (DESIGN.md SS8): fresh streaming build time must beat
    the slab baseline at every benched Lc on both engines — both the
    slab timed fresh in the same run (same-machine, noise-free yardstick)
    and the committed recorded baseline (drift contract, with the usual
    SLOWDOWN_LIMIT machine allowance).  Retry passes keep the BEST
    streaming observation per cell via ``floor``."""
    ok = True
    for engine, rows in fresh.get("engines", {}).items():
        for lc, r in sorted(rows.items(), key=lambda kv: int(kv[0])):
            key = f"BENCH_knn.json:knn-gate.{engine}.Lc{lc}"
            f = min(float(r["stream_s"]), floor.get(key, float("inf")))
            floor[key] = f
            slab_fresh = float(r["slab_s"])
            slab_base = float(
                base.get("engines", {}).get(engine, {}).get(lc, {}).get(
                    "slab_s", slab_fresh
                )
            )
            limit = max(
                slab_fresh * KNN_STREAM_MARGIN, slab_base * SLOWDOWN_LIMIT
            )
            verdict = "OK" if f <= limit else "STREAM_SLOWER_THAN_SLAB"
            ok = ok and verdict == "OK"
            if summary is not None:
                summary.append({
                    "gate": key, "bench": "knn", "kind": "knn-stream",
                    "fresh_s": f, "slab_fresh_s": slab_fresh,
                    "slab_base_s": slab_base, "limit_s": limit,
                    "verdict": verdict,
                })
            print(
                f"gate,{key},stream={f:.3f}s;slab_fresh={slab_fresh:.3f}s;"
                f"slab_base={slab_base:.3f}s;{verdict}"
            )
    return ok


def check_regressions(names: list[str], floor: dict | None = None,
                      summary: list | None = None) -> list[str]:
    """Compare fresh BENCH_DIR timings against committed repo-root
    baselines; print one verdict row per gated field and return the
    bench names with violations (>SLOWDOWN_LIMIT x).  ``floor`` carries
    the best fresh timing seen so far per field across retry passes —
    shared-runner wall clocks are noisy, so a field only regresses if
    its BEST observation is slow.  ``summary`` (when given) collects one
    machine-readable entry per gate row for CHECK_summary.json."""
    bad: list[str] = []
    floor = {} if floor is None else floor
    for name in names:
        if name not in GATES:
            continue
        fname, fields = GATES[name]
        base_f, fresh_f = REPO / fname, BENCH_DIR / fname
        if not base_f.exists():
            print(f"gate,{fname},SKIP_no_committed_baseline")
            continue
        base = json.loads(base_f.read_text())
        fresh = json.loads(fresh_f.read_text())
        for path in fields:
            key = f"{fname}:{'.'.join(path)}"
            b = _dig(base, path)
            f = min(_dig(fresh, path), floor.get(key, float("inf")))
            floor[key] = f
            ratio = f / b if b > 0 else float("inf")
            verdict = "OK" if ratio <= SLOWDOWN_LIMIT else "REGRESSION"
            if verdict != "OK" and name not in bad:
                bad.append(name)
            if summary is not None:
                summary.append({
                    "gate": key, "bench": name, "kind": "drift",
                    "base_s": b, "fresh_s": f, "ratio": ratio,
                    "verdict": verdict,
                })
            print(
                f"gate,{key},"
                f"base={b:.3f}s;fresh={f:.3f}s;ratio={ratio:.2f}x;{verdict}"
            )
        if name == "knn" and not _knn_stream_gate(base, fresh, floor,
                                                 summary):
            if name not in bad:
                bad.append(name)
    return bad


def main() -> None:
    global BENCH_DIR
    args = sys.argv[1:]
    check = "--check" in args
    bad_flags = [a for a in args if a.startswith("--") and a != "--check"]
    if bad_flags:
        # A typo'd --check must fail loudly, not silently skip the gate.
        sys.exit(f"unknown option(s) {bad_flags}; the only flag is --check")
    names = [a for a in args if not a.startswith("--")] or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        sys.exit(f"unknown bench(es) {unknown}; available: {list(BENCHES)}")
    if check:
        gated = [n for n in names if n in GATES]
        if not gated:
            sys.exit(f"--check needs at least one gated bench: {list(GATES)}")
        BENCH_DIR = RESULTS / "fresh"  # keep committed baselines untouched
        # Clear THIS run's gated artifacts up front: a stale fresh JSON
        # from an aborted earlier run must never shadow the bench we are
        # about to (re)run — the gate would silently compare old numbers.
        for name in gated:
            stale = BENCH_DIR / GATES[name][0]
            if stale.exists():
                stale.unlink()
        (BENCH_DIR / "CHECK_summary.json").unlink(missing_ok=True)
    from repro.runtime.platform import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    for name in names:
        BENCHES[name]()
    if check:
        floor: dict = {}
        summary: list = []
        bad = check_regressions(names, floor, summary)
        if bad:
            # One retry of only the offending benches: transient runner
            # noise clears (best-of-2 per field), real regressions persist.
            print(f"gate,retry,rerunning_{'+'.join(bad)}_once")
            for name in bad:
                BENCHES[name]()
            summary = [e for e in summary if e["bench"] not in bad]
            bad = check_regressions(bad, floor, summary)
        # Machine-readable per-bench delta summary, uploaded with the
        # fresh JSONs so a regression (or a promotable speedup) can be
        # triaged from the artifact alone.
        (BENCH_DIR / "CHECK_summary.json").write_text(json.dumps({
            "slowdown_limit": SLOWDOWN_LIMIT,
            "knn_stream_margin": KNN_STREAM_MARGIN,
            "benches": names,
            "gates": summary,
            "failed": bad,
            "passed": not bad,
        }, indent=1))
        if bad:
            sys.exit(
                f"bench regression gate FAILED: {bad} slower than "
                f"{SLOWDOWN_LIMIT}x baseline (see gate rows above; refresh "
                "baselines by rerunning without --check and committing the "
                "repo-root BENCH_*.json)"
            )
        print(f"gate,all,within_{SLOWDOWN_LIMIT}x_of_baselines")


if __name__ == "__main__":
    main()
