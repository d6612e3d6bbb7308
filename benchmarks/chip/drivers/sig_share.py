"""The significance stage over one node's share of library rows: the sig
work unit a fleet worker runs.

Set-up (``setup_s``): the recording is made on the device from the seed;
optE comes from a seeded placement of the configuration's histogram (the
same counts, so the same bucket plan and compiled programs, for every
seed); the share's observed rho comes from the program's
``run_phase2_chunks``, as a fleet worker's phase 2 leaves it; the
program's ``SignificanceChunkRunner`` is built with a
``SignificanceConfig`` seeded from the run's seed; and the first
``stream_depth`` chunks run through the timed call itself, so every
program the window drives is loaded or compiled before it opens.

Window: ``repro.inference.pipeline.run_sig_chunks`` with an explicit
chunk plan that cycles through the share's rows until ``seconds`` have
passed; the last chunk dispatched in time is finished, and the window
runs from the first timed dispatch to that chunk's durable p-value write.
Blocks go through the program's three ``TileWriter``s (``rho_conv``,
``rho_trend``, ``pvals``) in a temporary directory.

Check: a seeded sample of rows written in the window and of targets
spread over every optE bucket, read back from the writers' files, against
``reference_sig`` on the recording made again from the seed after the
program's state is gone.  A pair whose reference null, or whose pair of
curve points, lies within ``near_tie`` of the other side is left out of
the p-value or trend comparison and counted.
"""
from __future__ import annotations

import pathlib
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

import recording
import reference
import reference_sig
import trace_modules
import trace_reduce
from repro.core import ccm
from repro.core.pipeline import run_phase2_chunks
from repro.core.types import EDMConfig
from repro.data.store import TileWriter
from repro.inference import SignificanceChunkRunner, SignificanceConfig
from repro.inference.pipeline import run_sig_chunks
from repro.runtime import telemetry

if str(pathlib.Path(__file__).parent) not in sys.path:
    sys.path.insert(0, str(pathlib.Path(__file__).parent))
from ccm_share import Result, _Plan  # noqa: E402  the phase-2 cell's own

ARTIFACTS = ("rho_conv", "rho_trend", "pvals")


class _Writer(TileWriter):
    """The program's TileWriter, with the time each chunk's last tile
    became durable and whether every tile of the chunk was finite."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.finite: dict[int, bool] = {}
        self.writes: list[tuple[int, float, bool]] = []

    def write_tile(self, row0, col0, block, commit=True):
        super().write_tile(row0, col0, block, commit=commit)
        ok = self.finite.get(row0, True) and bool(np.isfinite(block).all())
        self.finite[row0] = ok
        if commit:  # the chunk's last tile
            self.writes.append((row0, time.perf_counter(), ok))
            self.finite.pop(row0)


def _sample_cols(rng, optE: np.ndarray, n: int) -> np.ndarray:
    """n targets spread evenly over the optE buckets."""
    Es = np.unique(optE)
    per = max(1, n // len(Es))
    out = []
    for e in Es:
        ids = np.flatnonzero(optE == e)
        out += list(rng.choice(ids, size=min(per, ids.size), replace=False))
    return np.sort(np.asarray(out, np.int64))


def _read(writer_dir, rows, cols, chunk, tile, inv):
    """Values of (rows x cols) from a writer's tile files (columns on
    disk in bucket order), NaN where a file is missing; and how many of
    the rows had a file missing."""
    got = np.full((len(rows), len(cols)), np.nan, np.float32)
    missing = 0
    pos = inv[cols]
    for i, r in enumerate(rows):
        r0 = r // chunk * chunk
        lost = False
        for c0 in np.unique(pos // tile * tile):
            f = writer_dir / f"tile_{r0:08d}_{c0:08d}.npy"
            sel = np.flatnonzero(pos // tile * tile == c0)
            if f.exists():
                got[i, sel] = np.load(f)[r - r0, pos[sel] - c0]
            else:
                lost = True
        missing += lost
    return got, missing


def run(ctx) -> Result:
    c, tr = ctx.config, ctx.traffic
    n_dev = tr["devices"]
    if len(ctx.devices) < n_dev:
        raise ValueError(f"traffic needs {n_dev} devices, has {len(ctx.devices)}")
    devices = ctx.devices[:n_dev]
    if "significance" in c:  # a configuration that states the analysis
        stated = dict(c["significance"], check=c["check"])
        run_as = {k: tr[k] for k in stated}
        if run_as != stated:
            raise ValueError(f"traffic {run_as} is not the configuration's "
                             f"{stated}")
    mesh = Mesh(np.array(devices), ("workers",))
    cfg = EDMConfig(
        E_max=c["E_max"], tau=c["tau"], Tp=c["Tp"],
        exclude_self=c["exclude_self"], lib_block=c["lib_block"],
        stream_depth=c["stream_depth"], engine=tr["engine"],
        bucketed=tr["bucketed"], target_tile=tr["target_tile"],
        **ctx.cfg_overrides,
    )
    sig = SignificanceConfig(
        lib_sizes=tuple(tr["lib_sizes"]), n_surrogates=tr["n_surrogates"],
        alpha=tr["alpha"], surrogate=tr["surrogate"], seed=int(ctx.seed),
    )
    N, L, S = c["N"], c["L"], c["library_rows"]
    Lp = cfg.n_points(L)
    chunk = mesh.size * cfg.lib_block
    m = sig.n_surrogates

    sink = None
    if ctx.trace:
        sink = telemetry.MemorySink()
        telemetry.configure(sink)

    # ---- set-up: data on the device, observed rho of the share, runner
    key = recording.seed_key(ctx.seed)
    ts_dev = recording.recording(key, N=N, L=L)
    fut = np.asarray(ccm.all_futures(ts_dev, cfg))
    ts = np.asarray(ts_dev)
    del ts_dev
    optE = recording.draw_optE(ctx.seed, c["optE_histogram"]["counts"], N)
    rho = np.zeros((S, N), np.float32)
    run_phase2_chunks(ts[:S], fut, optE, cfg, mesh,
                      [(r, min(chunk, S - r)) for r in range(0, S, chunk)],
                      rho=rho)
    del fut
    runner = SignificanceChunkRunner(ts, optE, cfg, sig, mesh)

    one = jax.jit(lambda x: x + 1)
    zeros = [jax.device_put(np.int32(0), d) for d in devices]
    for z in zeros:
        one(z).block_until_ready()

    window = types.SimpleNamespace(trace_dir=ctx.tmp / "trace", anchor=None)

    def open_window() -> float:
        for z in zeros:
            one(z).block_until_ready()
        window.compiles0 = ctx.counter.mark()
        if ctx.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(window.trace_dir),
                                     profiler_options=opts)
            window.anchor = time.monotonic()
            with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
                pass
        return time.perf_counter()

    writers = {}
    for name in ARTIFACTS:
        writers[name] = _Writer(ctx.tmp / name, S, N, stage="sig")
        writers[name].ensure_col_order(runner.order)
    plan = _Plan(S, chunk, cfg.stream_depth, ctx.seconds, open_window)
    run_sig_chunks(runner, plan, rho, writers["rho_conv"],
                   writers["rho_trend"], writers["pvals"])
    compiles1 = ctx.counter.mark()

    K = len(plan.dispatched)
    timed = writers["pvals"].writes[plan.warm:]
    t_end = timed[-1][1] if timed else time.perf_counter()
    window_s = t_end - plan.t0
    good = sum(1 for i in range(K)
               if all(len(w.writes) > plan.warm + i
                      and w.writes[plan.warm + i][2]
                      for w in writers.values()))
    pairs = sum(rows for _, _, rows in plan.dispatched) * N
    in_window = compiles1[0] - window.compiles0[0]
    ctx.log(f"window: {K} chunks, {window_s:.3f} s, null tile {runner.T} "
            f"({len(runner.tile_plans)} tiles a chunk), compiles in window "
            f"{in_window}, in set-up {window.compiles0[0]} "
            f"({window.compiles0[2]:.3f} s), cache hits {compiles1[1]}")

    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    limit = max(int(s.get("bytes_limit", 0)) for s in stats)

    trace = modules = None
    if ctx.trace:
        jax.profiler.stop_trace()
        telemetry.configure()
        mono1 = window.anchor + window_s
        spans = [(f"{r['stage']}/{r['name']}", r["mono"] - r["dur_s"],
                  r["mono"], r["attrs"]) for r in sink.records
                 if r["kind"] == "span"]
        profile = trace_reduce.load(trace_reduce.find_xplane(window.trace_dir))
        trace = trace_reduce.reduce_profile(
            profile, window.anchor, mono1,
            host_spans=[(n, a, b) for n, a, b, _ in spans],
            n_devices=len(devices))
        modules = trace_modules.module_seconds(profile, window.anchor, mono1,
                                               n_devices=len(devices))
        del profile
        window.spans = [s for s in spans
                        if s[2] >= window.anchor and s[1] <= mono1]

    # ---- check: read back a seeded sample, free the program's state,
    # then the reference on the same recording made again.
    ck = tr["check"]
    rng = np.random.default_rng([int(ctx.seed), 2])
    rows_done = sorted({r0 + i for _, r0, n in plan.dispatched
                        for i in range(n)})
    rows = sorted(int(r) for r in rng.choice(
        rows_done, size=min(ck["rows"], len(rows_done)), replace=False))
    cols = _sample_cols(rng, optE, ck["targets"])
    inv = np.argsort(runner.order)
    got, missing = {}, 0
    for name, w in writers.items():
        got[name], miss = _read(w.dir, rows, cols, chunk, runner.T, inv)
        missing = max(missing, miss)
    del runner, writers, ts, rho
    ts_dev = recording.recording(key, N=N, L=L)
    lib_ref = np.asarray(ts_dev[jnp.asarray(rows)])
    ys = ts_dev[jnp.asarray(cols)]
    del ts_dev
    t_ref = time.perf_counter()
    kw = dict(E_max=c["E_max"], tau=c["tau"], Tp=c["Tp"],
              exclude_self=c["exclude_self"])
    fut_ref = reference.futures(ys, c["E_max"], c["tau"], c["Tp"])
    rho_ref = reference.rho_rows(lib_ref, fut_ref, optE[cols], **kw)
    surr = reference_sig.surrogates(ctx.seed, ys, cols, m)
    surr_fut = reference.futures(surr.reshape(-1, L), c["E_max"], c["tau"],
                                 c["Tp"])
    null = reference.rho_rows(lib_ref, surr_fut, np.repeat(optE[cols], m),
                              **kw).reshape(len(rows), len(cols), m)
    p_ref = reference_sig.pvalues(rho_ref, null)
    perm = reference_sig.permutation(ctx.seed, Lp)
    curves = reference_sig.curves(lib_ref, fut_ref, optE[cols], perm,
                                  sig.lib_sizes, **kw)
    drho_ref, trend_ref, gaps = reference_sig.convergence(curves)
    ctx.log(f"check: {len(rows)} rows x {len(cols)} targets x {m} "
            f"surrogates, reference {time.perf_counter() - t_ref:.3f} s")

    tie = ck["near_tie"]
    p_tie = np.abs(null - rho_ref[..., None]).min(axis=-1) < tie
    p_bad = (np.rint(got["pvals"] * (m + 1)) != np.rint(p_ref * (m + 1)))
    n_pairs = len(sig.lib_sizes) * (len(sig.lib_sizes) - 1) // 2
    t_tie = gaps < tie
    t_bad = ~(np.abs(got["rho_trend"] - trend_ref) <= 1.0 / n_pairs)
    d = np.abs(got["rho_conv"].astype(np.float64) - drho_ref)
    drho_gap = float(np.max(d)) if np.isfinite(d).all() else float("inf")
    check = {
        "pval_mismatches": (int(np.sum(p_bad & ~p_tie)), 0),
        # a comparison that leaves most pairs out would prove nothing
        "pval_near_ties": (int(np.sum(p_tie)), len(rows) * len(cols) // 2),
        "trend_mismatches": (int(np.sum(t_bad & ~t_tie)), 0),
        "drho_max_abs_diff": (drho_gap, ck["drho_max_abs_diff_limit"]),
        "failed_chunks": (K - good, 0),
        "sample_rows_missing": (missing, 0),
    }
    correct = all(v <= lim for v, lim in check.values())
    counts = recording.histogram_counts(c["optE_histogram"]["counts"], N)
    window.__dict__.update(
        window_s=window_s, chunks=K, chunk_rows=chunk, rows=pairs // N,
        N=N, Lp=Lp, lib_sizes=sig.lib_sizes, m=m, optE_counts=counts,
        trace=trace, modules=modules, memory=stats, peak_bytes=peak,
        bytes_limit=limit, peaks=ctx.peaks, devices=len(devices),
    )
    return Result(
        correct=correct, attempted=K, failed=K - good,
        end_to_end={"pairs_per_s": pairs / window_s,
                    "setup_s": plan.t0 - ctx.t_start},
        check=check, memory_peak_bytes=peak, trace=trace, window=window,
    )
