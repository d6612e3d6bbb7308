"""Phase-2 CCM over one node's share of library rows: the work unit a
fleet worker runs.

Set-up (``setup_s``): the recording is made on the device from the seed,
its futures come from the program's ``ccm.all_futures`` and go to the
host as a fleet worker holds them, optE comes from a seeded placement of
the configuration's histogram (the same counts, so the same bucket plan
and the same compiled programs, for every seed), and the first
``stream_depth`` chunks run through the timed call itself, so every
program the window drives is loaded or compiled before it opens.

Window: ``repro.core.pipeline.run_phase2_chunks`` with an explicit chunk
plan that cycles through the share's rows until ``seconds`` have passed;
the last chunk dispatched in time is finished, and the window runs from
the first timed dispatch to that chunk's durable write.  Blocks go
through a ``TileWriter`` in a temporary directory.

Check: a seeded sample of the rows written in the window, read back from
the writer's files, against ``reference.rho_rows`` on the recording made
again from the seed after the program's state is gone.
"""
from __future__ import annotations

import dataclasses
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

import recording
import reference
import trace_reduce
from repro.core import ccm
from repro.core.pipeline import run_phase2_chunks
from repro.core.types import EDMConfig
from repro.data.store import TileWriter
from repro.runtime import telemetry


@dataclasses.dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict
    check: dict
    memory_peak_bytes: int
    trace: dict | None
    window: types.SimpleNamespace


class _Plan:
    """(row0, rows) chunks cycling through the share: ``warm`` chunks of
    set-up, then chunks until ``seconds`` have passed since the window
    opened (at least one)."""

    def __init__(self, share: int, chunk: int, warm: int, seconds: float,
                 open_window):
        self.cycle = [(r, min(chunk, share - r)) for r in range(0, share, chunk)]
        self.warm, self.seconds, self.open_window = warm, seconds, open_window
        self.dispatched: list[tuple[float, int, int]] = []
        self.t0 = None

    def __iter__(self):
        n = len(self.cycle)
        for i in range(self.warm):
            yield self.cycle[i % n]
        self.t0 = self.open_window()
        i = self.warm
        while not self.dispatched or time.perf_counter() < self.t0 + self.seconds:
            row0, rows = self.cycle[i % n]
            i += 1
            self.dispatched.append((time.perf_counter(), row0, rows))
            yield row0, rows


class _Writer(TileWriter):
    """The program's TileWriter, with the time each block became durable
    and whether it was finite."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.writes: list[tuple[int, int, float, bool]] = []

    def write_block(self, row0, rho_rows):
        super().write_block(row0, rho_rows)
        t = time.perf_counter()
        self.writes.append((row0, rho_rows.shape[0], t,
                            bool(np.isfinite(rho_rows).all())))


def _sample_rows(rng, rows: list[int], chunk: int, lib_block: int,
                 n: int) -> list[int]:
    """n rows drawn from ``rows``, spread over the chunk's device slots
    (row position // lib_block), so every device's part is checked."""
    slots: dict[int, list[int]] = {}
    for r in rows:
        slots.setdefault((r % chunk) // lib_block, []).append(r)
    per = max(1, n // len(slots))
    out = []
    for s in sorted(slots):
        out += list(rng.choice(slots[s], size=min(per, len(slots[s])),
                               replace=False))
    return sorted(int(r) for r in out)


def run(ctx) -> Result:
    c, tr = ctx.config, ctx.traffic
    n_dev = tr["devices"]
    if len(ctx.devices) < n_dev:
        raise ValueError(f"traffic needs {n_dev} devices, has {len(ctx.devices)}")
    devices = ctx.devices[:n_dev]
    mesh = Mesh(np.array(devices), ("workers",))
    cfg = EDMConfig(
        E_max=c["E_max"], tau=c["tau"], Tp=c["Tp"],
        exclude_self=c["exclude_self"], lib_block=c["lib_block"],
        stream_depth=c["stream_depth"], engine=tr["engine"],
        bucketed=tr["bucketed"], target_tile=tr["target_tile"],
        **ctx.cfg_overrides,
    )
    N, L, S = c["N"], c["L"], c["library_rows"]
    Lp = cfg.n_points(L)
    chunk = mesh.size * cfg.lib_block

    sink = None
    if ctx.trace:
        sink = telemetry.MemorySink()
        telemetry.configure(sink)

    # ---- set-up: data on the device, futures to the host, optE drawn
    key = recording.seed_key(ctx.seed)
    ts_dev = recording.recording(key, N=N, L=L)
    fut = np.asarray(ccm.all_futures(ts_dev, cfg))
    lib = np.asarray(ts_dev[:S])
    del ts_dev
    optE = recording.draw_optE(ctx.seed, c["optE_histogram"]["counts"], N)

    one = jax.jit(lambda x: x + 1)
    zeros = [jax.device_put(np.int32(0), d) for d in devices]
    for z in zeros:
        one(z).block_until_ready()

    window = types.SimpleNamespace(trace_dir=ctx.tmp / "trace", anchor=None)

    def open_window() -> float:
        # Every warm chunk has finished on every device before the
        # window opens: each device runs its programs in order.
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

    writer = _Writer(ctx.tmp / "rho", S, N)
    plan = _Plan(S, chunk, cfg.stream_depth, ctx.seconds, open_window)
    run_phase2_chunks(lib, fut, optE, cfg, mesh, plan, writer=writer)
    compiles1 = ctx.counter.mark()

    timed = writer.writes[plan.warm:]
    K = len(plan.dispatched)
    t_end = timed[-1][2] if timed else time.perf_counter()
    window_s = t_end - plan.t0
    lat = [w[2] - d[0] for d, w in zip(plan.dispatched, timed)]
    good = sum(1 for w in timed if w[3])
    pairs = sum(rows for _, _, rows in plan.dispatched) * N
    in_window = compiles1[0] - window.compiles0[0]
    ctx.log(f"window: {K} chunks, {window_s:.3f} s, compiles in window "
            f"{in_window}, in set-up {window.compiles0[0]} "
            f"({window.compiles0[2]:.3f} s), cache hits {compiles1[1]}")

    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    limit = max(int(s.get("bytes_limit", 0)) for s in stats)

    trace = None
    if ctx.trace:
        jax.profiler.stop_trace()
        telemetry.configure()
        mono1 = window.anchor + window_s
        spans = [(f"{r['stage']}/{r['name']}", r["mono"] - r["dur_s"],
                  r["mono"], r["attrs"]) for r in sink.records
                 if r["kind"] == "span"]
        trace = trace_reduce.reduce_dir(
            window.trace_dir, window.anchor, mono1,
            host_spans=[(n, a, b) for n, a, b, _ in spans],
            n_devices=len(devices))
        window.spans = [s for s in spans
                        if s[2] >= window.anchor and s[1] <= mono1]

    # ---- check: read back a seeded sample, free the program's state,
    # then the reference on the same recording made again.
    rng = np.random.default_rng([int(ctx.seed), 2])
    rows_done = sorted({r0 + i for _, r0, n in plan.dispatched
                        for i in range(n)})
    rows = _sample_rows(rng, rows_done, chunk, cfg.lib_block,
                        c["check"]["rows"])
    n_t = c["check"]["targets"] or N
    cols = np.sort(rng.choice(N, size=n_t, replace=False)) if n_t < N \
        else np.arange(N)
    got = np.full((len(rows), len(cols)), np.nan, np.float32)
    missing = 0
    for i, r in enumerate(rows):
        r0 = r // chunk * chunk
        f = writer.dir / f"rows_{r0:08d}.npy"
        if f.exists():
            got[i] = np.load(f)[r - r0, cols]
        else:
            missing += 1
    del fut, lib, writer
    ts_dev = recording.recording(key, N=N, L=L)
    lib_ref = np.asarray(ts_dev[jnp.asarray(rows)])
    fut_ref = reference.futures(ts_dev[jnp.asarray(cols)], c["E_max"],
                                c["tau"], c["Tp"])
    del ts_dev
    t_ref = time.perf_counter()
    want = reference.rho_rows(lib_ref, fut_ref, optE[cols], E_max=c["E_max"],
                              tau=c["tau"], Tp=c["Tp"],
                              exclude_self=c["exclude_self"])
    diff = np.abs(got.astype(np.float64) - want)
    gap = float(np.max(diff)) if np.isfinite(diff).all() else float("inf")
    ctx.log(f"check: {len(rows)} rows x {len(cols)} targets, reference "
            f"{time.perf_counter() - t_ref:.3f} s")

    check = {
        "rho_max_abs_diff": (gap, c["check"]["rho_max_abs_diff_limit"]),
        "failed_chunks": (K - good, 0),
        "sample_rows_missing": (missing, 0),
    }
    correct = all(v <= lim for v, lim in check.values())
    window.__dict__.update(
        window_s=window_s, chunks=K, chunk_rows=chunk, rows=pairs // N,
        N=N, Lp=Lp, optE_counts=recording.histogram_counts(
            c["optE_histogram"]["counts"], N),
        trace=trace, memory=stats, peak_bytes=peak, bytes_limit=limit,
        peaks=ctx.peaks, devices=len(devices),
    )
    return Result(
        correct=correct, attempted=K, failed=K - good,
        end_to_end={
            "pairs_per_s": pairs / window_s,
            "chunk_p90_s": float(np.percentile(lat, 90)) if lat else None,
            "setup_s": plan.t0 - ctx.t_start,
        },
        check=check, memory_peak_bytes=peak, trace=trace, window=window,
    )
