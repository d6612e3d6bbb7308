"""Run the EDM main path on one TPU chip and check what comes out.

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips  # four chips: the mesh path only

One chip:
  (a) CLI: ``edm_run --platform tpu --workers 1`` on a 512 x 1,450
      synthetic recording with convergence library sizes and surrogates,
      writing the causal map, the significance stage and ``edges/``.  The
      driver stays off the chip; its one worker process holds it.
  (b) Main path at Fish1_Normo width (N 53,053, L 1,450, E_max 20) with
      engine ``pallas-compiled``: one chip's share of the paper's
      512-worker decomposition, ceil(53,053 / 512) = 104 library rows
      against all N targets: ``run_phase1`` on the share's rows (the
      other targets' optE are drawn, seeded, from the share's optE
      histogram: phase 1 over all N rows does not fit the smoke's time
      limit), then ``run_phase2_chunks`` with an explicit chunk plan and a
      TileWriter — what a fleet worker runs per work unit.
  (c) Checks on the same rows: ``pallas-compiled`` against ``reference``,
      both on the chip (kNN indices equal, rho within RHO_TOL_CHIP), and a
      sample against ``reference`` on the host CPU backend of this process
      (indices equal, rho within RHO_TOL_CPU).

Four chips: the share of (b) on a 4-device mesh against the same rows on
one device, and ``knn_tables_library_sharded`` on four devices against
the unsharded build: both byte-identical, the repo's own contract.

Data comes from ``dummy_brain`` with a fixed seed.  The script exits
non-zero and prints no result line when JAX finds no TPU or any phase
fails.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
a JSON summary of every number printed goes to chiprun_out/.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import pathlib
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"
OUT = ROOT / "chiprun_out"

SEED = 0
N_FISH1, L_FISH1 = 53_053, 1_450  # Fish1_Normo, benchmarks/chip/configs/fish1_normo.json
L_SUBJECT11 = 8_528  # Subject11
E_MAX = 20
SHARE = -(-N_FISH1 // 512)  # 104 library rows: one of 512 workers
CLI_SHAPE = "512x1450"
CLI_LIB_SIZES = "300,700,1400"
CLI_SURROGATES = 20
PHASE1_LIB_BLOCK = 64  # phase-1 rows per device per chunk
CPU_ROWS, CPU_TARGETS = 3, 256
# rho tolerances.  kNN indices must match exactly: both engines compute
# every distance with the same IEEE f32 sequence (subtract, square,
# max 0, add), so neighbours and their order agree bit for bit.  rho may
# differ only by f32 rounding: on the chip the engines share the weights
# and differ in the order the lookup sums its k = 21 products (about
# 1e-7 relative per prediction); against the CPU the weights' exp, sqrt
# and division also round differently.  Pearson over Lp = 1,430 points
# turns per-prediction errors of that size into |drho| of order 1e-6.
# A wrong neighbour moves rho by 1e-3 or more, so 1e-4 separates the two.
RHO_TOL_CHIP = 1e-4
RHO_TOL_CPU = 1e-4

_SUMMARY: dict = {}


def say(key: str, value) -> None:
    _SUMMARY[key] = value
    print(f"{key}: {value}", flush=True)


class Fail(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise Fail(msg)


class CompileCounter:
    """Counts backend compiles and persistent-cache hits through JAX's
    monitoring events; ``seconds`` is the time spent compiling."""

    def __init__(self):
        import jax

        self.n = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.n, self.hits, self.seconds


class Phase:
    """Wall time of a phase split into compile and steady state."""

    def __init__(self, name: str, counter: CompileCounter):
        self.name, self.counter = name, counter

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.counter.mark()
        return self

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        wall = time.perf_counter() - self.t0
        n, hits, secs = (a - b for a, b in zip(self.counter.mark(), self.c0))
        say(f"{self.name}.wall_s", round(wall, 3))
        say(f"{self.name}.compile_s", round(secs, 3))
        say(f"{self.name}.steady_s", round(wall - secs, 3))
        say(f"{self.name}.compiles", n)
        say(f"{self.name}.cache_hits", hits)
        return False


# ------------------------------------------------------------ phase (a)
def phase_cli(tmp: pathlib.Path, tier: str = "tpu", shape: str = CLI_SHAPE,
              lib_sizes: str = CLI_LIB_SIZES,
              surrogates: int = CLI_SURROGATES, e_max: int = E_MAX) -> None:
    """edm_run through its CLI in a child; this process stays off JAX."""
    from repro.runtime import integrity

    out = tmp / "cli"
    cmd = [sys.executable, "-m", "repro.launch.edm_run", "--platform", tier,
           "--workers", "1", "--synthetic", shape, "--e-max", str(e_max),
           "--lib-sizes", lib_sizes, "--surrogates", str(surrogates),
           "--seed", str(SEED), "--out", str(out)]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(SRC), env.get("PYTHONPATH")) if p
    )
    t0 = time.perf_counter()
    # Own process group: on a timeout the driver AND its fleet worker go,
    # so no process is left holding the chip.
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=600)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.communicate()
        raise Fail("edm_run did not finish in 600 s")
    say("cli.wall_s", round(time.perf_counter() - t0, 3))
    tail = (stdout + stderr)[-3000:]
    check(proc.returncode == 0, f"edm_run exited {proc.returncode}:\n{tail}")
    edges_line = [ln for ln in stdout.splitlines() if "edges at FDR" in ln]
    check(bool(edges_line), f"edm_run printed no edge count:\n{tail}")
    say("cli.significance", edges_line[-1].strip())
    N = int(shape.split("x")[0])
    rho = np.load(out / "causal_map" / "data.npy", mmap_mode="r")
    check(rho.shape == (N, N), f"causal map shape {rho.shape}")
    check(bool(np.isfinite(rho).all()), "causal map holds non-finite rho")
    pv = np.load(out / "pvals" / "data.npy", mmap_mode="r")
    check(bool(((pv > 0) & (pv <= 1)).all()), "p-values outside (0, 1]")
    check((out / "edges").is_dir(), "no edges/ written")
    report = integrity.fsck_store(out)
    check(report["clean"], f"fsck of the CLI store: {report}")
    say("cli.store",
        f"causal_map {rho.shape}, pvals, edges/ written; fsck clean")


# ------------------------------------------------------------ phase (b)
def _read_rows(writer_dir: pathlib.Path, plan) -> np.ndarray:
    return np.concatenate(
        [np.load(writer_dir / f"rows_{row0:08d}.npy") for row0, _ in plan]
    )


def _chunk_plan(rows: int, chunk: int) -> list[tuple[int, int]]:
    return [(r, min(chunk, rows - r)) for r in range(0, rows, chunk)]


def seeded_optE(optE_share: np.ndarray, N: int) -> np.ndarray:
    """optE for all N targets: the share's own values, and for the other
    targets draws (seed SEED) from the share's optE histogram."""
    rng = np.random.default_rng(SEED)
    rest = rng.choice(optE_share, size=N - optE_share.shape[0])
    return np.concatenate([optE_share, rest]).astype(np.int32)


def run_share(ts, ts_fut, optE, cfg, mesh, share, tmp, tag):
    """Phase 2 for rows [0, share) against all targets through a
    TileWriter, as a fleet worker runs a unit; returns the rho rows."""
    from repro.core.pipeline import run_phase2_chunks
    from repro.data.store import TileWriter

    writer = TileWriter(tmp / tag, ts.shape[0])
    plan = _chunk_plan(share, mesh.size * cfg.lib_block)
    run_phase2_chunks(ts, ts_fut, optE, cfg, mesh, plan, writer=writer)
    return _read_rows(writer.dir, plan)


def phase1(ts, cfg, mesh, counter, share, name="phase1"):
    """optE of all N targets: phase 1 on the share's rows only, and the
    other targets' optE drawn (seeded) from the share's optE histogram,
    so phase 2 still sees a realistic bucket mix."""
    import dataclasses

    from repro.core.pipeline import run_phase1

    cfg1 = dataclasses.replace(cfg, lib_block=PHASE1_LIB_BLOCK)
    with Phase(name, counter):
        _, optE = run_phase1(ts[:share], cfg1, mesh)
    say(f"{name}.rows", f"share rows 0..{share}; other {ts.shape[0] - share} "
        "targets' optE drawn (seeded) from the share's optE histogram")
    return seeded_optE(optE, ts.shape[0])


def table_check(ts, cfg_a, cfg_b, plan, rows, block, device=None):
    """kNN tables of library rows ``rows`` under two configs: (index
    mismatches, max |dw|) over every bucket table."""
    import jax
    import jax.numpy as jnp

    from repro.core import ccm

    bad, dw = 0, 0.0
    for r0 in range(0, rows, block):
        x = ts[r0 : r0 + block]
        if x.shape[0] < block:
            x = np.concatenate([x, ts[: block - x.shape[0]]])
        ia, wa = ccm.ccm_block_tables_bucketed(jnp.asarray(x), cfg_a, plan)
        with (jax.default_device(device) if device is not None
              else contextlib.nullcontext()):
            ib, wb = ccm.ccm_block_tables_bucketed(jnp.asarray(x), cfg_b, plan)
        n = min(block, rows - r0)
        ia, ib = np.asarray(ia)[:n], np.asarray(ib)[:n]
        bad += int((ia != ib).sum())
        dw = max(dw, float(
            np.abs(np.asarray(wa)[:n] - np.asarray(wb)[:n]).max()
        ))
    return bad, dw


def phase_main(tmp: pathlib.Path, counter: CompileCounter, N=N_FISH1,
               L=L_FISH1, share=SHARE, engine="pallas-compiled",
               e_max=E_MAX) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.core import ccm
    from repro.core.types import EDMConfig
    from repro.data.synthetic import dummy_brain

    mesh = Mesh(np.array(jax.devices()[:1]), ("workers",))
    cfg = EDMConfig(E_max=e_max, engine=engine)
    cfg_ref = EDMConfig(E_max=e_max, engine="reference")
    say("engine", engine)
    say("shape", f"N {N} x L {L}, E_max {e_max}, share {share} rows, "
        f"lib_block {cfg.lib_block}")
    t0 = time.perf_counter()
    ts = dummy_brain(N, L, seed=SEED)
    say("data.setup_s", round(time.perf_counter() - t0, 3))

    optE = phase1(ts, cfg, mesh, counter, share)
    plan, order = ccm.make_bucket_plan(optE)
    say("buckets", len(plan.buckets))
    with Phase("futures", counter):
        ts_fut = np.asarray(ccm.all_futures(jnp.asarray(ts), cfg))

    with Phase("phase2", counter):
        rho = run_share(ts, ts_fut, optE, cfg, mesh, share, tmp, "p2")
    say("phase2.rho_shape", list(rho.shape))
    say("phase2.rho_entries_per_s",
        round(rho.size / _SUMMARY["phase2.wall_s"], 1))
    say("phase2.rho_entries_per_s_steady",
        round(rho.size / _SUMMARY["phase2.steady_s"], 1))
    check(rho.shape == (share, N), f"rho shape {rho.shape}")
    check(bool(np.isfinite(rho).all()), "non-finite rho")
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    say("peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))

    # ---- (c) the same rows through the reference engine, on the chip
    with Phase("phase2_reference", counter):
        rho_ref = run_share(ts, ts_fut, optE, cfg_ref, mesh, share, tmp,
                            "p2_ref")
    d = float(np.abs(rho - rho_ref).max())
    say("check.chip.rho_max_abs_diff", d)
    say("check.chip.rho_bit_identical_fraction",
        float(np.mean(rho.view(np.uint32) == rho_ref.view(np.uint32))))
    check(d <= RHO_TOL_CHIP, f"chip rho differs by {d} > {RHO_TOL_CHIP}")
    with Phase("check.chip.tables", counter):
        bad, dw = table_check(ts, cfg, cfg_ref, plan, share, cfg.lib_block)
    say("check.chip.knn_index_mismatches", bad)
    say("check.chip.weight_max_abs_diff", dw)
    check(bad == 0, f"{bad} kNN indices differ between engines on the chip")

    # ---- (c) a sample against the reference engine on the host CPU
    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(SEED + 1)
    tgt = np.sort(rng.choice(N, size=min(CPU_TARGETS, N), replace=False))
    plan_s, order_s = ccm.make_bucket_plan(optE[tgt])
    with Phase("check.cpu", counter):
        with jax.default_device(cpu):
            rho_c = np.asarray(ccm.ccm_block_bucketed(
                jnp.asarray(ts[:CPU_ROWS]), jnp.asarray(ts_fut[tgt][order_s]),
                cfg_ref, plan_s,
            ))[:, np.argsort(order_s)]
        bad_c, dw_c = table_check(ts, cfg, cfg_ref, plan, CPU_ROWS, CPU_ROWS,
                                  device=cpu)
    d_c = float(np.abs(rho[:CPU_ROWS, tgt] - rho_c).max())
    say("check.cpu.sample", f"{CPU_ROWS} rows x {tgt.size} targets")
    say("check.cpu.rho_max_abs_diff", d_c)
    say("check.cpu.knn_index_mismatches", bad_c)
    say("check.cpu.weight_max_abs_diff", dw_c)
    check(bad_c == 0, f"{bad_c} kNN indices differ between chip and CPU")
    check(d_c <= RHO_TOL_CPU, f"CPU rho differs by {d_c} > {RHO_TOL_CPU}")


# ------------------------------------------------------------ four chips
def phase_four(tmp: pathlib.Path, counter: CompileCounter, N=N_FISH1,
               L=L_FISH1, share=SHARE, engine="pallas-compiled",
               e_max=E_MAX, L_knn=L_SUBJECT11) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.core import ccm, embedding, knn
    from repro.core.pipeline import (
        knn_tables_library_sharded,
        make_ccm_chunk_fn_bucketed,
    )
    from repro.core.types import EDMConfig
    from repro.data.synthetic import dummy_brain

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    mesh4 = Mesh(np.array(devs[:4]), ("workers",))
    mesh1 = Mesh(np.array(devs[:1]), ("workers",))
    cfg = EDMConfig(E_max=e_max, engine=engine)
    say("engine", engine)
    ts = dummy_brain(N, L, seed=SEED)

    opt4 = phase1(ts, cfg, mesh4, counter, share, "phase1_mesh4")
    opt1 = phase1(ts, cfg, mesh1, counter, share, "phase1_mesh1")
    check(np.array_equal(opt4, opt1), "phase-1 optE differs 4 vs 1 device")
    ts_fut = np.asarray(ccm.all_futures(jnp.asarray(ts), cfg))
    with Phase("phase2_mesh4", counter):
        rho4 = run_share(ts, ts_fut, opt4, cfg, mesh4, share, tmp, "m4")
    with Phase("phase2_mesh1", counter):
        rho1 = run_share(ts, ts_fut, opt4, cfg, mesh1, share, tmp, "m1")
    same = rho4.tobytes() == rho1.tobytes()
    say("four.phase2_byte_identical", same)
    check(same, "phase-2 share differs between 4 devices and 1")
    # one chunk's output really spans the four devices
    plan, order = ccm.make_bucket_plan(opt4)
    fn = make_ccm_chunk_fn_bucketed(mesh4, cfg, plan)
    out = fn(jnp.asarray(ts[: 4 * cfg.lib_block]), jnp.asarray(ts_fut[order]))
    used = sorted({s.device.id for s in out.addressable_shards})
    say("four.phase2_chunk_devices", used)
    check(len(used) == 4, f"phase-2 chunk ran on devices {used}")

    x = dummy_brain(1, L_knn, seed=SEED + 2)[0]
    Lp = cfg.n_points(L_knn)
    V = embedding.lag_matrix(jnp.asarray(x), e_max, cfg.tau, Lp)
    k = cfg.k_max
    with Phase("knn_sharded_mesh4", counter):
        i4, d4 = knn_tables_library_sharded(V, V, k, cfg, exclude_self=True,
                                            mesh=mesh4)
        i4, d4 = np.asarray(i4), np.asarray(d4)
    tile = knn.resolve_stream_tile(Lp, cfg, profile="host")
    with Phase("knn_unsharded", counter):
        build = jax.jit(knn.knn_tables_all_E_streaming,
                        static_argnums=(2, 3), static_argnames=("tile_c",))
        i1, d1 = build(V, V, k, True, tile_c=tile)
        i1, d1 = np.asarray(i1), np.asarray(d1)
    same = i4.tobytes() == i1.tobytes() and d4.tobytes() == d1.tobytes()
    say("four.knn_sharded_shape", list(i4.shape))
    say("four.knn_sharded_byte_identical", same)
    check(same, "library-sharded kNN tables differ from the unsharded build")


# ------------------------------------------------------------------ main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device mesh path and its "
                    "one-device comparison")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.runtime import platform

    plat, _ = platform.probe_devices()
    if plat != "tpu":
        print(f"chip_smoke: JAX finds no TPU (backend {plat!r})",
              file=sys.stderr)
        return 1
    OUT.mkdir(exist_ok=True)
    summary_path = OUT / ("smoke_four.json" if args.four_chips
                          else "smoke.json")
    try:
        with tempfile.TemporaryDirectory() as tmp:
            tmp = pathlib.Path(tmp)
            if not args.four_chips:
                phase_cli(tmp)  # before this process touches the chip
            import jax

            plats = jax.config.jax_platforms
            if plats and "cpu" not in plats.split(","):
                # (c) compares against the host CPU backend in-process
                jax.config.update("jax_platforms", plats + ",cpu")
            platform.enable_compile_cache()
            counter = CompileCounter()
            dev = jax.devices()[0]
            check(dev.platform == "tpu", f"device platform {dev.platform}")
            say("device", f"{dev.platform} {dev.device_kind} x "
                f"{len(jax.devices())}")
            if args.four_chips:
                phase_four(tmp, counter)
            else:
                phase_main(tmp, counter)
            say("compiles_total", counter.n)
    except Fail as e:
        _SUMMARY["failed"] = str(e)
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    except Exception as e:
        _SUMMARY["failed"] = f"{type(e).__name__}: {e}"
        raise
    finally:
        summary_path.write_text(json.dumps(_SUMMARY, indent=1, default=str))
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
