"""EDM causal-inference launcher — the paper's end-to-end workflow.

  PYTHONPATH=src python -m repro.launch.edm_run \
      --dataset /path/to/store --out /tmp/causal_map
  PYTHONPATH=src python -m repro.launch.edm_run --synthetic 64x600 --out ...
  # brain-scale memory profile: 2D-tiled phase 2 (DESIGN.md SS7)
  PYTHONPATH=src python -m repro.launch.edm_run \
      --synthetic 128x600 --target-tile 32 --out /tmp/causal_map

  # statistically validated causal graph (DESIGN.md SS9)
  PYTHONPATH=src python -m repro.launch.edm_run --synthetic 64x600 \
      --lib-sizes 100,200,400 --surrogates 20 --fdr 0.05 --seed 0 --out ...

  # multi-process elastic fleet (DESIGN.md SS10): W masterless workers
  # claim (row-span) work units from a lease queue over the store;
  # output is bit-identical to --workers 0 (the in-process path)
  PYTHONPATH=src python -m repro.launch.edm_run --synthetic 64x500 \
      --surrogates 20 --workers 4 --out /tmp/fleet

Reads a zarr-lite dataset (data/store.py), runs distributed simplex
projection + CCM on all local devices (the production launch wraps the
same entry point under the pod mesh), streams (row-chunk x col-tile)
blocks to the output store, and can RESUME from a killed run (--out
manifest).  With --out the causal map is assembled into a disk-backed
memmap (<out>/causal_map/data.npy) — no dense (N, N) host allocation —
and --target-tile additionally streams targets through column tiles
instead of replicating the full (N, Lp) future matrix per device:
nothing then scales beyond the O(N x L) inputs (host working set
O(chunk x tile), device O(lib_block x buckets x Lp x k + tile x Lp)).

--lib-sizes / --surrogates run the causal-significance subsystem on the
freshly assembled map: one-sweep convergence CCM (rho_conv/ +
rho_trend/), surrogate-null p-values (pvals/), and the BH-FDR
significance-masked edge list (edges/) — all streamed through the same
TileWriter store, resumable like phase 2.  --seed makes the whole run
reproducible (subsampling permutation + every surrogate draw derive
from it; recorded in the run's meta.json)."""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.pipeline import run_causal_inference
from repro.core.types import EDMConfig
from repro.data import store
from repro.data.synthetic import dummy_brain
from repro.engine import available_engines
from repro.inference import SignificanceConfig, run_significance
from repro.runtime import autotune, history, platform, telemetry


def _run_fleet(args, ts, cfg, sig, plat, n_dev):
    """--workers N: self-spawn a local masterless fleet (DESIGN.md SS10).

    The driver only prepares the shared store (dataset + fleet.json) and
    spawns/waits on worker processes, and never touches the jax backend
    itself (``plat``/``n_dev`` come from a probe child), so every chip is
    free for the workers — it schedules nothing; workers
    claim work units from the lease queue themselves.  A worker that
    dies is NOT fatal: the survivors reclaim its units after lease
    expiry, so the run completes as long as one worker lives (the
    driver re-raises only if ALL workers failed or artifacts are
    missing).
    """
    import json
    import pathlib

    from repro.launch import edm_fleet

    if plat == "tpu" and args.workers > 1:
        # Each worker process takes every chip it sees, so a second local
        # worker would wait on chips the first one holds.
        raise SystemExit(
            f"--workers {args.workers} on a TPU host with {n_dev} chip(s): "
            "a worker process holds every local chip (its mesh spans all "
            f"{n_dev}), so local workers beyond the first would wait on a "
            "held chip; use --workers 1, or --workers 0 to run in-process"
        )
    out = pathlib.Path(args.out)
    dataset = args.dataset
    if args.synthetic:
        dataset = out / "dataset"
        meta_f = dataset / "meta.json"
        if meta_f.exists():
            # Resume: the stored dataset must BE the requested one — a
            # changed spec silently reusing old data (same N, different
            # L or seed semantics) would compute over the wrong series.
            have = json.loads(meta_f.read_text()).get("synthetic")
            if have != args.synthetic:
                raise SystemExit(
                    f"--out {out} holds a --synthetic {have} dataset but "
                    f"this run asks for {args.synthetic}; use a fresh "
                    "--out dir"
                )
        else:
            store.save_dataset(dataset, ts, {"synthetic": args.synthetic})
    edm_fleet.init_fleet(
        out, dataset, cfg, sig,
        unit_rows=args.unit_rows or n_dev * cfg.lib_block, seed=args.seed,
        # Fleet workers re-apply the driver's platform tier from
        # fleet.json; `distributed` opts externally-launched workers into
        # the multi-host mesh via their OWN rank env (DESIGN.md SS14) —
        # locally-spawned children have the mesh vars stripped.
        platform=args.platform,
        distributed=platform.distributed_spec_from_env() is not None,
    )
    t0 = time.time()

    def spawn(wid):
        # tuned_ttl: schedule knob from --autotune (lease expiry sized
        # to the measured hold-time tail); None -> worker default.
        return edm_fleet.spawn_worker(out, wid,
                                      ttl=getattr(args, "tuned_ttl", None),
                                      unit_retries=args.unit_retries)

    procs = {f"w{i}": spawn(f"w{i}") for i in range(args.workers)}
    restarts = dict.fromkeys(procs, 0)
    fails = []
    # Supervise instead of blind-waiting: a POISON marker (a work unit
    # that exhausted its bounded retries fleet-wide) means no surviving
    # worker can ever finish — kill the fleet and surface the unit id,
    # instead of letting the barrier spin on TTL steals until timeout.
    # A worker that merely CRASHED (nonzero exit, no poison) is
    # relaunched under the same id — it reclaims its own leases
    # instantly — up to --max-worker-restarts times.
    while procs:
        poison = sorted((out / "queue").glob("*.poison"))
        if poison:
            for p in procs.values():
                p.terminate()
            for p in procs.values():
                p.wait()
            info = json.loads(poison[0].read_text())
            raise SystemExit(
                f"fleet failed: work unit {info.get('uid')} failed "
                f"permanently after {info.get('attempts')} attempt(s): "
                f"{info.get('error')}"
            )
        for wid in list(procs):
            rc = procs[wid].poll()
            if rc is None:
                continue
            del procs[wid]
            if rc == 0:
                continue
            if restarts[wid] < args.max_worker_restarts:
                restarts[wid] += 1
                print(f"worker {wid} exited {rc}; relaunching "
                      f"({restarts[wid]}/{args.max_worker_restarts})")
                procs[wid] = spawn(wid)
            else:
                fails.append(wid)
                print(f"warning: worker {wid} exited {rc} with restarts "
                      "exhausted (surviving workers cover its units)")
        if procs:
            time.sleep(0.25)
    # Success = the queue's durable stage witnesses exist (done markers
    # are written strictly AFTER the store commit they certify — a mere
    # data.npy can be a torn open_memmap of a fleet that died
    # mid-assemble) AND every artifact this run was asked for is present.
    required = [out / "queue" / "assemble.done",
                out / "causal_map" / "data.npy",
                out / "causal_map" / "meta.json"]
    if sig is not None:
        required.append(out / "queue" / "finalize.done")
        if sig.lib_sizes:
            required += [out / "rho_conv" / "data.npy",
                         out / "rho_trend" / "data.npy"]
        if sig.n_surrogates:
            required += [out / "pvals" / "data.npy",
                         out / "edges" / "data.npy"]
    missing = [str(p) for p in required if not p.exists()]
    if missing:
        raise SystemExit(
            f"fleet failed: missing completion witness(es) {missing} "
            f"(worker failures: {fails or 'none reported'})"
        )
    meta = json.loads((out / "causal_map" / "meta.json").read_text())
    N = meta["shape"][0]
    dt = time.time() - t0
    print(f"fleet[{args.workers}] causal map {N}x{N} in {dt:.1f}s "
          f"({N * N / dt:.0f} cross-maps/s); engine {cfg.engine}; "
          f"buckets {meta['n_buckets']}/{cfg.E_max}; "
          f"tile {cfg.target_tile or N}")
    if sig is not None:
        emeta = json.loads((out / "edges" / "meta.json").read_text()) \
            if (out / "edges" / "meta.json").exists() else None
        if emeta is not None:
            print(f"significance: {emeta['n_edges']} edges at FDR "
                  f"{emeta['alpha']} (p* = {emeta['p_threshold']:.4g}, "
                  f"{emeta['n_tests']} tests)")


_FLAGS_EPILOG = """\
flag groups:
  input          --dataset | --synthetic NxL
  embedding      --e-max --tau
  geometry       --lib-block --target-tile --knn-tile --stream-depth
                 (all byte-invisible to outputs; see --autotune)
  engine         --engine {reference,pallas-*}
  platform       --platform {cpu,gpu,tpu} (runtime/platform.py tier:
                 XLA flags + default engine; DESIGN.md SS14).  Multi-
                 host mesh joins via env: EDM_COORDINATOR host:port,
                 EDM_NUM_PROCESSES, EDM_PROCESS_ID (docs/OPERATIONS.md)
  significance   --lib-sizes --surrogates --fdr --surrogate-kind --seed
  fleet          --workers --unit-rows --unit-retries
                 --max-worker-restarts
  observability  --no-telemetry (default sink: <out>/telemetry/
                 main.jsonl; EDM_TELEMETRY=off|stdout|jsonl:<path>
                 overrides); `edm_fleet status --out DIR [--watch]`
                 renders a store's live state; `edm_fleet trace` the
                 assembled causal trace + Chrome trace JSON; `edm_fleet
                 trends` the cross-run history (one summary appended
                 per finished run to <out>/history.jsonl or
                 $EDM_HISTORY; DESIGN.md SS13)
  integrity      every store artifact is checksummed at write time and
                 the run fingerprint (dataset content + config) is
                 stamped into <out>; `edm_fleet fsck --out DIR [--heal]`
                 verifies a store and revokes damaged units for
                 recompute (DESIGN.md SS12)
  autotuning     --autotune --tune-from (recorded-timing tuner ->
                 <out>/tuned.json; geometry knobs + schedule knobs:
                 lease ttl applied to spawned workers, worker count
                 recommended, stream depth from drain gather share;
                 DESIGN.md SS11/SS13)
"""


def build_parser() -> argparse.ArgumentParser:
    """The edm_run CLI surface — exposed as a function so tests
    (tests/test_docs.py) can parse README/runbook invocations against
    the REAL parser."""
    ap = argparse.ArgumentParser(
        prog="edm_run",
        description=__doc__.split("\n")[0],
        epilog=_FLAGS_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--dataset", help="zarr-lite dataset dir")
    ap.add_argument("--synthetic", help="NxL dummy dataset, e.g. 128x1000")
    ap.add_argument("--out", required=True)
    ap.add_argument("--e-max", type=int, default=20)
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--lib-block", type=int, default=8)
    ap.add_argument(
        "--platform", default=None, choices=platform.available_tiers(),
        help="execution tier (runtime/platform.py, DESIGN.md SS14): sets "
        "the jax platform, the tier's tuned XLA flags, and — unless "
        "--engine overrides — the tier's default engine.  Applied before "
        "the first jax backend touch; multi-host meshes additionally join "
        "via EDM_COORDINATOR/EDM_NUM_PROCESSES/EDM_PROCESS_ID",
    )
    ap.add_argument(
        "--target-tile", type=int, default=0,
        help="phase-2 column tile width (0 = untiled); > 0 streams targets "
        "in tiles so phase 2 allocates nothing beyond the O(NL) inputs "
        "(DESIGN.md SS7); output is bit-identical to the untiled path",
    )
    ap.add_argument(
        "--engine", default=None, choices=available_engines(),
        help="execution backend (repro.engine registry; default: reference)",
    )
    ap.add_argument(
        "--knn-tile", type=int, default=0,
        help="streaming kNN candidate-tile width (DESIGN.md SS8): 0 = "
        "auto-calibrated (widest tile under the VMEM budget), > 0 = force "
        "this width (distance working set flat in library length); output "
        "is bit-identical at every width",
    )
    ap.add_argument(
        "--stream-depth", type=int, default=2,
        help="CCM blocks in flight (2 = double buffering, 1 = synchronous)",
    )
    ap.add_argument(
        "--use-kernels", action="store_true",
        help="DEPRECATED: same as --engine pallas-compiled",
    )
    ap.add_argument(
        "--lib-sizes", default="",
        help="comma-separated ascending library sizes for the convergence "
        "diagnostic (DESIGN.md SS9), e.g. 100,200,400; writes rho_conv/ "
        "(delta-rho) and rho_trend/ (monotonic-trend) store artifacts",
    )
    ap.add_argument(
        "--surrogates", type=int, default=0,
        help="surrogate-null draws per target (0 = skip significance): "
        "writes per-pair p-values (pvals/) and the FDR-masked causal "
        "edge list (edges/)",
    )
    ap.add_argument(
        "--fdr", type=float, default=0.05,
        help="Benjamini-Hochberg FDR level of the edge mask",
    )
    ap.add_argument(
        "--surrogate-kind", default="phase", choices=("phase", "shuffle"),
        help="null model: FFT phase-randomized (spectrum-preserving) or "
        "random shuffle (amplitude-distribution only)",
    )
    ap.add_argument(
        "--seed", type=int, default=0,
        help="root seed of the significance stage: ONE jax.random key "
        "derived from it drives the convergence subsampling permutation "
        "and every surrogate draw (recorded in meta.json)",
    )
    ap.add_argument(
        "--workers", type=int, default=0,
        help="self-spawn a local fleet of this many masterless worker "
        "processes over the output store (DESIGN.md SS10); 0 = run "
        "in-process.  Any W produces bit-identical causal_map/rho_conv/"
        "pvals arrays.  The driver stays off the jax backend; on a TPU "
        "host W is at most 1 (a worker holds every local chip)",
    )
    ap.add_argument(
        "--unit-rows", type=int, default=0,
        help="fleet work-unit height in rows (claim granularity); "
        "0 = one worker chunk (devices x lib-block)",
    )
    ap.add_argument(
        "--unit-retries", type=int, default=3,
        help="failed compute attempts (fleet-wide, durable) before a work "
        "unit is poisoned and the fleet exits nonzero with its id",
    )
    ap.add_argument(
        "--max-worker-restarts", type=int, default=2,
        help="times the fleet driver relaunches a crashed worker process "
        "under the same id before giving its units to the survivors",
    )
    ap.add_argument(
        "--no-telemetry", action="store_true",
        help="disable the default per-run telemetry JSONL sink "
        "(<out>/telemetry/main.jsonl); records are byte-invisible to "
        "outputs, so this only saves the write traffic.  EDM_TELEMETRY="
        "off|stdout|jsonl:<path> overrides the default sink instead",
    )
    ap.add_argument(
        "--autotune", action="store_true",
        help="apply tuned geometry (<out or --tune-from>/tuned.json, or "
        "a fresh replay of recorded telemetry) before the run, and write "
        "<out>/tuned.json from this run's telemetry after it; shapes are "
        "byte-invisible to outputs (DESIGN.md SS11)",
    )
    ap.add_argument(
        "--tune-from",
        help="store whose recorded telemetry / tuned.json seeds "
        "--autotune (default: --out itself, i.e. a rerun tunes from the "
        "previous run)",
    )
    return ap


def main():
    ap = build_parser()
    args = ap.parse_args()

    # Platform tier + multi-host mesh join, BEFORE any jax backend touch
    # (XLA flags and jax_platforms are latched at backend init).
    if args.platform:
        applied = platform.apply_platform(args.platform)
        print(f"platform: tier {applied['tier']} "
              f"(engine default {applied['engine']})")
    dist = platform.init_distributed()
    if dist is not None:
        print(f"distributed: process {dist['process_id']}/"
              f"{dist['num_processes']} via {dist['coordinator']}")

    if args.synthetic:
        N, L = map(int, args.synthetic.split("x"))
        ts = dummy_brain(N, L)
    else:
        ts = np.asarray(store.load_dataset(args.dataset), np.float32)
    if args.use_kernels:
        if args.engine not in (None, "pallas-compiled"):
            ap.error("--use-kernels conflicts with --engine "
                     f"{args.engine}; drop the deprecated flag")
        print("note: --use-kernels is deprecated; use --engine pallas-compiled")
        engine = "pallas-compiled"
    elif args.engine:
        engine = args.engine
    elif args.platform:
        # The tier's default engine (registry tie-in): gpu/tpu tiers run
        # the Pallas kernels, cpu stays on the jnp reference engine.
        engine = platform.default_engine(args.platform)
    else:
        engine = "reference"
    cfg = EDMConfig(
        E_max=args.e_max, tau=args.tau, lib_block=args.lib_block,
        engine=engine, stream_depth=args.stream_depth,
        target_tile=args.target_tile, knn_tile_c=args.knn_tile,
    )
    if args.workers > 0:
        # The fleet driver stays off the jax backend (its workers need the
        # chips): a probe child reports the platform and device count.
        plat, n_dev = platform.probe_devices(args.platform)
    else:
        import jax

        platform.enable_compile_cache()
        plat, n_dev = jax.default_backend(), len(jax.devices())
    if not args.no_telemetry:
        telemetry.configure_from_env(
            default_path=telemetry.worker_jsonl(args.out, "main"),
            worker="main",
        )
    if args.autotune:
        # Tuned shapes are byte-invisible to outputs, so applying a
        # recommendation can only ever change wall time.  A fleet
        # restart reads the SAME tuned.json it wrote, so its fleet.json
        # spec check still passes (deterministic restart shapes).
        src = args.tune_from or args.out
        tuned = autotune.load_tuned(src) or autotune.recommend(src)
        if tuned is not None:
            cfg = autotune.apply_to_cfg(cfg, tuned, n_dev)
            rec = tuned["recommend"]
            # Schedule knobs (DESIGN.md SS13): the tuned lease TTL is
            # applied to the workers this driver spawns; the worker
            # count is a budget decision, so it is RECOMMENDED, never
            # silently applied.
            if rec.get("ttl"):
                args.tuned_ttl = float(rec["ttl"])
            if rec.get("workers") and args.workers > 0 \
                    and rec["workers"] != args.workers:
                print(f"autotune: recommend --workers {rec['workers']} "
                      f"(this run uses {args.workers}; straggler-tail "
                      "model, see tuned.json evidence)")
            print(f"autotune: applied {rec} from {src}")
        elif args.tune_from:
            raise SystemExit(
                f"--tune-from {src}: no tuned.json and no chunk telemetry "
                "to replay"
            )
    # Run-start clock anchor (runtime/trace.py aligns timelines on it),
    # then the run's config snapshot.
    telemetry.emit_clock_anchor(driver=True, workers=args.workers)
    telemetry.counter(
        "fleet", "run_config", engine=cfg.engine, lib_block=cfg.lib_block,
        target_tile=cfg.target_tile, knn_tile_c=cfg.knn_tile_c,
        stream_depth=cfg.stream_depth, workers=args.workers,
        autotune=bool(args.autotune),
    )
    # ONE sig construction for both drivers — the fleet path must run
    # exactly the config the in-process path would (bit-identity).
    lib_sizes = tuple(int(s) for s in args.lib_sizes.split(",") if s)
    sig = None
    if lib_sizes or args.surrogates:
        sig = SignificanceConfig(
            lib_sizes=lib_sizes, n_surrogates=args.surrogates,
            alpha=args.fdr, surrogate=args.surrogate_kind, seed=args.seed,
        )
    if args.workers > 0:
        try:
            _run_fleet(args, ts, cfg, sig, plat, n_dev)
            # Refresh the run-history record the finalize claimer wrote
            # so it also covers the driver's own telemetry tail (same
            # run identity -> replaces, never duplicates).
            history.record_run(args.out)
        finally:
            telemetry.shutdown()
        _autotune_epilogue(args)
        return
    t0 = time.time()
    result = run_causal_inference(ts, cfg, out_dir=args.out, progress=True)
    dt = time.time() - t0
    N = ts.shape[0]
    n_buckets = len(np.unique(np.asarray(result.optE)))
    print(f"causal map {N}x{N} in {dt:.1f}s "
          f"({N * N / dt:.0f} cross-maps/s); optE mean {result.optE.mean():.2f}; "
          f"engine {cfg.engine}; buckets {n_buckets}/{cfg.E_max}; "
          f"tile {cfg.target_tile or N}")
    meta = {
        "optE": result.optE.tolist(),
        "engine": cfg.engine,
        "bucketed": cfg.bucketed,
        "n_buckets": int(n_buckets),
        "stream_depth": cfg.stream_depth,
        "target_tile": cfg.target_tile,
        "knn_tile_c": cfg.knn_tile_c,
        "seed": args.seed,
    }
    # The pipeline already assembled the map into <out>/causal_map/data.npy
    # (memmap; no dense host copy) — only the zarr-lite meta is missing.
    # Re-saving result.rho here would truncate the very file backing it.
    store.save_meta(
        args.out + "/causal_map", result.rho.shape, result.rho.dtype, meta
    )

    if sig is not None:
        t1 = time.time()
        out = run_significance(
            ts, np.asarray(result.optE), np.asarray(result.rho), cfg, sig,
            out_dir=args.out, progress=True,
        )
        stages = [s for s, on in (("convergence", sig.lib_sizes),
                                  ("surrogates", sig.n_surrogates)) if on]
        print(f"significance [{'+'.join(stages)}] in {time.time() - t1:.1f}s"
              + (f"; {len(out.edges)} edges at FDR {args.fdr} "
                 f"(p* = {out.p_threshold:.4g}, {out.n_tests} tests)"
                 if out.edges is not None else ""))
    history.record_run(args.out)  # run-history summary (DESIGN.md SS13)
    telemetry.shutdown()  # flush the run's JSONL before any replay
    _autotune_epilogue(args)


def _autotune_epilogue(args) -> None:
    """--autotune: replay the telemetry THIS run just recorded and
    persist the recommendation beside fleet.json for the next run."""
    if not args.autotune:
        return
    tuned = autotune.recommend(args.out)
    if tuned is None:
        print("autotune: no chunk telemetry recorded this run "
              "(nothing computed, or telemetry disabled); tuned.json "
              "not updated")
        return
    p = autotune.write_tuned(args.out, tuned)
    print(f"autotune: wrote {p}: {tuned['recommend']}")


if __name__ == "__main__":
    main()
