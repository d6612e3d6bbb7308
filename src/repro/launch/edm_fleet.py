"""Masterless multi-process EDM fleet — the paper's 512-node master-worker
over the tile store, without the master (DESIGN.md SS10).

  # spawned for you:
  PYTHONPATH=src python -m repro.launch.edm_run --synthetic 64x500 \
      --workers 4 --surrogates 20 --out /tmp/fleet
  # or by hand / on other hosts sharing the filesystem:
  PYTHONPATH=src python -m repro.launch.edm_fleet --out /tmp/fleet \
      --worker-id w2

Every worker runs the SAME stage sequence and coordinates purely through
files in the shared ``--out`` store (works for local processes and for
hosts sharing a parallel filesystem alike):

  phase1   — one unit; the claimer runs simplex projection for all rows
             and persists optE + simplex rhos (the run's one broadcast).
  phase2   — (row-span) units claimed from a lease queue; each worker
             computes its units under its OWN local mesh with the
             existing chunk functions and streams tiles through a
             writer_id-sharded TileWriter.
  assemble — one unit: merge manifests, memmap-assemble causal_map/.
  sig      — (row-span) units of the significance stage: prefix-kNN
             convergence sweeps + surrogate-null batches per claimed
             chunk, through the same sharded writers.
  finalize — one unit: assemble rho_conv/rho_trend/pvals, recount the
             p histogram, BH-FDR edge list.

Elasticity: SIGKILL any worker at any point; its unclaimed units are
untouched, its claimed unit's lease expires (or is reclaimed instantly
by a relaunched worker with the same id) and is recomputed.  Because
every unit's values are geometry-independent and every store write is
an atomic replace of bit-identical content, the assembled causal_map,
rho_conv, and pvals arrays are byte-identical for ANY worker count,
kill schedule, or unit size — W=4 with a mid-run kill equals a fresh
W=1 run (asserted in CI).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

from repro.core import ccm
from repro.core.types import EDMConfig
from repro.data import store
from repro.data.store import TileWriter
from repro.inference import SignificanceConfig
from repro.runtime import faultpoints, history, integrity, telemetry, trace
from repro.runtime.workqueue import LeaseQueue, WorkUnit, plan_units

SPEC_NAME = "fleet.json"
STAGE_ORDER = ("phase1", "phase2", "assemble", "sig", "finalize")


# ------------------------------------------------------------------- spec
def init_fleet(
    out_dir: str | pathlib.Path,
    dataset: str | pathlib.Path,
    cfg: EDMConfig,
    sig: SignificanceConfig | None = None,
    unit_rows: int = 0,
    seed: int | None = None,
    platform: str | None = None,
    distributed: bool = False,
) -> dict:
    """Write the shared fleet spec every worker derives its queue from.

    unit_rows=0 resolves to one local-mesh chunk (devices x lib_block,
    devices counted by a probe child) — the natural claim granularity.
    The spec pins dataset path, configs, and the unit grid so W workers
    agree on the queue with no exchange.

    ``platform`` / ``distributed`` are the multi-host opt-in (DESIGN.md
    SS14): workers apply the named runtime/platform.py tier before their
    first jax touch, and with ``distributed`` they join the logical mesh
    via their own EDM_COORDINATOR / EDM_NUM_PROCESSES / EDM_PROCESS_ID
    environment (docs/OPERATIONS.md) — the spec opts the fleet in; the
    per-process rank always comes from the worker's environment.
    """
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    meta = json.loads((pathlib.Path(dataset) / "meta.json").read_text())
    N, L = (int(s) for s in meta["shape"][:2])
    if unit_rows <= 0:
        # A probe child counts the devices: this process stays off the
        # backend, so the chips are free for the workers it spawns.
        from repro.runtime.platform import probe_devices

        unit_rows = probe_devices(platform)[1] * cfg.lib_block
    if seed is None:
        seed = 0 if sig is None else sig.seed
    # Run fingerprint: dataset CONTENT (not path — the same path can hold
    # different bytes tomorrow) + canonicalized config.  In the spec it
    # rides the existing resume equality check; workers re-derive it from
    # the bytes they actually loaded at join time.
    # float32 canonicalization matches what workers compute over, so the
    # two sides always hash the same bytes regardless of storage dtype.
    ts = np.asarray(store.load_dataset(dataset), np.float32)
    fp = integrity.fingerprint_of(ts, cfg)
    spec = {
        "dataset": str(pathlib.Path(dataset).resolve()),
        "N": N,
        "L": L,  # pins dataset identity: same-N, different-L swaps refuse
        "unit_rows": int(unit_rows),
        "seed": int(seed),
        "cfg": dataclasses.asdict(cfg),
        "sig": None if sig is None else dataclasses.asdict(sig),
        "dataset_crc32": fp["dataset_crc32"],
        "fingerprint": fp["fingerprint"],
        "platform": platform,
        "distributed": bool(distributed),
    }
    # JSON round-trip so the resume equality check compares like with
    # like (tuples become lists exactly as they will when read back).
    spec = json.loads(json.dumps(spec))
    existing = out / SPEC_NAME
    if existing.exists():
        have = json.loads(existing.read_text())
        if have != spec:
            raise ValueError(
                f"fleet spec mismatch in {out}: store was initialised with "
                f"{have} but this run asks for {spec}; use a fresh --out dir"
            )
        return have
    store.atomic_write_text(existing, json.dumps(spec, indent=1))
    integrity.stamp_fingerprint(out, fp)
    return spec


def load_fleet(out_dir: str | pathlib.Path) -> dict:
    spec = json.loads((pathlib.Path(out_dir) / SPEC_NAME).read_text())
    spec["cfg"] = EDMConfig(**spec["cfg"])
    if spec["sig"] is not None:
        s = dict(spec["sig"])
        s["lib_sizes"] = tuple(s["lib_sizes"])
        spec["sig"] = SignificanceConfig(**s)
    return spec


def spawn_worker(
    out_dir: str | pathlib.Path,
    worker_id: str,
    ttl: float | None = None,
    env: dict | None = None,
    unit_retries: int | None = None,
) -> subprocess.Popen:
    """Spawn one fleet worker as a detached subprocess.

    Workers share the JAX persistent compilation cache that
    runtime/platform.enable_compile_cache places — $JAX_COMPILATION_CACHE_DIR
    when exported, else the one fixed directory in the checkout, so the
    cache survives across runs and --out dirs: W processes compile the
    same jit signatures, so all but the first hit the disk cache — the
    fleet's answer to the paper's GPU-init straggler tail (SSIV-B2).
    """
    e = dict(os.environ if env is None else env)
    # A locally-spawned worker must NOT inherit the driver's multi-host
    # rank: W children all claiming the driver's EDM_PROCESS_ID would
    # deadlock jax.distributed.initialize.  Cross-host workers are
    # launched externally (one per host, each with its own rank env —
    # docs/OPERATIONS.md); the fleet.json `distributed` flag opts them in.
    if env is None:
        from repro.runtime import platform as _platform

        for var in (_platform.ENV_COORDINATOR, _platform.ENV_NUM_PROCESSES,
                    _platform.ENV_PROCESS_ID,
                    _platform.ENV_LOCAL_DEVICE_IDS):
            e.pop(var, None)
    src = pathlib.Path(__file__).resolve().parents[2]
    e["PYTHONPATH"] = f"{src}:{e['PYTHONPATH']}" if e.get("PYTHONPATH") else str(src)
    cmd = [sys.executable, "-m", "repro.launch.edm_fleet",
           "--out", str(out_dir), "--worker-id", worker_id]
    if ttl is not None:
        cmd += ["--ttl", str(ttl)]
    if unit_retries is not None:
        cmd += ["--unit-retries", str(unit_retries)]
    return subprocess.Popen(cmd, env=e)


# ----------------------------------------------------------------- worker
def _sub_chunks(unit: WorkUnit, chunk: int) -> list[tuple[int, int]]:
    """Split a claimed unit into local-mesh-sized (row0, valid) chunks
    (a unit from a spec written under a different device count may span
    several of this worker's chunks — elastic across mesh sizes)."""
    hi = unit.row0 + unit.nrows
    return [(r, min(chunk, hi - r)) for r in range(unit.row0, hi, chunk)]


def _covered_and(writers: list[TileWriter]) -> np.ndarray:
    cov = writers[0].refresh().covered()
    for w in writers[1:]:
        cov &= w.refresh().covered()
    return cov


class FleetWorker:
    """One worker's walk through the stage sequence.  Usable in-process
    (tests drive several workers' stages by hand) or via main()."""

    def __init__(self, out_dir: str | pathlib.Path, worker_id: str,
                 ttl: float = 600.0, poll: float = 0.25,
                 timeout: float | None = 3600.0, progress: bool = True,
                 unit_retries: int = 3):
        self.out = pathlib.Path(out_dir)
        spec = load_fleet(self.out)
        self.cfg: EDMConfig = spec["cfg"]
        self.sig: SignificanceConfig | None = spec["sig"]
        self.unit_rows: int = spec["unit_rows"]
        self.seed: int = spec.get("seed", 0)
        self.ts = np.asarray(store.load_dataset(spec["dataset"]), np.float32)
        self.N = self.ts.shape[0]
        want = (spec["N"], spec.get("L", self.ts.shape[1]))
        if self.ts.shape != want:
            raise ValueError(
                f"dataset shape {self.ts.shape} != fleet spec {want}"
            )
        # Worker-join fingerprint check: the bytes THIS worker just
        # loaded must be the bytes the fleet was initialised on, or its
        # tiles would silently mix with everyone else's (DESIGN.md SS12).
        want_fp = spec.get("fingerprint")
        if want_fp is not None:
            have = integrity.fingerprint_of(self.ts, self.cfg)
            if have["fingerprint"] != want_fp:
                raise integrity.IntegrityError(
                    f"worker {worker_id}: run fingerprint "
                    f"{have['fingerprint']} (dataset crc "
                    f"{have['dataset_crc32']}) != fleet spec {want_fp} — "
                    f"the dataset at {spec['dataset']} changed since "
                    "init_fleet; use a fresh --out dir"
                )
        self.worker_id = worker_id
        self.queue = LeaseQueue(self.out / "queue", worker_id, ttl=ttl,
                                poll=poll, fail_limit=unit_retries)
        self.timeout = timeout
        self.progress = progress
        from repro.core.pipeline import default_mesh

        self.mesh = default_mesh()
        self.chunk = self.mesh.size * self.cfg.lib_block

    def _log(self, msg: str) -> None:
        if self.progress:
            print(f"[{self.worker_id}] {msg}", flush=True)

    def _renew_chunk(self, unit: WorkUnit) -> None:
        """Per-chunk keepalive: the ``chunk_pre`` fault point (chaos
        schedules inject errors/delays between chunks here) followed by
        the lease renewal that keeps a slow-but-alive unit unstolen."""
        faultpoints.fire("chunk_pre")
        self.queue.renew(unit)

    # -------------------------------------------------------- stage fns
    def _phase1(self) -> np.ndarray:
        from repro.core.pipeline import run_phase1

        p1 = self.out / "phase1"

        def compute(unit):
            self._log("phase1: simplex projection")
            rhos, optE = run_phase1(
                self.ts, self.cfg, self.mesh,
                on_chunk=lambda row0: self.queue.renew(unit),
            )
            p1.mkdir(parents=True, exist_ok=True)
            # optE.npy is the stage's completion WITNESS (already_done
            # below + pollers), so it must land LAST: a kill between
            # these writes then leaves an unwitnessed stage that gets
            # recomputed, never a witnessed stage missing artifacts.
            store.save_npy_checksummed(p1 / "simplex_rho.npy", rhos)
            store.save_meta(p1, optE.shape, optE.dtype, {"stat": "optE"})
            store.save_npy_checksummed(p1 / "optE.npy", optE)

        self.queue.run_stage(
            plan_units("phase1", self.N, self.unit_rows), compute,
            already_done=lambda u: (p1 / "optE.npy").exists(),
            timeout=self.timeout,
        )
        return np.load(p1 / "optE.npy")

    def _phase2(self, optE: np.ndarray) -> None:
        import jax.numpy as jnp

        from repro.core.pipeline import run_phase2_chunks

        ts_fut = np.asarray(ccm.all_futures(jnp.asarray(self.ts), self.cfg))
        writer = TileWriter(self.out, self.N, writer_id=self.worker_id)
        units = plan_units("phase2", self.N, self.unit_rows)

        def compute(unit):
            self._log(f"phase2 rows {unit.row0}..{unit.row0 + unit.nrows}")
            # Per-chunk lease renewal INSIDE the streaming loop: a unit
            # whose compute (first-touch Pallas compile, a straggler
            # chunk) outlives the TTL re-stamps its clock between chunks
            # instead of being stolen mid-flight.
            run_phase2_chunks(
                self.ts, ts_fut, optE, self.cfg, self.mesh,
                _sub_chunks(unit, self.chunk), writer=writer,
                on_chunk=lambda row0: self._renew_chunk(unit),
            )

        # Coverage snapshot ONCE per stage entry (refresh + covered walk
        # every manifest shard — O(tiles), not something to redo per
        # unit); units finished later are handled by the queue itself.
        cov = writer.refresh().covered()
        already_done = lambda u: bool(cov[u.row0 : u.row0 + u.nrows].all())
        self.queue.run_stage(units, compute, already_done=already_done,
                             timeout=self.timeout)

    def _assemble(self, optE: np.ndarray) -> np.ndarray:
        map_npy = self.out / "causal_map" / "data.npy"

        def compute(unit):
            self._log("assemble: causal_map")
            writer = TileWriter(self.out, self.N)
            if not writer.covered().all():
                # Queue markers say phase 2 is done but the store is not
                # covered — someone removed tiles or the fs lost data.
                # Fail loudly rather than assemble silent zero rows
                # (delete <out>/queue/ to force a recompute-from-coverage).
                raise RuntimeError(
                    f"phase-2 store {self.out} incomplete at assemble: "
                    f"{int((~writer.covered()).sum())} rows uncovered"
                )
            rho = writer.assemble(mmap_path=map_npy)
            n_buckets = len(np.unique(optE))
            store.save_meta(
                self.out / "causal_map", rho.shape, rho.dtype,
                {
                    "optE": optE.tolist(),
                    "engine": self.cfg.engine,
                    "bucketed": self.cfg.bucketed,
                    "n_buckets": int(n_buckets),
                    "stream_depth": self.cfg.stream_depth,
                    "target_tile": self.cfg.target_tile,
                    "knn_tile_c": self.cfg.knn_tile_c,
                    "seed": self.seed,
                    "fleet": True,
                },
            )
            # Run-history summary (DESIGN.md SS13): for a no-significance
            # fleet assemble IS finalize; a later sig finalize REPLACES
            # this record (same run identity).  Only the assemble claimer
            # writes — single history writer per run.
            history.record_run(self.out)

        self.queue.run_stage(
            plan_units("assemble", self.N, self.unit_rows), compute,
            timeout=self.timeout,
        )
        return np.load(map_npy, mmap_mode="r")

    def _significance(self, optE: np.ndarray, rho: np.ndarray) -> None:
        from repro.inference.pipeline import (
            SignificanceChunkRunner,
            _check_resume_config,
            _writer,
            finalize_significance,
            run_sig_chunks,
        )

        sig = self.sig
        _check_resume_config(self.out, sig)
        runner = SignificanceChunkRunner(
            self.ts, optE, self.cfg, sig, self.mesh
        )
        conv_w = trend_w = pv_w = None
        if runner.do_conv:
            conv_w = _writer(self.out, "rho_conv", self.N, runner.order,
                             writer_id=self.worker_id)
            trend_w = _writer(self.out, "rho_trend", self.N, runner.order,
                              writer_id=self.worker_id)
        if runner.do_null:
            pv_w = _writer(self.out, "pvals", self.N, runner.order,
                           writer_id=self.worker_id)
        writers = [w for w in (conv_w, trend_w, pv_w) if w is not None]

        def compute(unit):
            self._log(f"sig rows {unit.row0}..{unit.row0 + unit.nrows}")
            run_sig_chunks(runner, _sub_chunks(unit, self.chunk), rho,
                           conv_w, trend_w, pv_w,
                           on_chunk=lambda row0: self._renew_chunk(unit))

        # AND-of-coverages snapshot once per stage entry (SS9 resume
        # semantics: a chunk counts only when EVERY artifact has it).
        cov = _covered_and(writers)
        already_done = lambda u: bool(cov[u.row0 : u.row0 + u.nrows].all())
        with telemetry.span("sig", "stage"):
            self.queue.run_stage(
                plan_units("sig", self.N, self.unit_rows), compute,
                already_done=already_done, timeout=self.timeout,
            )
        telemetry.flush()

        def do_finalize(unit):
            self._log("finalize: assembly + recount + BH-FDR edges")
            out = finalize_significance(
                str(self.out), rho, self.cfg, sig, progress=self.progress
            )
            del out

        with telemetry.span("finalize", "stage"):
            self.queue.run_stage(
                plan_units("finalize", self.N, self.unit_rows), do_finalize,
                timeout=self.timeout,
            )
        telemetry.flush()

    # --------------------------------------------------------- full run
    def run(self) -> None:
        """Walk the full stage sequence.  Every stage is wrapped in a
        telemetry span (so each worker's JSONL covers all five stages
        even for units it never computed — the barrier wait IS the
        record) and flushed at the stage boundary, bounding what a
        SIGKILL can lose to one stage's unflushed tail."""
        t0 = time.time()
        # Run-start clock anchor: (epoch, monotonic) sample the trace
        # assembler aligns this worker's timeline on (DESIGN.md SS13).
        telemetry.emit_clock_anchor(worker_id=self.worker_id)
        with telemetry.span("phase1", "stage"):
            optE = self._phase1()
        telemetry.flush()
        with telemetry.span("phase2", "stage"):
            self._phase2(optE)
        telemetry.flush()
        with telemetry.span("assemble", "stage"):
            rho = self._assemble(optE)
        telemetry.flush()
        if self.sig is not None and (
            self.sig.lib_sizes or self.sig.n_surrogates > 0
        ):
            self._significance(optE, rho)
        self._log(f"done in {time.time() - t0:.1f}s")
        telemetry.flush()


# ----------------------------------------------------------------- status
def fleet_status(out_dir: str | pathlib.Path) -> dict:
    """Live fleet state for a store, from files alone (no worker RPC —
    masterless observability to match the masterless queue):

      stages    — per stage: total/done/poisoned unit counts plus every
                  live lease (worker, age, expired?) from the queue dir;
      coverage  — per store artifact: covered-row fraction from the
                  writer manifests (the ground truth the queue certifies);
      telemetry — per worker-file record/violation counts and per-stage
                  span-time + claim/steal/done rollups from the recorded
                  JSONL (empty when telemetry was off).

    Returns a JSON-safe dict; :func:`render_status` is the human form.
    """
    out = pathlib.Path(out_dir)
    spec = json.loads((out / SPEC_NAME).read_text())
    N, unit_rows = spec["N"], spec["unit_rows"]
    qdir = out / "queue"
    now = time.time()

    stages = {}
    for kind in STAGE_ORDER:
        if kind in ("sig", "finalize") and spec.get("sig") is None:
            continue
        units = plan_units(kind, N, unit_rows)
        done = sum((qdir / f"{u.uid}.done").exists() for u in units)
        poisoned, leases = [], []
        for u in units:
            pp = qdir / f"{u.uid}.poison"
            if pp.exists():
                try:
                    poisoned.append(json.loads(pp.read_text()))
                except ValueError:
                    poisoned.append({"uid": u.uid})
            lp = qdir / f"{u.uid}.lease"
            if lp.exists() and not (qdir / f"{u.uid}.done").exists():
                try:
                    held = json.loads(lp.read_text())
                except (OSError, ValueError):
                    continue
                age = now - held.get("t", now)
                leases.append({
                    "uid": u.uid, "worker": held.get("worker"),
                    "age_s": round(age, 1),
                    "expired": age > held.get("ttl", 0),
                })
        stages[kind] = {"total": len(units), "done": done,
                        "leases": leases, "poisoned": poisoned}

    coverage = {}
    artifacts = [("causal_map", out)]
    if spec.get("sig") is not None:
        s = spec["sig"]
        if s.get("lib_sizes"):
            artifacts += [("rho_conv", out / "rho_conv"),
                          ("rho_trend", out / "rho_trend")]
        if s.get("n_surrogates", 0) > 0:
            artifacts += [("pvals", out / "pvals")]
    for name, d in artifacts:
        if not pathlib.Path(d).exists():
            coverage[name] = {"covered": 0, "total": N, "pct": 0.0}
            continue
        cov = TileWriter(d, N).covered()
        coverage[name] = {
            "covered": int(cov.sum()), "total": N,
            "pct": round(100.0 * float(cov.mean()), 1),
        }

    workers: dict[str, dict] = {}
    per_stage: dict[str, dict] = {}
    violations = 0
    for stem, rec in telemetry.iter_store_records(out):
        w = workers.setdefault(stem, {"records": 0, "invalid": 0})
        w["records"] += 1
        if telemetry.validate(rec):
            w["invalid"] += 1
            violations += 1
            continue
        st = per_stage.setdefault(
            rec["stage"],
            {"span_s": 0.0, "claim": 0, "steal": 0, "done": 0},
        )
        if rec["kind"] == "span":
            if rec["name"] not in telemetry.NESTED_SPANS:
                st["span_s"] += rec["dur_s"]
        elif rec["name"] in ("claim", "steal", "done"):
            st[rec["name"]] += 1
    for st in per_stage.values():
        st["span_s"] = round(st["span_s"], 3)

    all_done = all(s["done"] == s["total"] for s in stages.values())
    full_cov = all(c["pct"] >= 100.0 for c in coverage.values())
    return {
        "out": str(out), "N": N, "L": spec.get("L"),
        "unit_rows": unit_rows,
        "stages": stages, "coverage": coverage,
        "telemetry": {"workers": workers, "stages": per_stage,
                      "violations": violations},
        "complete": bool(all_done and full_cov and coverage),
    }


def render_status(st: dict) -> str:
    lines = [
        f"fleet {st['out']}: N={st['N']} L={st['L']} "
        f"unit_rows={st['unit_rows']}"
        f"{'  [COMPLETE]' if st['complete'] else ''}",
        f"{'stage':<10} {'done':>9}  leases",
    ]
    for kind, s in st["stages"].items():
        parts = []
        for l in s["leases"]:
            flag = " EXPIRED" if l["expired"] else ""
            parts.append(f"{l['uid']}@{l['worker']} {l['age_s']}s{flag}")
        for p in s["poisoned"]:
            parts.append(f"{p.get('uid')} POISONED ({p.get('error', '?')})")
        lines.append(
            f"{kind:<10} {s['done']:>4}/{s['total']:<4}  "
            + ("; ".join(parts) or "-")
        )
    lines.append("coverage: " + ", ".join(
        f"{name} {c['pct']}% ({c['covered']}/{c['total']})"
        for name, c in st["coverage"].items()
    ))
    tel = st["telemetry"]
    if tel["workers"]:
        nrec = sum(w["records"] for w in tel["workers"].values())
        lines.append(
            f"telemetry: {len(tel['workers'])} worker file(s), {nrec} "
            f"records, {tel['violations']} schema violation(s)"
        )
        for stage, s in sorted(tel["stages"].items()):
            lines.append(
                f"  {stage:<10} span {s['span_s']:>8.3f}s  "
                f"claims {s['claim']}  steals {s['steal']}  "
                f"done {s['done']}"
            )
    else:
        lines.append("telemetry: no records (sink disabled or not started)")
    return "\n".join(lines)


def watch_status(
    out_dir: str | pathlib.Path,
    interval: float = 2.0,
    iterations: int | None = None,
    file=None,
) -> dict:
    """``status --watch``: re-render fleet state every ``interval``
    seconds until the run completes, adding what a single snapshot
    cannot show —

      * per-stage throughput (units done/s) and row-coverage rate with
        an ETA, both from deltas between refreshes;
      * STRAGGLER flags on live leases whose age exceeds the fleet's
        p95 unit hold time (the recorded ``held`` counters — a unit
        held longer than 95% of completed holds is statistically late,
        long before its TTL expires).

    ``iterations`` bounds the loop for tests/CI; returns the last
    status dict.  Pure reader — same files-only observability as
    :func:`fleet_status`, no worker RPC.
    """
    f = file or sys.stdout
    prev_t: float | None = None
    prev_cov: dict[str, int] = {}
    prev_done: dict[str, int] = {}
    n = 0
    while True:
        st = fleet_status(out_dir)
        now = time.time()
        lines = [render_status(st)]
        if prev_t is not None:
            dt = max(now - prev_t, 1e-6)
            for kind, s in st["stages"].items():
                d = s["done"] - prev_done.get(kind, s["done"])
                if d > 0 and s["done"] < s["total"]:
                    rate = d / dt
                    eta = (s["total"] - s["done"]) / rate
                    lines.append(f"watch: {kind} {rate:.2f} units/s, "
                                 f"ETA {eta:.0f}s")
            for name, c in st["coverage"].items():
                d = c["covered"] - prev_cov.get(name, c["covered"])
                if d > 0 and c["covered"] < c["total"]:
                    rate = d / dt
                    eta = (c["total"] - c["covered"]) / rate
                    lines.append(f"watch: {name} {rate:.1f} rows/s, "
                                 f"ETA {eta:.0f}s")
        held = trace.held_percentiles(out_dir)
        p95 = held.get("p95")
        if p95:
            for kind, s in st["stages"].items():
                for l in s["leases"]:
                    if l["age_s"] > p95:
                        lines.append(
                            f"watch: STRAGGLER {l['uid']}@{l['worker']} "
                            f"held {l['age_s']}s > fleet p95 {p95:.1f}s"
                            + (" (lease EXPIRED)" if l["expired"] else ""))
        print("\n".join(lines), file=f, flush=True)
        prev_t = now
        prev_cov = {k: c["covered"] for k, c in st["coverage"].items()}
        prev_done = {k: s["done"] for k, s in st["stages"].items()}
        n += 1
        if st["complete"] or (iterations is not None and n >= iterations):
            return st
        time.sleep(interval)


_FLAGS_EPILOG = """\
commands:
  work (default)      claim and compute units until the run completes
  status              render live lease/coverage/telemetry state and exit
  fsck                verify every store artifact against its recorded
                      checksum (masterless, from files alone) and exit
  trace               assemble the fleet-wide causal trace from recorded
                      telemetry: unit lifecycles, clock-skew-aligned
                      timelines, critical path through the stage DAG,
                      wall-time buckets (compute / gather / store /
                      queue-wait / straggler-tail); writes Chrome
                      trace-event JSON loadable in Perfetto
  trends              render the cross-run history (one summary record
                      appended per finished run): regression flags vs
                      the previous same-fingerprint run and a
                      knob-vs-throughput table

flags (work):
  --out DIR           shared fleet store holding fleet.json   [required]
  --worker-id ID      stable queue identity                   [required]
  --ttl SEC           lease expiry                            [600]
  --poll SEC          barrier poll interval                   [0.25]
  --timeout SEC       max wait on one stage barrier           [3600]
  --unit-retries N    attempts before a unit is poisoned      [3]

flags (status):
  --out DIR           fleet store to inspect                  [required]
  --json              machine-readable status dict
  --expect-complete   exit 1 unless all stages done AND every
                      artifact at 100% row coverage
  --watch             re-render every --interval seconds until complete,
                      with per-stage throughput, ETA, and STRAGGLER
                      flags on leases older than the fleet p95 hold time
  --interval SEC      --watch refresh period                  [2]

flags (fsck):
  --out DIR           store to verify                         [required]
  --json              machine-readable fsck report
  --heal              revoke damaged tiles' manifest entries + queue done
                      markers so one normal fleet pass recomputes exactly
                      the damaged units (refused on a stale fingerprint:
                      wrong INPUTS cannot be healed, only recomputed)
  --expect-clean      exit 1 unless the store verifies clean

flags (trace):
  --out DIR           fleet store whose telemetry to assemble [required]
  --trace-out FILE    Chrome trace JSON path     [<out>/trace.json]
  --json              machine-readable trace analysis (units, stages,
                      buckets, critical path) instead of the one-pager
  --reconcile         exit 1 unless per-stage span totals match
                      `status` within 1% (CI gate)

flags (trends):
  --history FILE      history JSONL to render [<out>/history.jsonl or
                      $EDM_HISTORY; --out optional when given]
  --json              machine-readable trends analysis

environment:
  EDM_TELEMETRY       off | stdout | jsonl:<path>; unset -> per-worker
                      JSONL at <out>/telemetry/<worker-id>.jsonl
  EDM_HISTORY         shared run-history JSONL (default:
                      <out>/history.jsonl; one summary record appended
                      per finished run, same-run reruns replace theirs)
  EDM_FAULTS          fault-injection spec (runtime/faultpoints.py), e.g.
                      tile_pre_rename:crash@3 — testing only
  EDM_COORDINATOR     multi-host mesh (DESIGN.md SS14; applied only when
  EDM_NUM_PROCESSES   fleet.json opts in via its `distributed` flag):
  EDM_PROCESS_ID      coordinator host:port of rank 0, world size, and
                      THIS process's rank; each externally-launched
                      worker exports its own rank before `work`
                      (docs/OPERATIONS.md has the per-host recipe)
"""


def apply_spec_platform(out_dir: str | pathlib.Path) -> None:
    """Fleet workers' platform/mesh opt-in (DESIGN.md SS14): apply the
    fleet.json `platform` tier and — when the spec says `distributed` —
    join the multi-host mesh from this process's own EDM_* rank env.
    MUST run before the worker's first jax backend touch (FleetWorker's
    constructor builds the mesh), hence a free function on the raw spec
    rather than a FleetWorker method."""
    raw = json.loads((pathlib.Path(out_dir) / SPEC_NAME).read_text())
    from repro.runtime import platform as rt_platform

    tier = raw.get("platform")
    if tier:
        rt_platform.apply_platform(tier)
    if raw.get("distributed"):
        rt_platform.init_distributed()


def build_parser() -> argparse.ArgumentParser:
    """The edm_fleet CLI surface — exposed as a function so tests
    (tests/test_docs.py) can parse README/runbook invocations against
    the REAL parser."""
    ap = argparse.ArgumentParser(
        prog="edm_fleet",
        description=__doc__.split("\n")[0],
        epilog=_FLAGS_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("cmd", nargs="?", default="work",
                    choices=["work", "status", "fsck", "trace", "trends"],
                    help="work: run a fleet worker (default); status: "
                    "render live fleet state for --out and exit; fsck: "
                    "verify store integrity (optionally --heal) and exit; "
                    "trace: assemble the fleet causal trace + Chrome "
                    "trace JSON; trends: render the cross-run history")
    ap.add_argument("--out",
                    help="shared fleet store (must hold fleet.json; see "
                    "edm_run --workers or init_fleet); required for every "
                    "command except `trends --history FILE`")
    ap.add_argument("--worker-id",
                    help="stable queue identity; relaunching a killed "
                    "worker under the SAME id reclaims its leases instantly")
    ap.add_argument("--ttl", type=float, default=600.0,
                    help="lease expiry seconds (crashed foreign workers' "
                    "units become claimable after this)")
    ap.add_argument("--poll", type=float, default=0.25,
                    help="barrier poll interval seconds")
    ap.add_argument("--timeout", type=float, default=3600.0,
                    help="max seconds to wait on any one stage barrier")
    ap.add_argument("--unit-retries", type=int, default=3,
                    help="failed compute attempts (fleet-wide) before a "
                    "unit is poisoned and the whole fleet exits nonzero")
    ap.add_argument("--json", action="store_true",
                    help="status: print the machine-readable status dict")
    ap.add_argument("--expect-complete", action="store_true",
                    help="status: exit 1 unless every stage is done and "
                    "every artifact reports 100%% row coverage")
    ap.add_argument("--heal", action="store_true",
                    help="fsck: revoke damaged coverage + done markers so "
                    "a normal fleet pass recomputes exactly what was lost")
    ap.add_argument("--expect-clean", action="store_true",
                    help="fsck: exit 1 unless the store verifies clean")
    ap.add_argument("--watch", action="store_true",
                    help="status: re-render every --interval seconds until "
                    "the run completes, with throughput, ETA, and "
                    "straggler flags (lease age > fleet p95 hold time)")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="status --watch refresh period in seconds")
    ap.add_argument("--trace-out",
                    help="trace: Chrome trace-event JSON destination "
                    "(default <out>/trace.json; load in Perfetto)")
    ap.add_argument("--reconcile", action="store_true",
                    help="trace: exit 1 unless per-stage span totals "
                    "reconcile with `status` within 1%%")
    ap.add_argument("--history",
                    help="trends: history JSONL to render (default "
                    "$EDM_HISTORY or <out>/history.jsonl)")
    return ap


def main(argv=None) -> None:
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.out is None and not (args.cmd == "trends" and args.history):
        ap.error(f"{args.cmd} requires --out")

    if args.cmd == "status":
        if args.watch:
            watch_status(args.out, interval=args.interval)
            return
        st = fleet_status(args.out)
        print(json.dumps(st, indent=1) if args.json else render_status(st))
        if args.expect_complete and not st["complete"]:
            sys.exit(1)
        return

    if args.cmd == "trace":
        tr = trace.assemble_trace(args.out)
        dest = pathlib.Path(args.trace_out) if args.trace_out \
            else pathlib.Path(args.out) / "trace.json"
        trace.write_chrome_trace(args.out, dest)
        rep = trace.reconcile(tr, fleet_status(args.out)) \
            if args.reconcile else None
        if args.json:
            print(json.dumps(
                {**tr, "reconcile": rep} if rep else tr, indent=1))
        else:
            print(trace.render_trace(tr))
            print(f"chrome trace: {dest} (load in Perfetto / "
                  "chrome://tracing)")
            if rep is not None:
                for stage, s in sorted(rep["stages"].items()):
                    print(f"reconcile {stage}: trace {s['trace_s']}s vs "
                          f"status {s['status_s']}s "
                          f"(delta {s['delta_pct']}%)")
        if rep is not None and not rep["ok"]:
            sys.exit(1)
        return

    if args.cmd == "trends":
        hp = pathlib.Path(args.history) if args.history \
            else history.history_path(args.out)
        recs = history.load_history(hp)
        if args.json:
            print(json.dumps(
                {"path": str(hp), **history.analyze_trends(recs)}, indent=1))
        else:
            print(f"history: {hp}")
            print(history.render_trends(recs))
        return

    if args.cmd == "fsck":
        report = integrity.fsck_store(args.out, heal=args.heal)
        print(json.dumps(report, indent=1) if args.json
              else integrity.render_fsck(report))
        if args.expect_clean and not report["clean"]:
            sys.exit(1)
        return

    if not args.worker_id:
        ap.error("work requires --worker-id")
    # Platform tier + optional multi-host mesh join from the shared spec,
    # BEFORE the first jax touch below (DESIGN.md SS14).
    apply_spec_platform(args.out)
    from repro.runtime import platform as rt_platform

    rt_platform.enable_compile_cache()
    telemetry.configure_from_env(
        default_path=telemetry.worker_jsonl(args.out, args.worker_id),
        worker=args.worker_id,
    )
    try:
        FleetWorker(args.out, args.worker_id, ttl=args.ttl, poll=args.poll,
                    timeout=args.timeout,
                    unit_retries=args.unit_retries).run()
    finally:
        telemetry.shutdown()


if __name__ == "__main__":
    main()
