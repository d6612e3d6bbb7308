"""Structured runtime telemetry — the fleet's observability spine (DESIGN.md SS11).

The paper reached 101,729 neurons in 199 s only after profiling-driven
tuning of per-node work shapes (SSIV-B); our fleet has three geometry
knobs (chunk rows, target_tile, knn_tile_c) whose values are invisible
at runtime.  This module records WHERE the wall time goes, as structured
records every layer can emit without knowing who is listening:

  * :func:`span` — a timed context manager (``dur_s`` stamped on exit),
    also entered as a ``jax.profiler.TraceAnnotation`` so a profiler
    trace holds it on the device operations' clock;
  * :func:`counter` — a point event with a value (claims, steals, bytes,
    cache entries, calibration results).

Records flow to pluggable SINKS (the ``HomebrewNLP-Jax`` wandblog idiom:
one emit call, N backends):

  * :class:`JsonlSink` — one JSON record per line under the run store
    (``<out>/telemetry/<worker>.jsonl``); the fleet default.  Crash-safe
    by the same temp+fsync+rename discipline as the store manifests: the
    file on disk is ALWAYS a complete, parseable JSONL — a SIGKILL
    mid-flush leaves the previous generation, never a torn line.
  * :class:`MemorySink` — in-process record list for tests.
  * :class:`StdoutSink` — one line per record for CI logs.

Telemetry is byte-invisible to outputs: nothing here touches compute,
and every sink writes only under ``telemetry/`` (never inside an
artifact dir), so W=1 == W=4 byte-identity holds with sinks enabled.
When no sink is configured, :func:`emit` is a cheap no-op — hot paths
may call it unconditionally.

Record schema (version 1; :func:`validate` is the shared checker used
by tests and ``edm_fleet status``):

  v        int     schema version (== 1)
  kind     str     "span" | "counter"
  stage    str     pipeline stage ("phase1", "phase2", "assemble",
                   "sig", "finalize") or runtime layer ("queue",
                   "store", "stream", "engine", "fleet")
  name     str     record name within the stage (e.g. "chunk",
                   "claim", "write_tile", "knn_tile")
  t        float   epoch seconds at emit (span: at exit)
  mono     float   CLOCK_MONOTONIC seconds at emit — the skew/NTP-step
                   immune sibling of ``t`` that runtime/trace.py aligns
                   cross-worker timelines on (extra field; schema-v1
                   validators ignore it)
  dur_s    float   span wall time (spans only)
  value    float   counter value (counters only)
  worker   str     emitting identity (worker id or "main")
  pid      int     emitting process
  seq      int     per-process monotonic sequence number
  attrs    dict    free-form JSON-safe details (row0, bytes, lease age…)

Loss window: the JSONL sink batches ``flush_every`` records per atomic
rewrite, so a SIGKILL can lose at most the records since the last
flush.  The queue flushes at every UNIT boundary (done/failure — see
runtime/workqueue.py) and the fleet at every STAGE boundary, bounding
the loss to the current unit's in-progress tail; an exit hook
(:mod:`atexit`, registered at configure time) flushes on every
non-SIGKILL death so only a hard kill can lose even that.
"""
from __future__ import annotations

import atexit
import contextlib
import json
import os
import pathlib
import sys
import threading
import time
from typing import Iterator

#: pipeline stages every full run walks (the "five stages" of the fleet);
#: validate() additionally accepts the runtime layers below.
PIPELINE_STAGES = ("phase1", "phase2", "assemble", "sig", "finalize")
RUNTIME_STAGES = ("queue", "store", "stream", "engine", "fleet")
SCHEMA_VERSION = 1
#: spans that re-time seconds a span of their own stage already holds
#: (the whole unit; a chunk's dispatch; a drain's wait, copy and
#: un-sort).  The per-stage span totals (`edm_fleet status`, the
#: trace's ``span_totals``, history's ``total_span_s``) leave them out.
NESTED_SPANS = frozenset({"unit", "dispatch", "device_wait", "d2h_copy",
                          "unsort"})

_lock = threading.Lock()
_sinks: list["Sink"] = []
_worker = "main"
_seq = 0
_atexit_registered = False


# ------------------------------------------------------------------- sinks
class Sink:
    """Sink protocol: ``write(record)`` per record, ``flush`` to make
    buffered records durable, ``close`` once at shutdown.  Subclasses
    need not be thread-safe — the module lock serializes calls."""

    def write(self, rec: dict) -> None:  # pragma: no cover - interface
        raise NotImplementedError

    def flush(self) -> None:
        pass

    def close(self) -> None:
        self.flush()


class MemorySink(Sink):
    """In-memory record list (tests)."""

    def __init__(self):
        self.records: list[dict] = []

    def write(self, rec: dict) -> None:
        self.records.append(rec)


class StdoutSink(Sink):
    """One ``telemetry,<stage>,<name>,...`` line per record — greppable
    CI-log form, same field order as the JSONL schema."""

    def __init__(self, file=None):
        self._file = file

    def write(self, rec: dict) -> None:
        f = self._file or sys.stdout
        head = rec["dur_s"] if rec["kind"] == "span" else rec["value"]
        print(
            f"telemetry,{rec['stage']},{rec['name']},{head:.6f},"
            f"{json.dumps(rec.get('attrs') or {}, sort_keys=True)}",
            file=f, flush=True,
        )


class JsonlSink(Sink):
    """Crash-safe JSONL file sink.

    Records accumulate in memory and every flush atomically REWRITES the
    whole file (write-temp + fsync + os.replace — the store-manifest
    durability primitive, imported from data/store so there is one
    implementation).  A reader therefore always sees a complete JSONL
    generation, never a torn tail; a relaunched worker with the same
    sink path re-loads the previous generation so its records survive
    the rewrite.  Record volume is O(chunks + units + tiles) per run —
    small enough that the rewrite stays off any hot path (and flushes
    are batched every ``flush_every`` records regardless).
    """

    def __init__(self, path: str | pathlib.Path, flush_every: int = 32):
        self.path = pathlib.Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self.flush_every = max(1, int(flush_every))
        self._records: list[dict] = list(read_jsonl(self.path))
        self._unflushed = 0

    def write(self, rec: dict) -> None:
        self._records.append(rec)
        self._unflushed += 1
        if self._unflushed >= self.flush_every:
            self.flush()

    def flush(self) -> None:
        if self._unflushed == 0:
            return
        from repro.data.store import atomic_write_text  # lazy: no cycle

        atomic_write_text(
            self.path,
            "".join(json.dumps(r) + "\n" for r in self._records),
        )
        self._unflushed = 0


def read_jsonl(path: str | pathlib.Path) -> list[dict]:
    """Read a telemetry JSONL, tolerating a missing file and (for
    foreign, non-atomic writers) a torn trailing line."""
    p = pathlib.Path(path)
    if not p.exists():
        return []
    out: list[dict] = []
    for line in p.read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            out.append(json.loads(line))
        except ValueError:
            continue  # torn tail of a non-atomic writer
    return out


# ------------------------------------------------------------ configuration
def configure(*sinks: Sink, worker: str | None = None) -> None:
    """Install the process's sink list (replacing any previous ones) and
    optionally its emitting identity.  ``configure()`` with no sinks
    disables telemetry."""
    global _sinks, _atexit_registered
    with _lock:
        for s in _sinks:
            try:
                s.close()
            except OSError:
                pass
        _sinks = list(sinks)
        if worker is not None:
            set_identity(worker)
        if _sinks and not _atexit_registered:
            # Last-chance flush on any non-SIGKILL exit (normal return,
            # sys.exit, unhandled exception): the batched JSONL tail is
            # lost only to a hard kill, and even that loss is bounded by
            # the unit-boundary flushes (see module docstring).
            atexit.register(flush)
            _atexit_registered = True


def configure_from_env(
    default_path: str | pathlib.Path | None = None,
    worker: str | None = None,
) -> None:
    """Honor ``EDM_TELEMETRY``: ``off`` (no sinks), ``stdout``,
    ``jsonl:<path>``, or unset — in which case ``default_path`` (when
    given) enables the JSONL sink there, the fleet/driver default."""
    spec = os.environ.get("EDM_TELEMETRY", "")
    if spec == "off":
        configure(worker=worker)
    elif spec == "stdout":
        configure(StdoutSink(), worker=worker)
    elif spec.startswith("jsonl:"):
        configure(JsonlSink(spec[len("jsonl:"):]), worker=worker)
    elif default_path is not None:
        configure(JsonlSink(default_path), worker=worker)
    else:
        configure(worker=worker)


def set_identity(worker: str) -> None:
    global _worker
    _worker = worker


def enabled() -> bool:
    return bool(_sinks)


def flush() -> None:
    with _lock:
        for s in _sinks:
            s.flush()


def shutdown() -> None:
    configure()


# ------------------------------------------------------------------- emit
def _emit(kind: str, stage: str, name: str, *, dur_s=None, value=None,
          attrs=None) -> None:
    global _seq
    if not _sinks:
        return
    with _lock:
        _seq += 1
        rec = {
            "v": SCHEMA_VERSION,
            "kind": kind,
            "stage": stage,
            "name": name,
            "t": time.time(),
            "mono": time.monotonic(),
            "worker": _worker,
            "pid": os.getpid(),
            "seq": _seq,
            "attrs": dict(attrs or {}),
        }
        if kind == "span":
            rec["dur_s"] = float(dur_s)
        else:
            rec["value"] = float(value)
        for s in _sinks:
            s.write(rec)


def counter(stage: str, name: str, value: float = 1.0, **attrs) -> None:
    """Point event: queue claims/steals/dones, bytes written, cache
    entries, calibration results…"""
    _emit("counter", stage, name, value=value, attrs=attrs)


def emit_clock_anchor(**attrs) -> None:
    """One explicit (epoch, monotonic) clock sample at run/worker start.

    Every record already carries both clocks (``t`` + ``mono``); the
    anchor marks the RUN START on both scales so runtime/trace.py can
    align workers on their monotonic clocks (immune to NTP steps
    mid-run) and detect cross-host epoch skew against the queue's
    causal order.  Emitted by the fleet worker and the edm_run driver,
    never implicitly by :func:`configure` (tests install sinks freely
    and count records)."""
    counter("fleet", "clock_anchor",
            epoch=time.time(), mono=time.monotonic(), **attrs)


def _annotation(label: str):
    """``jax.profiler.TraceAnnotation(label)`` when JAX is already loaded,
    else a no-op: telemetry never imports JAX itself, so a process that
    does not use it (the fleet parent) stays off the backend."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(label)


@contextlib.contextmanager
def span(stage: str, name: str, **attrs):
    """Timed region; ``dur_s`` is wall time between enter and exit.  The
    yielded dict lets the body add attrs discovered mid-span (e.g. fsync
    time, tile count).  Emits nothing when no sink is configured.

    Under a sink the span also enters a profiler annotation named
    ``<stage>/<name>``, so a ``jax.profiler`` trace shows it in the host
    plane, on the device operations' clock."""
    if not _sinks:
        yield {}
        return
    extra: dict = {}
    with _annotation(f"{stage}/{name}"):
        t0 = time.perf_counter()
        try:
            yield extra
        finally:
            _emit("span", stage, name, dur_s=time.perf_counter() - t0,
                  attrs={**attrs, **extra})


# ------------------------------------------------------------- validation
_REQUIRED = {"v": int, "kind": str, "stage": str, "name": str, "t": float,
             "worker": str, "pid": int, "seq": int, "attrs": dict}


def validate(rec: dict) -> list[str]:
    """Schema check; returns a list of violations (empty == valid)."""
    errs: list[str] = []
    for field, typ in _REQUIRED.items():
        if field not in rec:
            errs.append(f"missing field {field!r}")
        elif typ is float:
            if not isinstance(rec[field], (int, float)):
                errs.append(f"{field}={rec[field]!r} not a number")
        elif not isinstance(rec[field], typ):
            errs.append(f"{field}={rec[field]!r} not {typ.__name__}")
    if errs:
        return errs
    if rec["v"] != SCHEMA_VERSION:
        errs.append(f"schema version {rec['v']} != {SCHEMA_VERSION}")
    if rec["kind"] == "span":
        if not isinstance(rec.get("dur_s"), (int, float)) or rec["dur_s"] < 0:
            errs.append(f"span dur_s={rec.get('dur_s')!r} invalid")
    elif rec["kind"] == "counter":
        if not isinstance(rec.get("value"), (int, float)):
            errs.append(f"counter value={rec.get('value')!r} invalid")
    else:
        errs.append(f"kind={rec['kind']!r} not span|counter")
    if rec["stage"] not in PIPELINE_STAGES + RUNTIME_STAGES:
        errs.append(f"stage={rec['stage']!r} unknown")
    try:
        json.dumps(rec["attrs"])
    except (TypeError, ValueError):
        errs.append("attrs not JSON-serializable")
    return errs


# -------------------------------------------------------------- store I/O
def store_telemetry_dir(out_dir: str | pathlib.Path) -> pathlib.Path:
    return pathlib.Path(out_dir) / "telemetry"


def worker_jsonl(out_dir: str | pathlib.Path, worker: str) -> pathlib.Path:
    return store_telemetry_dir(out_dir) / f"{worker}.jsonl"


def iter_store_records(
    out_dir: str | pathlib.Path,
) -> Iterator[tuple[str, dict]]:
    """Yield (worker_file_stem, record) over every per-worker JSONL a
    run store holds — the replay input of ``runtime/autotune`` and the
    summary input of ``edm_fleet status``."""
    d = store_telemetry_dir(out_dir)
    if not d.exists():
        return
    for p in sorted(d.glob("*.jsonl")):
        for rec in read_jsonl(p):
            yield p.stem, rec


# ---------------------------------------------------- compile-cache probe
def compile_cache_entries() -> int | None:
    """Entry count of the JAX persistent compilation cache directory
    (runtime/platform.compile_cache_dir: the environment's, or the one
    enable_compile_cache placed), or None when no cache is configured.
    Pipelines snapshot this at stage boundaries: the DELTA is the number
    of fresh compilations the stage paid (everything else was a cache
    hit — the fleet's straggler metric, DESIGN.md SS10)."""
    from repro.runtime.platform import compile_cache_dir

    d = compile_cache_dir()
    if d is None:
        return None
    if not d.exists():  # configured; JAX creates it on the first write
        return 0
    try:
        return sum(1 for _ in d.iterdir())
    except OSError:
        return None


def emit_compile_cache(stage: str, before: int | None) -> int | None:
    """Counter of new persistent-cache entries since ``before``; returns
    the new snapshot (chainable across stages)."""
    now = compile_cache_entries()
    if now is not None and before is not None:
        counter(stage, "compile_cache", float(now - before),
                entries=now, new=now - before)
    return now
