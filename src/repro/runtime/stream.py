"""Async chunk queue: overlap device compute with host-side drains —
the double-buffered chunk streaming of DESIGN.md SS6.

JAX dispatch is asynchronous — a jitted call returns device futures
immediately and only blocks when the host materializes them (np.asarray).
The seed pipeline serialized that: convert chunk i to numpy (blocking on
its compute AND device->host copy), write its row block, only then build
and dispatch chunk i+1.  :class:`ChunkStreamer` keeps up to ``depth``
chunks in flight instead, so with depth=2 (double buffering) chunk i+1's
host->device transfer and compute are already queued while chunk i's
copy-out and TileWriter write drain — the streaming store comes off
the critical path (paper SSIII-C's sequential-block-write design point,
now overlapped).

Backend-agnostic: nothing here is EDM-specific, and later sharding /
multi-host PRs can reuse the same queue for their own chunk loops.
"""
from __future__ import annotations

import collections
from typing import Any, Callable

import numpy as np

from time import perf_counter as _perf

from repro.runtime import telemetry


class ChunkStreamer:
    """Bounded queue of in-flight device chunks with ordered drains.

    drain(tag, host_array) is called in submission order — required by
    consumers like TileWriter whose resume manifest must only cover
    rows that are durably on disk.  Tags are opaque to the streamer; the
    EDM pipeline uses (row0, valid) for full-width row chunks and
    (row0, col0, valid) for the tiled 2D decomposition (DESIGN.md SS7),
    where depth bounds the number of (row-chunk x col-tile) blocks in
    flight — i.e. device-side live tiles — not just row chunks.
    """

    def __init__(
        self,
        drain: Callable[[Any, np.ndarray], None],
        depth: int = 2,
        stage: str = "stream",
    ):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.drain = drain
        self.depth = depth
        self.stage = stage  # telemetry label only (never touches bytes)
        self._pending: collections.deque[tuple[Any, Any]] = collections.deque()

    def __len__(self) -> int:
        return len(self._pending)

    def submit(self, tag: Any, device_value: Any) -> None:
        """Enqueue an (already dispatched) device value; drains the oldest
        chunk(s) once ``depth`` are in flight.  depth=1 therefore drains the
        chunk just submitted — the fully synchronous legacy behaviour; with
        depth=2 the next chunk can be built and dispatched while one older
        chunk is still in flight (double buffering)."""
        self._pending.append((tag, device_value))
        while len(self._pending) >= self.depth:
            self._drain_one()

    def _drain_one(self) -> None:
        tag, dev = self._pending.popleft()
        label = repr(tag)
        with telemetry.span(self.stage, "drain",
                            tag=label, in_flight=len(self._pending),
                            depth=self.depth) as t:
            # gather_s = wait for the device + copy to the host; the two
            # child spans split it.
            with telemetry.span(self.stage, "device_wait", tag=label):
                t0 = _perf()
                wait = getattr(dev, "block_until_ready", None)
                if wait is not None:
                    wait()
                wait_s = _perf() - t0
            with telemetry.span(self.stage, "d2h_copy", tag=label) as c:
                t0 = _perf()
                host = np.asarray(dev)
                copy_s = _perf() - t0
                c["bytes"] = int(host.nbytes)
            t["gather_s"] = wait_s + copy_s
            t["bytes"] = int(host.nbytes)
            self.drain(tag, host)

    def flush(self) -> None:
        """Drain everything still in flight (call once after the loop)."""
        while self._pending:
            self._drain_one()

    def __enter__(self) -> "ChunkStreamer":
        return self

    def __exit__(self, exc_type, *_exc) -> None:
        # Don't mask an in-loop exception with a drain of stale chunks.
        if exc_type is None:
            self.flush()
        else:
            self._pending.clear()
