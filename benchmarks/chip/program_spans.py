"""The program's telemetry spans of a traced run, read for the metrics.

``drivers/ccm_share.py`` hands the readers ``w.spans``: each span of the
program's ``MemorySink`` that overlaps the window, as ``(stage/name,
start, end, attrs)`` on the host's monotonic clock, with the window at
``[w.anchor, w.anchor + w.window_s]``.  A program that does not emit a
span gives no reading (``None``), never a zero.
"""
from __future__ import annotations


def named(w, name: str) -> list:
    return [s for s in getattr(w, "spans", ()) if s[0] == name]


def window_share(w, name: str) -> float | None:
    """Per cent of the window inside spans called ``name``, each clipped
    to the window (spans of one name do not overlap: the host runs one
    chunk's drain at a time)."""
    spans = named(w, name)
    if not spans:
        return None
    w0, w1 = w.anchor, w.anchor + w.window_s
    inside = sum(max(0.0, min(b, w1) - max(a, w0)) for _, a, b, _ in spans)
    return 100.0 * inside / w.window_s


def unit_attr(w, key: str) -> float | None:
    """``key`` of the ``phase2/unit`` span (one ``run_phase2_chunks``
    call) that runs the window; with several, the first to start."""
    units = [s for s in named(w, "phase2/unit") if key in s[3]]
    if not units:
        return None
    return float(min(units, key=lambda s: s[1])[3][key])
