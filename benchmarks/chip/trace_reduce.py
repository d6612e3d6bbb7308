"""Reduce a profiler trace of the window to device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` and gives, for the window:

- busy time per device: the union of the intervals in which an operation
  ran (events of the device plane's ``XLA Ops`` line), clipped to the
  window; idle is the rest of the window; a device whose trace stops
  early is left out of the means and sums (``complete_devices``);
- device self time per operation, by its HLO instruction name without
  the numeric suffix (``%ccm_lookup.46 = ...`` counts as ``ccm_lookup``);
  an operation nested in another (a kernel inside a loop) is taken out of
  the outer one's time, so the times add up to the busy time;
- the idle gaps, each put down to what the host was doing during most of
  it: the innermost of the program's telemetry spans open then (spans on
  the host's monotonic clock), or no span.

Host and device times share the trace's clock.  The window is placed on
it by an anchor: a ``TraceAnnotation`` named :data:`ANCHOR` entered at a
known monotonic time; the window then runs ``mono_end - anchor_mono``
seconds from the anchor's start.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import pathlib
import re

ANCHOR = "chipbench.window_start"
OPS_LINE = "XLA Ops"
NO_SPAN = "host: no span open"
TOP = 10
CUT = 0.05  # share of the window by which a device's trace may end early


def find_xplane(trace_dir) -> pathlib.Path:
    found = glob.glob(str(pathlib.Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(found)}")
    return pathlib.Path(found[0])


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def anchor_ns(profile) -> float:
    """Start of the anchor annotation on the trace's clock."""
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ANCHOR:
                    return float(ev.start_ns)
    raise ValueError(f"no {ANCHOR!r} event in the trace's host planes")


def op_name(event_name: str) -> str:
    """``%while.67 = (...) while(...)`` -> ``while``."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.(\d+|clone))+$", "", name)


def device_ops(profile, n_devices: int) -> list[list[tuple[str, float, float]]]:
    """(name, start_ns, end_ns) of every operation on each of the first
    ``n_devices`` device planes, in device order."""
    planes = sorted(
        (p for p in profile.planes if p.name.startswith("/device:")
         and any(ln.name == OPS_LINE for ln in p.lines)),
        key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if len(planes) < n_devices:
        raise ValueError(f"trace has {len(planes)} device planes with an "
                         f"{OPS_LINE!r} line, expected {n_devices}")
    out = []
    for p in planes[:n_devices]:
        evs = []
        for ln in p.lines:
            if ln.name == OPS_LINE:
                evs += [(op_name(ev.name), float(ev.start_ns),
                         float(ev.end_ns)) for ev in ln.events]
        out.append(evs)
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a, b, w0, w1):
    return max(a, w0), min(b, w1)


def self_times(events) -> dict[str, float]:
    """Seconds per name of (name, start_ns, end_ns) events, each less the
    time of the events nested inside it."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end]
    for nm, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack and b <= stack[-1][1]:
            out[stack[-1][0]] = out.get(stack[-1][0], 0.0) - (b - a) * 1e-9
        out[nm] = out.get(nm, 0.0) + (b - a) * 1e-9
        stack.append([nm, b])
    return out


def host_segments(spans) -> list[tuple[float, float, str]]:
    """The host's timeline cut where any span opens or closes, each piece
    labelled by the innermost (shortest) span open over it."""
    pts = sorted({p for _, a, b in spans for p in (a, b)})
    opening = sorted(spans, key=lambda s: s[1])
    heap: list[tuple[float, float, str]] = []  # (length, end, name)
    segs, j = [], 0
    for x0, x1 in zip(pts, pts[1:]):
        while j < len(opening) and opening[j][1] <= x0:
            nm, a, b = opening[j]
            heapq.heappush(heap, (b - a, b, nm))
            j += 1
        while heap and heap[0][1] <= x0:
            heapq.heappop(heap)
        label = heap[0][2] if heap else NO_SPAN
        if segs and segs[-1][2] == label and segs[-1][1] == x0:
            segs[-1] = (segs[-1][0], x1, label)
        else:
            segs.append((x0, x1, label))
    return segs


def label_gap(a: float, b: float, segs, starts) -> str:
    """The label covering most of [a, b] in ``host_segments`` (starts:
    their start times); time outside every span counts as NO_SPAN."""
    cover = {NO_SPAN: 0.0}
    covered = 0.0
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(segs) and segs[i][0] < b:
        ov = min(b, segs[i][1]) - max(a, segs[i][0])
        if ov > 0:
            cover[segs[i][2]] = cover.get(segs[i][2], 0.0) + ov
            covered += ov
        i += 1
    cover[NO_SPAN] += (b - a) - covered
    return max(cover.items(), key=lambda kv: kv[1])[0]


def reduce_window(devices_ops, w0: float, w1: float, host_spans=()) -> dict:
    """Metrics of [w0, w1] (ns) from per-device operation lists and host
    spans ((name, start_ns, end_ns))."""
    # The profiler can stop recording one device's operations early (a
    # chip that also runs the futures' copies to the others filled its
    # trace 3.5 s into a 20 s window).  A device whose operations end
    # more than CUT of the window before the others' is left out of the
    # means and sums: its time would read as idle.
    last = [max((b for _, a, b in evs if a < w1), default=w0)
            for evs in devices_ops]
    keep = [min(x, w1) >= min(max(last), w1) - CUT * (w1 - w0) for x in last]
    n = sum(keep)
    per_dev, ops_total, gaps_total = [], {}, {}
    segs = host_segments(host_spans)
    starts = [sg[0] for sg in segs]
    for evs, complete in zip(devices_ops, keep):
        clipped = [(nm, *_clip(a, b, w0, w1)) for nm, a, b in evs]
        clipped = [(nm, a, b) for nm, a, b in clipped if b > a]
        busy = union((a, b) for _, a, b in clipped)
        busy_ns = sum(b - a for a, b in busy)
        ops = self_times(clipped)
        per_dev.append({"busy_s": busy_ns * 1e-9, "ops": ops,
                        "complete": complete})
        if not complete:
            continue
        for nm, t in ops.items():
            ops_total[nm] = ops_total.get(nm, 0.0) + t
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                g = gaps_total.setdefault(label_gap(a, b, segs, starts),
                                          [0.0, 0, 0.0])
                g[0] += (b - a) * 1e-9
                g[1] += 1
                g[2] = max(g[2], (b - a) * 1e-9)
    window_s = (w1 - w0) * 1e-9
    mean_ops = {nm: t / n for nm, t in ops_total.items()}
    top_ops = sorted(mean_ops.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps_total.items(), key=lambda kv: -kv[1][0])[:TOP]
    full = [d for d in per_dev if d["complete"]]
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in full) / n,
        "busy_s_fullest": max(d["busy_s"] for d in full),
        "devices": per_dev,
        "complete_devices": n,
        "ops": mean_ops,
        "breakdown": {
            "device_ops": [[nm, t] for nm, t in top_ops],
            "idle_gaps": [[f"{nm} ({cnt} gaps on {n} device(s), longest "
                           f"{mx!r} s)", t / n]
                          for nm, (t, cnt, mx) in top_gaps],
        },
    }


def reduce_profile(profile, anchor_mono: float, mono_end: float,
                   host_spans=(), n_devices: int = 1) -> dict:
    """:func:`reduce_window` of the window [anchor, anchor + (mono_end -
    anchor_mono)]; host spans given on the monotonic clock (seconds)."""
    a = anchor_ns(profile)
    to_ns = lambda m: a + (m - anchor_mono) * 1e9  # noqa: E731
    spans = [(nm, to_ns(s0), to_ns(s1)) for nm, s0, s1 in host_spans]
    return reduce_window(device_ops(profile, n_devices), a, to_ns(mono_end),
                         spans)


def reduce_dir(trace_dir, anchor_mono: float, mono_end: float,
               host_spans=(), n_devices: int = 1) -> dict:
    return reduce_profile(load(find_xplane(trace_dir)), anchor_mono,
                          mono_end, host_spans, n_devices)


def kernel_seconds(reduced: dict, kernel: str) -> float:
    """Device seconds, summed over the devices whose trace covers the
    window, of operations named after ``kernel``."""
    return sum(t for d in reduced["devices"] if d["complete"]
               for nm, t in d["ops"].items() if kernel in nm)
