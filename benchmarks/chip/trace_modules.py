"""Device time of the program's jitted modules in the window.

The profiler's device planes hold, beside the ``XLA Ops`` line that
``trace_reduce`` reads, an ``XLA Modules`` line: one event per run of a
compiled program, named after its jitted function (``jit_<name>``).  A
part of the program that no kernel names, such as surrogate generation,
whose FFT the TPU lowers to matrix-unit convolutions, is read here by
the module it runs as.
"""
from __future__ import annotations

import re

import trace_reduce

MODULES_LINE = "XLA Modules"


def module_name(event_name: str) -> str:
    """``jit_surrogate_futures(123)`` -> ``jit_surrogate_futures``."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def module_seconds(profile, anchor_mono: float, mono_end: float,
                   n_devices: int = 1) -> dict[str, float]:
    """Seconds per module name, summed over the first ``n_devices``
    device planes, each event clipped to the window [anchor, anchor +
    (mono_end - anchor_mono)]; {} where the trace has no modules line."""
    w0 = trace_reduce.anchor_ns(profile)
    w1 = w0 + (mono_end - anchor_mono) * 1e9
    planes = sorted(
        (p for p in profile.planes if p.name.startswith("/device:")
         and any(ln.name == trace_reduce.OPS_LINE for ln in p.lines)),
        key=lambda p: int(p.name.rsplit(":", 1)[1]))[:n_devices]
    out: dict[str, float] = {}
    for p in planes:
        for ln in p.lines:
            if ln.name != MODULES_LINE:
                continue
            for ev in ln.events:
                a, b = max(float(ev.start_ns), w0), min(float(ev.end_ns), w1)
                if b > a:
                    nm = module_name(ev.name)
                    out[nm] = out.get(nm, 0.0) + (b - a) * 1e-9
    return out
