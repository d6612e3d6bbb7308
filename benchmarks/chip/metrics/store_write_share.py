"""store_write_share (%): host time inside the store over the window.

Source: the program's own telemetry spans (a ``MemorySink`` installed in
the traced run).  For each ``phase2/drain`` span of the window, its
duration less its ``gather_s`` (the wait for the device and the copy to
the host) is the time spent un-sorting and writing the block through the
``TileWriter``, fsync included.  ``gather_s`` alone would not do: it
includes the wait for the device.
"""
SPAN = "phase2/drain"


def read(w):
    spans = [s for s in getattr(w, "spans", ()) if s[0] == SPAN]
    if not spans:
        return None
    store = sum((b - a) - attrs.get("gather_s", 0.0)
                for _, a, b, attrs in spans)
    return 100.0 * store / w.window_s
