"""sig_store_write_share (%): host time inside the significance stage's
store over the window.

Source: the program's ``sig/drain`` spans.  A drain waits for the device
and copies the block to the host (its ``gather_s``), then writes it
through the ``TileWriter`` (routing, write, fsync, manifest): the
interval from ``gather_s`` after its start to its end, clipped to the
window, is the store's.
"""
import program_spans

SPAN = "sig/drain"


def read(w):
    spans = program_spans.named(w, SPAN)
    if not spans:
        return None
    w0, w1 = w.anchor, w.anchor + w.window_s
    inside = sum(max(0.0, min(b, w1) - max(a + attrs.get("gather_s", 0.0), w0))
                 for _, a, b, attrs in spans)
    return 100.0 * inside / w.window_s
