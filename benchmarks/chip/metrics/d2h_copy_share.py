"""d2h_copy_share (%): device-to-host copies of finished blocks over the
window.

Source: the program's ``phase2/d2h_copy`` spans (``np.asarray`` of a
block already computed), clipped to the window.
"""
import program_spans

SPAN = "phase2/d2h_copy"


def read(w):
    return program_spans.window_share(w, SPAN)
