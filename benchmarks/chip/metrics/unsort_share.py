"""unsort_share (%): the host's column un-sort of the bucketed blocks
over the window.

Source: the program's ``phase2/unsort`` spans (targets back from the
optE-bucket order to their own, on the host), clipped to the window.
"""
import program_spans

SPAN = "phase2/unsort"


def read(w):
    return program_spans.window_share(w, SPAN)
