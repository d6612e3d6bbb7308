"""device_idle_share (%): 1 - busy / window on the fullest device.

Source: the profiler trace.  Busy is the union of the intervals in which
an operation ran on the device (``trace_reduce``); the fullest device is
the one with the most busy time.
"""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * (1.0 - w.trace["busy_s_fullest"] / w.trace["window_s"])
