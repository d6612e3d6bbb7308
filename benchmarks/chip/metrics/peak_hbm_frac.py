"""peak_hbm_frac: peak device memory over the device's limit.

Source: the runtime's memory counters, ``memory_stats()`` of each device
after the window: ``peak_bytes_in_use`` over ``bytes_limit`` on the
fullest device.  The peak is the process's, set-up included.
"""


def read(w):
    if not getattr(w, "bytes_limit", 0):
        return None
    return w.peak_bytes / w.bytes_limit
