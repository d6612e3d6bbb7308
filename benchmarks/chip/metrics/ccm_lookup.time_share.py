"""ccm_lookup.time_share (%): device time of the CCM lookup kernel over
the window, averaged over the devices whose trace covers it.

Source: the profiler trace; operations whose name contains
``ccm_lookup`` (the jitted wrapper of
``ccm_lookup_kernel``, as the trace names its custom call).
"""
KERNEL = "ccm_lookup"


def read(w):
    if w.trace is None:
        return None
    import trace_reduce

    t = trace_reduce.kernel_seconds(w.trace, KERNEL)
    if t <= 0:
        return None
    return 100.0 * t / w.trace["complete_devices"] / w.trace["window_s"]
