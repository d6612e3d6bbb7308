"""knn_topk.time_share (%): device time of the streaming kNN kernel over
the window, averaged over the devices whose trace covers it.

Source: the profiler trace; operations whose name contains
``knn_topk_streaming`` (the jitted wrapper of
``knn_topk_stream_kernel``, as the trace names its custom call).
"""
KERNEL = "knn_topk_stream"


def read(w):
    if w.trace is None:
        return None
    import trace_reduce

    t = trace_reduce.kernel_seconds(w.trace, KERNEL)
    if t <= 0:
        return None
    return 100.0 * t / w.trace["complete_devices"] / w.trace["window_s"]
