"""knn_topk_prefix.time_share (%): device time of the prefix-snapshot kNN
kernel (the convergence stage's tables) over the window, averaged over
the devices whose trace covers it.

Source: the profiler trace; operations whose name contains
``knn_topk_prefix`` (``pallas_call(name=...)`` of
``knn_topk_prefix_kernel``).
"""
KERNEL = "knn_topk_prefix"


def read(w):
    if w.trace is None:
        return None
    import trace_reduce

    t = trace_reduce.kernel_seconds(w.trace, KERNEL)
    if t <= 0:
        return None
    return 100.0 * t / w.trace["complete_devices"] / w.trace["window_s"]
