"""surrogates.time_share (%): device time of surrogate generation over the
window, averaged over the devices whose trace covers it.

Source: the profiler trace's ``XLA Modules`` line (``trace_modules``):
runs of the program's jitted ``surrogate_futures``, the module every
surrogate batch is made in (FFT, phases, inverse FFT, futures).  Its FFT
lowers to convolutions on the TPU's matrix unit, whose operations carry
no name of their own, so the module is what names it.
"""
MODULE = "surrogate_futures"


def read(w):
    if w.trace is None or not getattr(w, "modules", None):
        return None
    t = sum(s for nm, s in w.modules.items() if MODULE in nm)
    if t <= 0:
        return None
    return 100.0 * t / w.trace["complete_devices"] / w.trace["window_s"]
