"""ccm_lookup_roofline (%): the least time the chip could take for the
cross-map lookups the window needed, over the kernel's device time.

Operations and bytes are what the algorithm needs, counted from shapes:

- operations: 2 * (E + 1) * Lp per (library row, target) pair, E the
  target's embedding dimension (a multiply and an add per neighbour per
  point);
- bytes: every target's futures (N x Lp float32) once per chunk on each
  device, the tables (Lp x (E + 1) int32 indices and float32 weights per
  library row and E of the plan), and one float32 rho per pair out, so a
  lookup fused with Pearson is held to the same count.

Rows are those dispatched in the window, padding included.  The least
time is the larger of operations over the measured float32 vector peak
and bytes over the HBM bandwidth (``peaks.json``).
"""
KERNEL = "ccm_lookup"


def counts(rows: int, device_chunks: int, N: int, Lp: int,
           optE_counts: dict) -> tuple[float, float]:
    """(operations, bytes) of the lookups of ``rows`` library rows
    against N targets, ``device_chunks`` chunks summed over devices."""
    ops = float(rows) * sum(2.0 * (e + 1) * Lp * n
                            for e, n in optE_counts.items())
    tables = float(rows) * sum(Lp * (e + 1) * 8 for e in optE_counts)
    nbytes = float(device_chunks) * N * Lp * 4 + tables + float(rows) * N * 4
    return ops, nbytes


def read(w):
    if w.trace is None:
        return None
    import trace_reduce

    t = trace_reduce.kernel_seconds(w.trace, KERNEL)
    if t <= 0:
        return None
    n = w.trace["complete_devices"]  # devices whose kernel time is summed
    ops, nbytes = counts(w.chunks * w.chunk_rows * n / w.devices,
                         w.chunks * n, w.N, w.Lp, w.optE_counts)
    least = max(ops / w.peaks["f32_vector_ops_per_s"],
                nbytes / w.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
