"""knn_topk_roofline (%): the least time the chip could take for the kNN
tables the window needed, over the kernel's device time.

Operations and bytes are what the algorithm needs, counted from shapes,
per library row (query and candidate points are both the row's Lp
embedded points):

- operations: Lp * Lp * (3 * E_top + n_E).  Distances accumulate over
  the lags cumulatively, 3 operations (subtract, multiply, add) per added
  dimension up to E_top, the largest E of the bucket plan; selection
  needs at least one comparison per candidate for each of the n_E
  embedding dimensions of the plan;
- bytes: the embedded vectors in (queries and candidates, E_top x Lp
  float32 each) and the tables out (n_E tables of Lp x (E_top + 1)
  int32 indices and float32 distances).

Rows are those dispatched in the window, padding included: the kernel
computes them.  The least time is the larger of operations over the
chip's measured float32 vector peak and bytes over its HBM bandwidth
(``peaks.json``); its share of the kernel time cannot pass 100%.
"""
KERNEL = "knn_topk_stream"


def counts(rows: int, Lp: int, Es) -> tuple[float, float]:
    """(operations, bytes) of kNN tables for ``rows`` library rows."""
    e_top, n_e = max(Es), len(Es)
    ops = float(rows) * Lp * Lp * (3 * e_top + n_e)
    nbytes = float(rows) * (2 * e_top * Lp * 4 + n_e * Lp * (e_top + 1) * 8)
    return ops, nbytes


def read(w):
    if w.trace is None:
        return None
    import trace_reduce

    t = trace_reduce.kernel_seconds(w.trace, KERNEL)
    if t <= 0:
        return None
    # each device computes its share of the rows; count the shares of the
    # devices whose kernel time is summed
    share = w.trace["complete_devices"] / w.devices
    ops, nbytes = counts(w.chunks * w.chunk_rows * share, w.Lp,
                         sorted(w.optE_counts))
    least = max(ops / w.peaks["f32_vector_ops_per_s"],
                nbytes / w.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
