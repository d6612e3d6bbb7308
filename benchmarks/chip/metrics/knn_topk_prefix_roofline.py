"""knn_topk_prefix_roofline (%): the least time the chip could take for
the convergence stage's prefix-snapshot kNN tables the window needed,
over the kernel's device time.

Operations and bytes are what the algorithm needs, counted from shapes,
per library row (queries are the row's Lp embedded points, candidates the
first lib_sizes[-1] points of the seeded permutation):

- operations: Lp * Lc * (3 * E_top + n_E), Lc = lib_sizes[-1].
  Distances accumulate over the lags cumulatively, 3 operations
  (subtract, multiply, add) per added dimension up to E_top, the largest
  E of the bucket plan; selection needs at least one comparison per
  candidate for each of the n_E embedding dimensions of the plan, once
  over the sweep whatever the number of library sizes (the prefixes are
  nested: a size's table is the running selection at its boundary);
- bytes: the embedded vectors in (queries and candidates, E_top x Lp
  float32 each), the permutation (Lc int32), and the tables out at every
  library size (S * n_E tables of Lp x (E_top + 1) int32 indices and
  float32 distances).

Rows are those dispatched in the window, padding included: the kernel
computes them.  The least time is the larger of operations over the
chip's measured float32 vector peak and bytes over its HBM bandwidth
(``peaks.json``); its share of the kernel time cannot pass 100%.
"""
KERNEL = "knn_topk_prefix"


def counts(rows: int, Lp: int, Es, lib_sizes) -> tuple[float, float]:
    """(operations, bytes) of prefix-snapshot tables for ``rows`` rows."""
    e_top, n_e, n_s, lc = max(Es), len(Es), len(lib_sizes), lib_sizes[-1]
    ops = float(rows) * Lp * lc * (3 * e_top + n_e)
    nbytes = float(rows) * (2 * e_top * Lp * 4 + lc * 4
                            + n_s * n_e * Lp * (e_top + 1) * 8)
    return ops, nbytes


def read(w):
    if w.trace is None or not getattr(w, "lib_sizes", None):
        return None
    import trace_reduce

    t = trace_reduce.kernel_seconds(w.trace, KERNEL)
    if t <= 0:
        return None
    share = w.trace["complete_devices"] / w.devices
    ops, nbytes = counts(w.chunks * w.chunk_rows * share, w.Lp,
                         sorted(w.optE_counts), w.lib_sizes)
    least = max(ops / w.peaks["f32_vector_ops_per_s"],
                nbytes / w.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
