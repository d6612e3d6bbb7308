"""Pallas TPU kernels: cumulative multi-E pairwise distances + fused top-k.

The paper's hot spot (97% of cppEDM runtime) re-architected for TPU
(DESIGN.md SS2/SS8).  ONE selection layout — STREAMING:

``knn_topk_stream_kernel``: the grid has a minor-most CANDIDATE-TILE
dimension.  Each program accumulates a (block_q, tile_c) distance tile
on-chip from the lag slices, partial-sorts the tile to its own top-k
with the k-pass selector, and folds it into a running SORTED
(E_max, block_q, k) top-k carried in VMEM scratch across tiles via a
two-pointer merge under the same (distance, arrival) order as the
shared bitonic network of the jnp builders (core/knn.merge_topk_sorted).
Per-program VMEM is O(E_max*tile_c + block_q*tile_c + E_max*block_q*k)
— INDEPENDENT of Lc (``stream_block_shapes`` is the pure shape function
the CI guard asserts on): arbitrary library lengths fit a 16 MB VMEM
budget, and a tile covering the whole library degenerates to one direct
selection, so small libraries pay nothing for the tiling.

TPU layout: queries ride the sublane axis ((rows, E) query blocks, so a
lag column is a (rows, 1) vector) and candidates the lane axis
((E, tile_c) blocks, tile_c a multiple of 128); every in-kernel write
of a selected entry goes through a lane-iota mask, never a dynamic
lane offset.

``knn_topk_prefix_kernel``: the same running merge with candidate tiles
CLIPPED at library-size boundaries (DESIGN.md SS9): candidates are
pre-gathered into sweep order (applying the optional ``col_ids``
permutation), each clipped segment padded to ``tile_c`` with masked
id -1 columns, and the running carry is emitted to the per-size output
slot at every boundary tile — the one-sweep prefix-snapshot tables of
the CCM convergence diagnostic, in-kernel, replacing the per-size
rebuild fallback the Pallas engines used to inherit.

Shared selection machinery: the per-tile top-k is a fused k-pass masked
argmin on the VPU (k = E+1 <= 21); TPU has no radix-sort analogue, and
k-pass selection is O(k*width) vector work per row versus
O(width log width) for a sort.  Candidate columns are padded to the lane
boundary and masked with _BIG.  Tie rule: argmin picks the first minimum
position, and the merge's (distance, arrival) order keeps running
entries ahead of tile entries — equal distances always resolve to the
earliest sweep position (the lowest candidate id in natural order),
exactly the lax.top_k rule, so the kernels and the jnp builders agree
bit-for-bit.

Ragged queries: wrappers split the query axis into full ``block_q``
blocks plus one 8-row-aligned tail block (``_query_splits``), so a ragged
Lq pays O(8) padded rows of selection work instead of a whole extra
block.

``dist_dtype`` (EDMConfig.dist_dtype): the distance ACCUMULATOR runs in
this dtype (bfloat16 halves the tile working set); merge keys and output
distances are always float32.
"""
from __future__ import annotations

import bisect
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# THE shared pinned-rounding accumulate (maximum(sq, 0) FMA guard): one
# definition for the jnp builders, the kernels, and the ref oracle — the
# exact float sequence the cross-layout bit-identity contract rests on.
from repro.core.knn import _acc_sq_cols

_BIG = 3.0e38  # finite +inf stand-in (avoids inf-inf NaNs)
_IMAX = 2147483647  # python literal: a jnp scalar here would be captured
# by pallas kernel traces as a constant, which pallas_call rejects.
_LANES = 128  # TPU vreg lane width: minor block dims are multiples of it
# Query blocks are independent; the candidate-tile axis carries the
# running top-k (and the prefix kernel's revisited snapshot block).
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary")
)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _query_splits(Lq: int, block_q: int) -> list[tuple[int, int, int]]:
    """Query-axis work plan: [(row0, rows, block)] — full ``block_q``
    blocks plus one 8-row-aligned tail block for the ragged remainder
    (sublane granularity), so padded tail rows cost at most 7 rows of
    k-pass VPU work instead of a whole extra block.  Queries ride the
    sublane axis of the kernels' (rows, E) query blocks, so an 8-aligned
    tail block is a legal TPU block shape."""
    main = (Lq // block_q) * block_q
    splits = []
    if main:
        splits.append((0, main, block_q))
    rem = Lq - main
    if rem:
        splits.append((main, rem, min(block_q, max(8, _round_up(rem, 8)))))
    return splits


def _over_query_splits(Vq, block_q, call_split, q_axis: int = 1):
    """Shared wrapper scaffold: run ``call_split(VqT_p, row0, rows_pad,
    bq)`` -> (idx, dist) over the _query_splits plan and stitch the
    per-split results back along the query axis (``q_axis`` of the OUTPUT
    arrays).  Vq is (E, Lq); each split hands the kernel its queries
    TRANSPOSED to (rows_pad, E), padded to a block multiple — queries on
    sublanes, lag rows on lanes, so the kernel reads one lag column as a
    (rows, 1) vector against a (1, tile_c) candidate row."""
    Lq = Vq.shape[1]
    take = (slice(None),) * q_axis
    outs = []
    for row0, rows, bq in _query_splits(Lq, block_q):
        rows_pad = pl.cdiv(rows, bq) * bq
        VqT_p = jnp.pad(
            Vq[:, row0 : row0 + rows].T, ((0, rows_pad - rows), (0, 0))
        )
        idx, dist = call_split(VqT_p, row0, rows_pad, bq)
        outs.append((idx[take + (slice(0, rows),)],
                     dist[take + (slice(0, rows),)]))
    if len(outs) == 1:
        return outs[0]
    return (
        jnp.concatenate([o[0] for o in outs], axis=q_axis),
        jnp.concatenate([o[1] for o in outs], axis=q_axis),
    )


def _lane_tile(tile_c: int, k: int, width: int) -> int:
    """Candidate-tile width legal on the TPU: a multiple of the 128-lane
    vreg width, at least k (the per-tile partial sort needs k real
    columns) and no wider than the lane-padded ``width``."""
    return max(_round_up(k, _LANES), min(_round_up(tile_c, _LANES),
                                         _round_up(width, _LANES)))


def _kpass_select(md, mi, k):
    """Fused k-pass masked-argmin top-k over a (rows, width) buffer.

    md: f32 merge keys; mi: i32 candidate ids per column (a (1, width)
    row broadcast over the rows), OR a scalar BASE when the ids are
    affine in the column position (id = base + column, the stream
    kernel's natural-order tiles) — the affine form skips the full-width
    id-extraction pass (``base + argmin`` is a per-row add).  The argmin
    is the first column holding the row minimum (min over the column
    iota where the key equals the minimum).  Selected positions are
    knocked out with +inf (strictly above the _BIG mask value, so an
    already-taken position can never shadow a real masked candidate),
    and pass ``kk`` writes its pick into output lane ``kk`` through a
    lane-iota mask — no dynamic-offset store, which Mosaic cannot lower.
    Returns (ids, dists) each (rows, k), sorted ascending with ties
    resolved to the earliest buffer position — identical for both id
    forms.
    """
    rows, width = md.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)
    affine = jnp.ndim(mi) == 0

    def body(kk, carry):
        md_cur, idxs, dists = carry
        m = jnp.min(md_cur, axis=1, keepdims=True)
        am = jnp.min(jnp.where(md_cur == m, pos, _IMAX), axis=1, keepdims=True)
        hit = pos == am
        if affine:
            sel = mi + am
        else:
            sel = jnp.min(jnp.where(hit, mi, _IMAX), axis=1, keepdims=True)
        here = slot == kk
        idxs = jnp.where(here, sel, idxs)
        dists = jnp.where(here, m, dists)
        md_cur = jnp.where(hit, jnp.float32(jnp.inf), md_cur)
        return md_cur, idxs, dists

    _, idxs, dists = jax.lax.fori_loop(
        0,
        k,
        body,
        (
            md,
            jnp.zeros((rows, k), jnp.int32),
            jnp.zeros((rows, k), jnp.float32),
        ),
    )
    return idxs, dists


def _merge_sorted(run_i, run_d, new_i, new_d, k):
    """Two-pointer merge of two sorted (rows, k) top-k lists -> the top-k
    of their union, (rows, k) ascending.

    The in-kernel form of core/knn.merge_topk_sorted: the same total
    order — (distance, arrival) with every running entry arriving before
    every tile entry, so ties go to the running list and, within a list,
    to the earlier position — hence the same output bit-for-bit, but
    built from lane-mask selects and lane reductions only (the network's
    reshapes and reversals along the lane axis do not lower in Mosaic).
    Step kk reads the head of each list through a lane-iota mask, takes
    the smaller (running on ties; an exhausted list never wins) and
    writes it to output lane kk.  O(k^2) lane work per row — small next
    to the k-pass tile selection it follows.
    """
    rows = run_d.shape[0]
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)

    def head(a, p, fill):
        return jnp.min(jnp.where(slot == p, a, fill), axis=1, keepdims=True)

    def body(kk, carry):
        pr, pn, oi, od = carry
        rd, nd = head(run_d, pr, jnp.inf), head(new_d, pn, jnp.inf)
        ri, ni = head(run_i, pr, _IMAX), head(new_i, pn, _IMAX)
        take = (pr < k) & ((pn >= k) | (rd <= nd))
        here = slot == kk
        oi = jnp.where(here, jnp.where(take, ri, ni), oi)
        od = jnp.where(here, jnp.where(take, rd, nd), od)
        step = take.astype(jnp.int32)
        return pr + step, pn + (1 - step), oi, od

    zero = jnp.zeros((rows, 1), jnp.int32)
    _, _, oi, od = jax.lax.fori_loop(
        0, k, body,
        (zero, zero, jnp.zeros((rows, k), jnp.int32),
         jnp.zeros((rows, k), jnp.float32)),
    )
    return oi, od


def _restore_inf(d):
    """Masked candidates carry the finite _BIG inside the selection (the
    k-pass knockout needs +inf strictly above the mask value); the dense
    oracle reports them as +inf, so restore inf on the way out — only
    reachable in the degenerate k == Lc case where a masked self is
    selected."""
    return jnp.where(d >= _BIG, jnp.float32(jnp.inf), d)


# ------------------------------------------------------------- streaming
def stream_block_shapes(
    E_max: int, k: int, block_q: int, tile_c: int
) -> dict[str, tuple[int, ...]]:
    """Per-program block/scratch shapes of the streaming kernel.

    A PURE function of (E_max, k, block_q, tile_c): the library length Lc
    appears nowhere — it only scales the GRID — which is the flat-VMEM
    scaling guarantee the CI guard asserts (tests/test_knn_streaming).
    ``knn_topk_stream_pallas`` builds its BlockSpecs and scratch from this
    dict, so the guard constrains the real kernel, not a copy.  Queries
    arrive transposed, (block_q, E_max): query rows on sublanes.

    ``tile_ids``/``tile_topk``/``merge`` are kernel-internal working
    arrays (the candidate-id lanes, the tile's own partial top-k, and the
    merged (id, dist) output of the two-pointer merge), tracked here so
    ``stream_vmem_bytes`` models the true peak.
    """
    return {
        "vq": (block_q, E_max),
        "vc_tile": (E_max, tile_c),
        "out": (E_max, block_q, k),
        "scratch_idx": (E_max, block_q, k),
        "scratch_dist": (E_max, block_q, k),
        "tile_ids": (block_q, tile_c),
        "tile_topk": (block_q, k),
        "merge": (block_q, k),
    }


def stream_vmem_bytes(
    E_max: int, k: int, block_q: int, tile_c: int, dist_dtype=jnp.float32
) -> int:
    """VMEM budget estimate for one streaming program (DESIGN.md SS8):
    blocks + scratch + the distance tile (dist_dtype) + the candidate-id
    lanes + the tile partial top-k + the merge output.  Independent of
    Lc."""
    s = stream_block_shapes(E_max, k, block_q, tile_c)
    n = lambda shp: functools.reduce(lambda a, b: a * b, shp, 1)
    it = jnp.dtype(dist_dtype).itemsize
    return (
        4 * (n(s["vq"]) + n(s["vc_tile"]))  # f32 lag blocks
        + 4 * (n(s["out"]) * 2)  # idx + dist output blocks
        + 4 * (n(s["scratch_idx"]) + n(s["scratch_dist"]))
        + it * block_q * tile_c  # distance tile accumulator
        + 4 * n(s["tile_ids"])  # i32 candidate-id lanes
        + (4 + 4) * n(s["tile_topk"])  # tile partial top-k (id + dist)
        + (4 + 4) * n(s["merge"])  # merged (id, dist)
    )


def knn_topk_stream_kernel(
    vq_ref,
    vc_ref,
    idx_ref,
    dist_ref,
    idx_s=None,
    dist_s=None,
    *,
    E_max: int,
    k: int,
    Lc: int,
    block_q: int,
    tile_c: int,
    exclude_self: bool,
    row0: int = 0,
    dist_dtype=jnp.float32,
    single_tile: bool = False,
):
    """Grid (query_block, candidate_tile); candidate tiles are minor-most,
    so the running (E_max, block_q, k) top-k in VMEM scratch accumulates
    across the tiles of one query block and is flushed to the output block
    on the last tile.

    The running scratch is kept SORTED by (distance, arrival) as an
    invariant: each tile is partial-sorted to its own top-k with the
    k-pass selector (O(k*tile_c) VPU work); the FIRST tile's top-k seeds
    the scratch directly (a merge against sentinels is an identity — and
    with ``single_tile`` statically true, the whole scratch/merge/flush
    machinery drops out of the program: the one-tile grid IS a direct
    dense selection, the small-library fast case the calibrator
    exploits); every later tile folds in with the two-pointer merge —
    running entries (globally earlier sweep positions, i.e. smaller
    candidate ids) win ties, so equal distances resolve to the lowest
    candidate id, exactly the lax.top_k rule: bit-identical to the jnp
    builders and the dense oracle.
    """
    qi = pl.program_id(0)
    ci = pl.program_id(1)

    base = ci * tile_c
    col_ids = base + jax.lax.broadcasted_iota(jnp.int32, (1, tile_c), 1)
    invalid = jnp.broadcast_to(col_ids >= Lc, (block_q, tile_c))
    if exclude_self:
        row_ids = row0 + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0
        )
        invalid = invalid | (col_ids == row_ids)

    vq = vq_ref[...]  # (block_q, E_max)
    lane = jax.lax.broadcasted_iota(jnp.int32, vq.shape, 1)

    def per_e(e, D):
        # lag column e of the query block (a masked lane sum: exact) and
        # lag row e of the candidate tile
        q = jnp.sum(jnp.where(lane == e, vq, 0.0), axis=1, keepdims=True)
        D = _acc_sq_cols(D, q, vc_ref[pl.ds(e, 1), :], dist_dtype)
        Dm = jnp.where(invalid, _BIG, D.astype(jnp.float32))
        t_i, t_d = _kpass_select(Dm, base, k)  # affine ids
        if single_tile:
            idx_ref[e] = t_i
            dist_ref[e] = _restore_inf(t_d)
            return D

        @pl.when(ci == 0)
        def _seed():
            idx_s[e] = t_i
            dist_s[e] = t_d

        @pl.when(ci != 0)
        def _fold():
            m_i, m_d = _merge_sorted(idx_s[e], dist_s[e], t_i, t_d, k)
            idx_s[e] = m_i
            dist_s[e] = m_d

        return D

    jax.lax.fori_loop(
        0, E_max, per_e, jnp.zeros((block_q, tile_c), dist_dtype)
    )

    if not single_tile:

        @pl.when(ci == pl.num_programs(1) - 1)
        def _flush():
            idx_ref[...] = idx_s[...]
            dist_ref[...] = _restore_inf(dist_s[...])


def knn_topk_stream_pallas(
    Vq: jax.Array,
    Vc: jax.Array,
    k: int,
    exclude_self: bool,
    *,
    interpret: bool,
    block_q: int = 128,
    tile_c: int = 512,
    dist_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """Raw streaming pallas_call wrapper (padding via ops.knn_topk_streaming).

    VMEM per program is stream_vmem_bytes(...) — flat in Lc — so library
    length is bounded by HBM, not by the 16 MB VMEM budget.  tile_c is
    made lane-legal by ``_lane_tile`` (a multiple of 128, >= k, no wider
    than the padded library — a tile covering Lc is one direct selection,
    the small-library fast case the calibrator exploits).  ``interpret``
    is explicit: True runs the Pallas interpreter (tests, the
    ``pallas-interpret`` engine), False compiles with Mosaic for the TPU.
    """
    E_max = Vq.shape[0]
    Lc = Vc.shape[1]
    if k > Lc:
        raise ValueError(f"k={k} exceeds candidate count Lc={Lc}")
    tile_c = _lane_tile(tile_c, k, Lc)
    n_c = pl.cdiv(Lc, tile_c)
    # Balance tile widths under the cap (same tile count, lane-aligned
    # ceil(Lc / n_c) width) so the grid pays O(128 * n_c) padded columns
    # instead of a whole ragged tail tile.
    tile_c = _lane_tile(pl.cdiv(Lc, n_c), k, Lc)
    Vc_p = jnp.pad(Vc, ((0, 0), (0, n_c * tile_c - Lc)))

    def call_split(VqT_p, row0, rows_pad, bq):
        shapes = stream_block_shapes(E_max, k, bq, tile_c)
        kernel = functools.partial(
            knn_topk_stream_kernel,
            E_max=E_max,
            k=k,
            Lc=Lc,
            block_q=bq,
            tile_c=tile_c,
            exclude_self=exclude_self,
            row0=row0,
            dist_dtype=dist_dtype,
            single_tile=n_c == 1,
        )
        # one-tile grids select directly into the outputs: no running
        # top-k scratch to allocate or flush
        scratch = [] if n_c == 1 else [
            pltpu.VMEM(shapes["scratch_idx"], jnp.int32),
            pltpu.VMEM(shapes["scratch_dist"], jnp.float32),
        ]
        return pl.pallas_call(
            kernel,
            grid=(rows_pad // bq, n_c),
            in_specs=[
                pl.BlockSpec(shapes["vq"], lambda i, j: (i, 0)),
                pl.BlockSpec(shapes["vc_tile"], lambda i, j: (0, j)),
            ],
            out_specs=[
                pl.BlockSpec(shapes["out"], lambda i, j: (0, i, 0)),
                pl.BlockSpec(shapes["out"], lambda i, j: (0, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((E_max, rows_pad, k), jnp.int32),
                jax.ShapeDtypeStruct((E_max, rows_pad, k), jnp.float32),
            ],
            scratch_shapes=scratch,
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
            name="knn_topk_stream",
        )(VqT_p, Vc_p)

    return _over_query_splits(Vq, block_q, call_split)


# ------------------------------------------- prefix snapshots (DESIGN SS9)
def prefix_block_shapes(
    E_hi: int, nb: int, k: int, block_q: int, tile_c: int
) -> dict[str, tuple[int, ...]]:
    """Per-program block/scratch shapes of the prefix-snapshot kernel —
    like ``stream_block_shapes``, a pure function of the static tile
    parameters: neither the library length nor the NUMBER of library
    sizes appears (the size count S only scales the output allocation
    and the grid's boundary-tile count), so prefix snapshots inherit the
    flat-VMEM guarantee.  The candidate ids are one (1, tile_c) lane row
    of a (1, n_tiles * tile_c) array — a block whose sublane dim equals
    the array's, as the TPU tiling rule requires."""
    return {
        "vq": (block_q, E_hi),
        "vc_tile": (E_hi, tile_c),
        "ids": (1, tile_c),
        "out": (1, nb, block_q, k),
        "scratch_idx": (nb, block_q, k),
        "scratch_dist": (nb, block_q, k),
        "tile_topk": (block_q, k),
        "merge": (block_q, k),
    }


def knn_topk_prefix_kernel(
    slot_ref,  # scalar-prefetch (n_tiles,) snapshot-slot table; consumed
    # by the output index_map, unused in the body.
    vq_ref,
    vc_ref,
    ids_ref,
    idx_ref,
    dist_ref,
    idx_s,
    dist_s,
    *,
    buckets: tuple[int, ...],
    k: int,
    block_q: int,
    tile_c: int,
    exclude_self: bool,
    row0: int = 0,
    dist_dtype=jnp.float32,
):
    """In-kernel prefix snapshots: the streaming running merge over
    candidate tiles pre-clipped at library-size boundaries.

    Candidates arrive pre-gathered in sweep order (the ``col_ids``
    permutation already applied by the wrapper); ``ids_ref`` carries each
    lane's ORIGINAL candidate id, -1 on the padding that fills clipped
    segments up to ``tile_c`` (masked to _BIG like out-of-range columns,
    so padding never enters a table — every prefix holds >= k real
    candidates by the wrapper's validation).  Selection runs only at the
    ``buckets`` dimensions into an (nb, block_q, k) sorted running
    scratch.  Every program writes the carry to its snapshot slot's
    output block; consecutive tiles of one slot revisit the same block
    (one VMEM-resident write), and the LAST writer is the tile ending
    exactly at the slot's library-size boundary — so each emitted slot
    holds the prefix table, bit-identical to the one-sweep jnp builder
    and the per-size rebuild oracle.
    """
    qi = pl.program_id(0)
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        idx_s[...] = jnp.zeros(idx_s.shape, jnp.int32)
        dist_s[...] = jnp.full(dist_s.shape, jnp.inf, jnp.float32)

    ids = ids_ref[...]  # (1, tile_c)
    invalid = jnp.broadcast_to(ids < 0, (block_q, tile_c))
    if exclude_self:
        row_ids = row0 + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0
        )
        invalid = invalid | (ids == row_ids)

    vq = vq_ref[...]  # (block_q, E_hi)
    lane = jax.lax.broadcasted_iota(jnp.int32, vq.shape, 1)

    def per_e(e, D):
        q = jnp.sum(jnp.where(lane == e, vq, 0.0), axis=1, keepdims=True)
        D = _acc_sq_cols(D, q, vc_ref[pl.ds(e, 1), :], dist_dtype)
        # selection only at the bucket dimensions; bucket E's slot is the
        # number of buckets below it
        is_bucket = functools.reduce(
            jnp.logical_or, [e + 1 == E for E in buckets]
        )
        si = sum(jnp.where(e + 1 > E, 1, 0) for E in buckets)

        @pl.when(is_bucket)
        def _select():
            Dm = jnp.where(invalid, _BIG, D.astype(jnp.float32))
            t_i, t_d = _kpass_select(Dm, ids, k)
            # Slot si folds bucket si's tile selection; the first tile's
            # merge against the inf-seeded scratch is an identity.
            m_i, m_d = _merge_sorted(idx_s[si], dist_s[si], t_i, t_d, k)
            idx_s[si] = m_i
            dist_s[si] = m_d
            idx_ref[0, si] = m_i
            # Restore +inf on masked-selected entries (see the stream
            # kernel's flush) so the carry matches the jnp builders
            # bit-for-bit even in the degenerate k == prefix-size case.
            dist_ref[0, si] = _restore_inf(m_d)

        return D

    jax.lax.fori_loop(
        0, buckets[-1], per_e, jnp.zeros((block_q, tile_c), dist_dtype)
    )


def knn_topk_prefix_pallas(
    Vq: jax.Array,
    Vc: jax.Array,
    k: int,
    exclude_self: bool,
    buckets: tuple[int, ...],
    lib_sizes: tuple[int, ...],
    *,
    interpret: bool,
    block_q: int = 128,
    tile_c: int = 512,
    dist_dtype=jnp.float32,
    col_ids: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Raw prefix-snapshot pallas_call wrapper (DESIGN.md SS9).

    Returns (idx, sq_dists), each (S, len(buckets), Lq, k) — the same
    contract (and bit-identical output) as
    core/knn.knn_tables_prefix_streaming / _rebuild.

    The ragged clipped tiles of ``_prefix_tile_bounds`` (the SAME bounds
    the jnp one-sweep builder uses) are made uniform for the Pallas grid
    by a static gather plan: position j of padded tile t maps to sweep
    position bounds[t].start + j (through the optional ``col_ids``
    permutation) or to a masked -1 lane.  Each tile's snapshot SLOT (the
    library size whose boundary closes the tile's segment) rides in as a
    scalar-prefetch vector the output index_map indexes (index maps may
    not capture array constants), so no dynamic stores are needed.
    """
    from repro.core import knn as core_knn

    E_rows, Lq = Vq.shape
    Lc = Vc.shape[1]
    core_knn._check_prefix_args(
        Lq, Lc, k, exclude_self, buckets, lib_sizes, E_rows, col_ids
    )
    E_hi = buckets[-1]
    nb = len(buckets)
    S = len(lib_sizes)
    need = k + 1 if exclude_self else k
    tile_c = _round_up(max(tile_c, need), _LANES)
    bounds = core_knn._prefix_tile_bounds(lib_sizes, tile_c)
    n_tiles = len(bounds)

    pos = np.zeros((n_tiles, tile_c), np.int32)
    valid = np.zeros((n_tiles, tile_c), bool)
    slots = np.zeros((n_tiles,), np.int32)
    for t, (start, stop) in enumerate(bounds):
        w = stop - start
        pos[t, :w] = np.arange(start, stop, dtype=np.int32)
        valid[t, :w] = True
        slots[t] = bisect.bisect_left(lib_sizes, stop)
    posj = jnp.asarray(pos.reshape(1, -1))
    validj = jnp.asarray(valid.reshape(1, -1))
    if col_ids is None:
        ids_val = posj
    else:
        ids_val = jnp.take(col_ids.astype(jnp.int32), posj)
    ids = jnp.where(validj, ids_val, -1)  # (1, n_tiles * tile_c)
    gather = jnp.where(validj, ids_val, 0).reshape(-1)
    Vc_g = jnp.take(Vc[:E_hi], gather, axis=1)  # (E_hi, n_tiles * tile_c)
    slot_arr = jnp.asarray(slots)

    def call_split(VqT_p, row0, rows_pad, bq):
        shapes = prefix_block_shapes(E_hi, nb, k, bq, tile_c)
        kernel = functools.partial(
            knn_topk_prefix_kernel,
            buckets=tuple(buckets),
            k=k,
            block_q=bq,
            tile_c=tile_c,
            exclude_self=exclude_self,
            row0=row0,
            dist_dtype=dist_dtype,
        )
        out_spec = pl.BlockSpec(
            shapes["out"], lambda i, j, slots: (slots[j], 0, i, 0)
        )
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(rows_pad // bq, n_tiles),
                in_specs=[
                    pl.BlockSpec(shapes["vq"], lambda i, j, slots: (i, 0)),
                    pl.BlockSpec(
                        shapes["vc_tile"], lambda i, j, slots: (0, j)
                    ),
                    pl.BlockSpec(shapes["ids"], lambda i, j, slots: (0, j)),
                ],
                out_specs=[out_spec, out_spec],
                scratch_shapes=[
                    pltpu.VMEM(shapes["scratch_idx"], jnp.int32),
                    pltpu.VMEM(shapes["scratch_dist"], jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((S, nb, rows_pad, k), jnp.int32),
                jax.ShapeDtypeStruct((S, nb, rows_pad, k), jnp.float32),
            ],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
            name="knn_topk_prefix",
        )(slot_arr, VqT_p, Vc_g, ids)

    return _over_query_splits(Vq[:E_hi], block_q, call_split, q_axis=2)
