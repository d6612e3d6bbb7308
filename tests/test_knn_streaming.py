"""Streaming candidate-tiled kNN selection (DESIGN.md SS8).

Contracts under test:
  * the partial merge network (core/knn.merge_topk_sorted) reproduces
    lax.top_k over the union of any column partition BIT-identically
    (idx AND float32 distances) — k not a power of two, k == Lc,
    duplicate/dead-neuron ties, lists narrower than k;
  * streaming == dense-oracle BIT-identity on both the jnp builders and
    the Pallas stream kernel, for tile widths that do and do not divide
    Lc — including the tie-heavy duplicate/dead-neuron cases (the PR 2
    simplex_weights d1~0 regime) and the bf16-accumulate path;
  * the in-kernel prefix snapshots == the per-size rebuild oracle,
    bit-for-bit, with and without the col_ids permutation;
  * the streaming kernel's per-program block/scratch shapes are a pure
    function of (E_max, k, block_q, tile_c) — INDEPENDENT of Lc — and
    the VMEM model counts the merge network's doubled top-k working set
    (the CI guard);
  * the library-sharded builder + host-side merge reproduce the
    unsharded table bit-for-bit;
  * EDMConfig.knn_tile_c resolution (auto-calibrated / forced width) is
    shared by every engine and invisible in the causal map.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import EDMConfig, ccm_matrix, knn, simplex_batch
from repro.data.synthetic import dummy_brain


def _rand_V(E, L, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((E, L)), jnp.float32)


# -------------------------------------------------- merge network unit
def _merge_vs_topk_oracle(D, k, split):
    """Partition columns at `split`, top-k each part, fold through the
    merge network; must equal lax.top_k over all columns, bit for bit."""
    Lc = D.shape[1]
    ka = min(k, split)
    kb = min(k, Lc - split)
    neg_a, ia = jax.lax.top_k(-D[:, :split], ka)
    neg_b, ib = jax.lax.top_k(-D[:, split:], kb)
    mi, md = knn.merge_topk_sorted(
        ia.astype(jnp.int32), -neg_a,
        (ib + split).astype(jnp.int32), -neg_b, k,
    )
    neg_o, io = jax.lax.top_k(-D, k)
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(io))
    np.testing.assert_array_equal(np.asarray(md), np.asarray(-neg_o))


@pytest.mark.parametrize(
    "Lq,Lc,k,split",
    [
        (17, 40, 5, 13),    # k not a power of two, ragged split
        (8, 64, 21, 30),    # paper k=21 (not pow2), both parts >= k
        (9, 12, 12, 5),     # k == Lc: BOTH parts narrower than k
        (5, 30, 16, 16),    # k a power of two, exact split
        (7, 9, 8, 1),       # run list of width 1
    ],
)
def test_merge_network_vs_topk_oracle(Lq, Lc, k, split):
    rng = np.random.default_rng(Lq * 100 + Lc)
    D = jnp.asarray(rng.standard_normal((Lq, Lc)) ** 2, jnp.float32)
    _merge_vs_topk_oracle(D, k, split)


@pytest.mark.parametrize("split", [7, 24, 31])
def test_merge_network_tie_rule(split):
    """All-tied and duplicate-column distances: equal keys must resolve
    to the LOWEST candidate id (running list before tile, position
    ascending) — exactly the lax.top_k rule."""
    Lq, Lc, k = 6, 48, 9
    _merge_vs_topk_oracle(jnp.zeros((Lq, Lc), jnp.float32), k, split)
    rng = np.random.default_rng(3)
    half = jnp.asarray(rng.standard_normal((Lq, 24)) ** 2, jnp.float32)
    _merge_vs_topk_oracle(jnp.concatenate([half, half], axis=1), k, split)


def test_merge_network_keeps_sorted_invariant():
    """Merged output is sorted ascending — the invariant the running
    carry relies on across tiles."""
    rng = np.random.default_rng(11)
    D = jnp.asarray(rng.standard_normal((13, 57)) ** 2, jnp.float32)
    neg_a, ia = jax.lax.top_k(-D[:, :29], 7)
    neg_b, ib = jax.lax.top_k(-D[:, 29:], 7)
    mi, md = knn.merge_topk_sorted(
        ia.astype(jnp.int32), -neg_a, (ib + 29).astype(jnp.int32), -neg_b, 7
    )
    assert np.all(np.diff(np.asarray(md), axis=-1) >= 0)


# ------------------------------------------------------- jnp builders
@pytest.mark.parametrize(
    "Lq,Lc,E,k,exclude_self,tile_c",
    [
        (130, 130, 8, 9, True, 48),   # non-dividing tile
        (128, 128, 6, 7, True, 32),   # dividing tile
        (100, 257, 5, 6, False, 64),  # rectangular, non-dividing
        (50, 300, 5, 6, False, 300),  # single tile == library width
        (60, 60, 4, 60, True, 16),    # k == Lc (masked self selected)
    ],
)
def test_streaming_bit_identical_to_dense(Lq, Lc, E, k, exclude_self, tile_c):
    Vq = _rand_V(E, Lq, Lq * 1000 + Lc)
    Vc = Vq if exclude_self else _rand_V(E, Lc, Lc)
    i0, d0 = knn.knn_tables_dense(Vq, Vc, k, exclude_self, impl="unroll")
    i1, d1 = knn.knn_tables_all_E_streaming(
        Vq, Vc, k, exclude_self, tile_c=tile_c
    )
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


@pytest.mark.parametrize("tile_c", [32, 48])  # dividing / non-dividing of 96
def test_streaming_ties_dead_and_duplicate_neurons(tile_c):
    """All-tied rows (dead series: every distance exactly 0) and duplicate
    candidates must resolve ties identically in the tiled merge and
    lax.top_k — the d1~0 simplex_weights regime from PR 2."""
    # dead neuron: constant series -> V all equal -> D == 0 everywhere
    Vdead = jnp.zeros((5, 96), jnp.float32)
    i0, d0 = knn.knn_tables_dense(Vdead, Vdead, 6, True, impl="unroll")
    i1, d1 = knn.knn_tables_all_E_streaming(Vdead, Vdead, 6, True, tile_c=tile_c)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    # ties resolve to the LOWEST candidate id (self masked out)
    assert np.asarray(i1)[0, 0, :3].tolist() == [1, 2, 3]
    assert np.all(np.asarray(d1) == 0.0)

    # duplicate neurons: pairs of identical candidate columns
    rng = np.random.default_rng(7)
    half = jnp.asarray(rng.standard_normal((5, 48)), jnp.float32)
    Vdup = jnp.concatenate([half, half], axis=1)  # cols j and j+48 identical
    i0, d0 = knn.knn_tables_dense(Vdup, Vdup, 7, True, impl="unroll")
    i1, d1 = knn.knn_tables_all_E_streaming(Vdup, Vdup, 7, True, tile_c=tile_c)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    # each query's zero-distance duplicate is found, lowest-id first
    assert np.all(np.asarray(d1)[:, :, 0] == 0.0)


def test_streaming_bucketed_bit_identical(tile_sizes=(33, 70, 140)):
    V = _rand_V(8, 140, 2)
    buckets = (2, 5, 8)
    i0, d0 = knn.knn_tables_bucketed_dense(V, V, 9, True, buckets)
    for tc in tile_sizes:
        i1, d1 = knn.knn_tables_bucketed_streaming(
            V, V, 9, True, buckets, tile_c=tc
        )
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


def test_streaming_counts_table_rows():
    knn.reset_table_counters()
    V = _rand_V(6, 90, 3)
    knn.knn_tables_all_E_streaming(V, V, 7, True, tile_c=30)
    assert knn.TABLE_ROWS_BUILT["all_E"] == 6
    knn.knn_tables_bucketed_streaming(V, V, 7, True, (2, 6), tile_c=30)
    assert knn.TABLE_ROWS_BUILT["bucketed"] == 2
    knn.reset_table_counters()


def test_streaming_rejects_bad_args():
    V = _rand_V(4, 50, 4)
    with pytest.raises(ValueError, match="exceeds candidate count"):
        knn.knn_tables_all_E_streaming(V, V, 51, True, tile_c=16)
    with pytest.raises(ValueError, match="ascending"):
        knn.knn_tables_bucketed_streaming(V, V, 5, True, (3, 2), tile_c=16)


# ------------------------------------------------------ pallas kernels
@pytest.mark.parametrize(
    "E,Lq,Lc,k,exclude_self,block_q,tile_c",
    [
        (4, 100, 100, 5, True, 64, 48),    # ragged Lq tail, non-dividing tile
        (6, 128, 192, 7, False, 64, 64),   # dividing everything
        (3, 129, 257, 4, False, 64, 100),  # ragged both axes
        (5, 60, 60, 60, True, 32, 16),     # k == Lc (tile clamped up to k)
    ],
)
def test_stream_kernel_bit_identical_to_dense_oracle(
    E, Lq, Lc, k, exclude_self, block_q, tile_c
):
    from repro.kernels.knn_topk.ops import knn_topk_streaming
    from repro.kernels.knn_topk.ref import knn_topk_ref

    Vq = _rand_V(E, Lq, E * 100 + Lq)
    Vc = Vq if exclude_self else _rand_V(E, Lc, Lc + 1)
    i0, d0 = knn_topk_ref(Vq, Vc, k, exclude_self)
    i_st, d_st = knn_topk_streaming(
        Vq, Vc, k, exclude_self=exclude_self, block_q=block_q, tile_c=tile_c,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i_st))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d_st))


def test_stream_kernel_vs_streaming_oracle():
    from repro.kernels.knn_topk.ops import knn_topk_streaming
    from repro.kernels.knn_topk.ref import knn_topk_stream_ref

    V = _rand_V(6, 150, 11)
    idx, d = knn_topk_streaming(V, V, 7, exclude_self=True, block_q=64,
                                tile_c=40, interpret=True)
    ridx, rd = knn_topk_stream_ref(V, V, 7, exclude_self=True, tile_c=64)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(rd))


def test_stream_kernel_ties_match_dense_oracle():
    from repro.kernels.knn_topk.ops import knn_topk_streaming
    from repro.kernels.knn_topk.ref import knn_topk_ref

    V = jnp.zeros((5, 90), jnp.float32)  # dead neuron: all ties
    i0, d0 = knn_topk_ref(V, V, 6, True)
    i_st, d_st = knn_topk_streaming(V, V, 6, exclude_self=True, block_q=32,
                                    tile_c=24, interpret=True)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i_st))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d_st))


def test_dist_dtype_bf16_reaches_kernels():
    """EDMConfig.dist_dtype is honoured by the Pallas stream kernel (bf16
    tile accumulation, float32 merge keys): bf16 actually changes the
    numerics vs f32 (proof it reached the accumulator, not a silently
    ignored knob) while agreeing with the f32 dense oracle to bf16
    resolution (TOLERANCE oracle — bit-identity across differently-fused
    bf16 paths is not a contract: XLA's excess-precision simplification
    elides convert pairs inside fused accumulate chains, so two
    fusion contexts can round differently)."""
    from repro.kernels.knn_topk.ops import knn_topk_streaming
    from repro.kernels.knn_topk.ref import knn_topk_ref

    V = _rand_V(6, 120, 13)
    i_st, d_st = knn_topk_streaming(V, V, 7, exclude_self=True, block_q=64,
                                    tile_c=40, dist_dtype="bfloat16", interpret=True)
    assert d_st.dtype == jnp.float32  # merge keys / outputs stay f32
    _, d_f32 = knn_topk_ref(V, V, 7, True)
    assert not np.array_equal(np.asarray(d_f32), np.asarray(d_st))
    # bf16 distances agree with the f32 dense oracle to bf16 resolution
    np.testing.assert_allclose(
        np.asarray(d_f32), np.asarray(d_st), rtol=2e-2, atol=2e-2
    )
    # the jnp streaming builder's bf16 path holds the same tolerance
    _, d_j = knn.knn_tables_all_E_streaming(
        V, V, 7, True, tile_c=40, dist_dtype=jnp.bfloat16
    )
    np.testing.assert_allclose(
        np.asarray(d_f32), np.asarray(d_j), rtol=2e-2, atol=2e-2
    )


def test_ragged_tail_split_covers_all_queries():
    """_query_splits: full blocks + one 8-aligned tail block; outputs for
    every query row match the unsplit reference (the padded-query waste
    fix must not change results)."""
    from repro.kernels.knn_topk.knn_topk import _query_splits
    from repro.kernels.knn_topk.ops import knn_topk_streaming
    from repro.kernels.knn_topk.ref import knn_topk_ref

    assert _query_splits(256, 128) == [(0, 256, 128)]
    assert _query_splits(130, 128) == [(0, 128, 128), (128, 2, 8)]
    assert _query_splits(50, 128) == [(0, 50, 56)]
    for Lq in (130, 50, 255):
        V = _rand_V(4, Lq, Lq)
        idx, d = knn_topk_streaming(V, V, 5, exclude_self=True, block_q=128,
                                    tile_c=64, interpret=True)
        ridx, rd = knn_topk_ref(V, V, 5, True)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
        np.testing.assert_array_equal(np.asarray(d), np.asarray(rd))


# ------------------------------------------- in-kernel prefix snapshots
@pytest.mark.parametrize("tile_c", [16, 37, 120, 512])
def test_prefix_kernel_bit_identical_to_rebuild(tile_c):
    """The prefix-snapshot kernel (tiles clipped at library-size
    boundaries, carry emitted per boundary) == the per-size rebuild
    oracle, bit for bit, at tile widths that land inside, across, and
    beyond every segment."""
    from repro.kernels.knn_topk.ops import knn_topk_prefix

    Vq = _rand_V(5, 37, 100)
    Vc = _rand_V(5, 203, 101)
    buckets, lib_sizes = (1, 3, 5), (40, 97, 203)
    oi, od = knn.knn_tables_prefix_rebuild(
        Vq, Vc, 7, False, buckets, lib_sizes, 64
    )
    pi, pd = knn_topk_prefix(
        Vq, Vc, 7, False, buckets, lib_sizes, tile_c=tile_c, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(oi))
    np.testing.assert_array_equal(np.asarray(pd), np.asarray(od))


def test_prefix_kernel_col_ids_and_self_exclusion():
    from repro.kernels.knn_topk.ops import knn_topk_prefix

    V = _rand_V(5, 96, 102)
    buckets, lib_sizes = (2, 5), (30, 96)
    rng = np.random.default_rng(9)
    cid = jnp.asarray(rng.permutation(96).astype(np.int32))
    oi, od = knn.knn_tables_prefix_rebuild(
        V, V, 6, True, buckets, lib_sizes, 32, col_ids=cid
    )
    pi, pd = knn_topk_prefix(
        V, V, 6, True, buckets, lib_sizes, tile_c=40, col_ids=cid,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(oi))
    np.testing.assert_array_equal(np.asarray(pd), np.asarray(od))


def test_pallas_engine_prefix_uses_in_kernel_snapshots():
    """Engine.knn_tables_prefix on the Pallas engines routes to the
    in-kernel snapshot kernel — no per-size rebuild fallback — and stays
    bit-identical to the reference one-sweep builder (tol 0)."""
    import repro.engine as engines

    eng = engines.get_engine("pallas-interpret")
    ref = engines.get_engine("reference")
    assert type(eng).knn_tables_prefix is not engines.base.Engine.knn_tables_prefix
    V = _rand_V(4, 80, 103)
    cfg = EDMConfig(E_max=4, engine="pallas-interpret")
    kw = dict(buckets=(1, 4), lib_sizes=(25, 80), exclude_self=True, cfg=cfg)
    ei, ed = eng.knn_tables_prefix(V, V, 5, **kw)
    ri, rd = ref.knn_tables_prefix(V, V, 5, **kw)
    np.testing.assert_array_equal(np.asarray(ei), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(ed), np.asarray(rd))


# ----------------------------------------------------- CI guard: VMEM
def test_stream_kernel_blocks_independent_of_Lc():
    """CI guard: the streaming kernel's per-program block/scratch shapes
    and VMEM budget are a pure function of (E_max, k, block_q, tile_c) —
    the library length only scales the grid.  stream_block_shapes is the
    SAME function knn_topk_stream_pallas builds its BlockSpecs from."""
    from repro.kernels.knn_topk.knn_topk import (
        prefix_block_shapes,
        stream_block_shapes,
        stream_vmem_bytes,
    )

    shapes = stream_block_shapes(20, 21, 128, 512)
    import inspect
    sig = inspect.signature(stream_block_shapes)
    assert "Lc" not in sig.parameters  # shape function cannot even see Lc
    assert shapes["vc_tile"] == (20, 512)
    assert shapes["scratch_idx"] == (20, 128, 21)
    # TPU layout: queries on sublanes, lag rows on lanes
    assert shapes["vq"] == (128, 20)
    # the in-kernel two-pointer merge's (id, dist) output is part of the
    # shape contract and the VMEM model
    assert shapes["merge"] == (128, 21)
    assert stream_vmem_bytes(20, 21, 128, 512) >= (4 + 4) * 128 * 21
    assert prefix_block_shapes(20, 3, 21, 128, 512)["merge"] == (128, 21)
    # paper-scale budget: E_max=20, k=21, block_q=128 fits a 16 MB VMEM
    # with headroom at ANY library length, even at the calibrator's
    # widest 4096 tile
    assert stream_vmem_bytes(20, 21, 128, 512) < 4 * 2**20
    assert stream_vmem_bytes(20, 21, 128, 4096) < 8 * 2**20
    # the jnp streaming working-set model takes no Lc parameter either
    # (structural flatness); pin that so the model cannot silently grow
    # a hidden Lc term
    assert "Lc" not in inspect.signature(knn.streaming_bytes).parameters
    assert knn.streaming_bytes(128, 21, 512, 20) < 4 * 2**20


def test_tile_resolution_and_calibration():
    """knn_tile_c semantics: > 0 forced, 0 one-shot calibrated (widest
    power-of-two tile under the VMEM budget, clamped to the library),
    -1 (the removed dense path) a clear deprecation error."""
    assert knn.resolve_stream_tile(100, EDMConfig(knn_tile_c=64)) == 64
    auto = knn.resolve_stream_tile(1000, EDMConfig())
    assert auto == knn.calibrate_knn_tile(1000)
    # small library: the calibrated tile covers it entirely (degenerates
    # to one direct selection — no small-L regression vs a dense pass)
    assert knn.calibrate_knn_tile(1000) >= 1000
    # large library: widest tile under the budget, capped and pow2
    big = knn.calibrate_knn_tile(64000)
    assert big == knn.calibrate_knn_tile(16000)  # cap reached
    assert big & (big - 1) == 0 and knn.KNN_TILE_MIN <= big <= knn.KNN_TILE_MAX
    assert knn.streaming_bytes(128, 21, big, 20) <= knn.KNN_TILE_BUDGET_BYTES
    with pytest.raises(ValueError, match="deprecated"):
        EDMConfig(knn_tile_c=-1)
    with pytest.raises(ValueError, match="knn_tile_c"):
        EDMConfig(knn_tile_c=-2)
    class _FakeCfg:
        knn_tile_c = -1
        E_max, dist_dtype = 20, "float32"
        k_max = 21
    with pytest.raises(ValueError, match="deprecated"):
        knn.resolve_stream_tile(100, _FakeCfg())


# ------------------------------------------------- library sharding
def test_merge_shard_tables_bit_identical():
    """Per-shard top-k + host merge == unsharded table, bit for bit,
    across shard counts (including shards narrower than k)."""
    rng = np.random.default_rng(17)
    Vq = jnp.asarray(rng.standard_normal((6, 120)), jnp.float32)
    i0, d0 = knn.knn_tables_dense(Vq, Vq, 7, True, impl="unroll")
    for S in (2, 3, 5):
        shard = -(-120 // S)
        parts = [
            knn.knn_tables_all_E_streaming(
                Vq, Vq[:, s * shard : min((s + 1) * shard, 120)],
                min(7, shard), True, tile_c=16,
                col_offset=s * shard, col_hi=min((s + 1) * shard, 120),
            )
            for s in range(S)
        ]
        mi, md = knn.merge_shard_tables(
            [p[0] for p in parts], [p[1] for p in parts], k=7
        )
        np.testing.assert_array_equal(mi, np.asarray(i0))
        np.testing.assert_array_equal(md, np.asarray(d0))


def test_merge_topk_tree_bit_identical_vs_oracle():
    """The DEVICE-side tree merge (DESIGN.md SS14) == the host lexsort
    oracle == the unsharded table — idx AND f32 dists, ties included —
    across shard counts (pow2 and not, shards narrower than k) and at
    the k == Lc exclude-self edge where +inf masked entries reach the
    final table."""
    rng = np.random.default_rng(17)
    V = rng.standard_normal((6, 120)).astype(np.float32)
    # duplicate columns across future shard boundaries force exact
    # cross-shard distance ties — the (distance, id) rule must decide
    V[:, 50] = V[:, 10]
    V[:, 90] = V[:, 10]
    V[:, 91] = V[:, 33]
    Vq = jnp.asarray(V)
    for k in (7, 120):  # 120 == Lc: one masked +inf (self) entry survives
        i0, d0 = knn.knn_tables_all_E_streaming(Vq, Vq, k, True, tile_c=32)
        for S in (2, 3, 4, 5):
            shard = -(-120 // S)
            parts = [
                knn.knn_tables_all_E_streaming(
                    Vq, Vq[:, s * shard : min((s + 1) * shard, 120)],
                    min(k, shard, 120 - s * shard), True, tile_c=16,
                    col_offset=s * shard, col_hi=min((s + 1) * shard, 120),
                )
                for s in range(S)
            ]
            ti, td = knn.merge_topk_tree(
                [p[0] for p in parts], [p[1] for p in parts], k
            )
            oi, od = knn.merge_shard_tables(
                [p[0] for p in parts], [p[1] for p in parts], k=k
            )
            np.testing.assert_array_equal(np.asarray(ti), oi)
            np.testing.assert_array_equal(np.asarray(td), od)
            np.testing.assert_array_equal(np.asarray(ti), np.asarray(i0))
            np.testing.assert_array_equal(np.asarray(td), np.asarray(d0))


@pytest.mark.parametrize("engine_name", ["reference", "pallas-interpret"])
def test_merge_tree_on_engine_tables(engine_name):
    """Acceptance bit (DESIGN.md SS14): the device-side merge is
    bit-identical to the merge_shard_tables oracle on per-shard tables
    built by BOTH the jnp and the Pallas engines, for >= 2 shard
    counts."""
    from repro import engine

    eng = engine.get_engine(engine_name)
    rng = np.random.default_rng(29)
    V = rng.standard_normal((4, 96)).astype(np.float32)
    V[:, 64] = V[:, 3]  # cross-shard tie
    Vq = jnp.asarray(V)
    cfg = EDMConfig(E_max=4)
    k = 6
    u_i, u_d = eng.knn_tables(Vq, Vq, k, exclude_self=False, cfg=cfg)
    for S in (2, 4):
        shard = 96 // S
        idx_p, d_p = [], []
        for s in range(S):
            li, ld = eng.knn_tables(
                Vq, Vq[:, s * shard : (s + 1) * shard], min(k, shard),
                exclude_self=False, cfg=cfg,
            )
            idx_p.append(li + s * shard)  # local -> global candidate ids
            d_p.append(ld)
        ti, td = knn.merge_topk_tree(idx_p, d_p, k)
        oi, od = knn.merge_shard_tables(idx_p, d_p, k=k)
        np.testing.assert_array_equal(np.asarray(ti), oi)
        np.testing.assert_array_equal(np.asarray(td), od)
        np.testing.assert_array_equal(np.asarray(ti), np.asarray(u_i))
        np.testing.assert_array_equal(np.asarray(td), np.asarray(u_d))


def test_library_sharded_pipeline_builder():
    """The shard_map-backed builder (local mesh) == dense-oracle table,
    and — the SS14 bugfix — it returns DEVICE arrays (no host np
    round-trip on the collective path)."""
    import jax

    from repro.core.pipeline import knn_tables_library_sharded

    Vq = _rand_V(5, 110, 23)
    cfg = EDMConfig(E_max=5)
    mi, md = knn_tables_library_sharded(Vq, Vq, 6, cfg, exclude_self=True)
    assert isinstance(mi, jax.Array) and isinstance(md, jax.Array)
    i0, d0 = knn.knn_tables_dense(Vq, Vq, 6, True, impl="unroll")
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(md), np.asarray(d0))


def test_library_sharded_sim_path():
    """The simulated-shard path (sequential per-shard builds + the same
    device tree merge; used by benchmarks/CI on few devices) matches the
    unsharded table bit-for-bit across shard counts."""
    from repro.core.pipeline import knn_tables_library_sharded_sim

    Vq = _rand_V(5, 110, 23)
    cfg = EDMConfig(E_max=5)
    i0, d0 = knn.knn_tables_dense(Vq, Vq, 6, True, impl="unroll")
    for S in (2, 3, 4):
        si, sd = knn_tables_library_sharded_sim(
            Vq, Vq, 6, cfg, exclude_self=True, shards=S
        )
        np.testing.assert_array_equal(np.asarray(si), np.asarray(i0))
        np.testing.assert_array_equal(np.asarray(sd), np.asarray(d0))


def test_library_sharded_multi_device():
    """4 fake devices: each selects over its candidate shard and the
    DEVICE-side collective (ppermute butterfly at W=4, all_gather fold
    at W=3) reproduces the unsharded table bit-for-bit (subprocess — the
    in-process suite must see the real single CPU device)."""
    import os
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import EDMConfig, knn
        from repro.core.pipeline import knn_tables_library_sharded

        assert len(jax.devices()) == 4
        rng = np.random.default_rng(31)
        Vq = jnp.asarray(rng.standard_normal((5, 130)), jnp.float32)
        cfg = EDMConfig(E_max=5, knn_tile_c=16)  # force a narrow tile
        i0, d0 = knn.knn_tables_dense(Vq, Vq, 6, True, impl="unroll")
        # W=4: power-of-two ppermute butterfly; device arrays out
        mi, md = knn_tables_library_sharded(Vq, Vq, 6, cfg, exclude_self=True)
        assert isinstance(mi, jax.Array) and isinstance(md, jax.Array)
        np.testing.assert_array_equal(np.asarray(mi), np.asarray(i0))
        np.testing.assert_array_equal(np.asarray(md), np.asarray(d0))
        # W=3: non-power-of-two all_gather + tree fold
        mesh3 = jax.make_mesh((3,), ("workers",), devices=jax.devices()[:3])
        mi, md = knn_tables_library_sharded(
            Vq, Vq, 6, cfg, exclude_self=True, mesh=mesh3)
        np.testing.assert_array_equal(np.asarray(mi), np.asarray(i0))
        np.testing.assert_array_equal(np.asarray(md), np.asarray(d0))
        print("sharded-4dev collective == unsharded: OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=600, cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


# --------------------------------------------------- engine routing
@pytest.mark.parametrize("engine", ["reference", "pallas-interpret"])
def test_causal_map_invariant_under_knn_tile(engine):
    """Auto-calibrated and forced tiles (dividing and non-dividing)
    produce the SAME causal map on both engines — the acceptance bit."""
    ts = jnp.asarray(dummy_brain(10, 260, seed=21))
    _, optE = simplex_batch(ts, EDMConfig(E_max=4))
    rho_auto = np.asarray(
        ccm_matrix(ts, optE, EDMConfig(E_max=4, engine=engine))
    )
    for tile in (32, 37):  # divides / does not divide Lp
        rho_t = np.asarray(
            ccm_matrix(
                ts, optE, EDMConfig(E_max=4, engine=engine, knn_tile_c=tile)
            )
        )
        np.testing.assert_array_equal(rho_auto, rho_t)


def test_phase1_invariant_under_knn_tile():
    """Phase 1 (simplex sweep) also routes through the streaming builders
    unchanged: optE and rhos identical under any forced tile width."""
    ts = jnp.asarray(dummy_brain(8, 240, seed=29))
    r0, e0 = simplex_batch(ts, EDMConfig(E_max=4))
    r1, e1 = simplex_batch(ts, EDMConfig(E_max=4, knn_tile_c=41))
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
