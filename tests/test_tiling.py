"""Target-tiled phase-2 CCM (DESIGN.md SS7) + the PR bugfix sweep:

  * tiled vs untiled rho bit-identical across tile sizes (including ones
    that don't divide N), and tiled rho against the all-E oracle
    ``ccm_naive`` at every tile size;
  * no dense (N, N) host allocation when phase 2 streams to a store;
  * TileWriter 2D manifest: coverage, elastic/fragmented chunk_plan,
    col_order persistence, assemble (dense and memmap);
  * simplex_weights tied-neighbour (d1 ~ 0) handling — dead-neuron
    datasets produce a finite causal map end-to-end;
  * k_override / k <= Lp validation, and the removed all-E layout
    refused by EDMConfig.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    BucketPlan,
    EDMConfig,
    ccm_matrix,
    ccm_naive,
    ccm_row_tables_bucketed,
    make_bucket_plan,
    make_tile_plans,
    simplex_batch,
    simplex_weights,
)
from repro.data.store import RowBlockWriter, TileWriter
from repro.data.synthetic import dummy_brain


# ------------------------------------------------------- tiled bit-identity
def test_tiled_matrix_bit_identical_across_tile_sizes():
    """Acceptance: tiling the target axis must not change a single bit,
    for dividing and non-dividing tile widths."""
    N = 14
    ts = jnp.asarray(dummy_brain(N, 250, seed=21))
    cfg0 = EDMConfig(E_max=5)
    _, optE = simplex_batch(ts, cfg0)
    base = np.asarray(ccm_matrix(ts, optE, cfg0))
    for tile in (3, 5, N, 4 * N):  # 5 and 3 do not divide N=14
        cfg = EDMConfig(E_max=5, target_tile=tile)
        tiled = np.asarray(ccm_matrix(ts, optE, cfg))
        np.testing.assert_array_equal(tiled, base, err_msg=f"tile={tile}")


@pytest.mark.parametrize("tile", [3, 5, 14, 56])  # N=14: 3 and 5 do not divide
def test_tiled_matrix_matches_naive_oracle(tile):
    """The tiled bucketed map agrees with the all-E oracle (paper Alg. 1,
    one table per pair) at every tile width."""
    ts = jnp.asarray(dummy_brain(14, 250, seed=21))
    cfg = EDMConfig(E_max=5, target_tile=tile)
    _, optE = simplex_batch(ts, cfg)
    tiled = np.asarray(ccm_matrix(ts, optE, cfg))
    naive = np.asarray(ccm_naive(ts, optE, cfg))
    np.testing.assert_allclose(tiled, naive, rtol=0, atol=1e-5)


def test_tiled_pipeline_bit_identical_with_store(tmp_path):
    """Full pipeline: tiled + streamed-to-store == untiled in-memory."""
    from repro.core.pipeline import run_causal_inference

    ts = dummy_brain(13, 230, seed=3)
    base = run_causal_inference(ts, EDMConfig(E_max=4, lib_block=3))
    cfg = EDMConfig(E_max=4, lib_block=3, target_tile=5)
    out = run_causal_inference(ts, cfg, out_dir=str(tmp_path / "tiled"))
    assert isinstance(out.rho, np.memmap)  # disk-backed, not a host array
    np.testing.assert_array_equal(np.asarray(out.rho), np.asarray(base.rho))


def test_make_tile_plans_cover_and_bounded_signatures():
    optE = np.asarray([2] * 5 + [4] * 9 + [7] * 3, np.int32)
    plan, _ = make_bucket_plan(optE)
    plans = make_tile_plans(plan, 4)
    # tiles cover [0, N) in order
    assert [c0 for c0, _ in plans] == [0, 4, 8, 12, 16]
    assert all(sum(c for _, c in sp) in (4, 1) for _, sp in plans)
    assert sum(sum(c for _, c in sp) for _, sp in plans) == plan.n_targets
    # boundary tile straddles buckets 0 and 1
    assert plans[1][1] == ((0, 1), (1, 3))
    # distinct jit signatures stay small (~2 x len(buckets))
    assert len({sp for _, sp in plans}) <= 2 * len(plan.buckets)
    with pytest.raises(ValueError, match="tile"):
        make_tile_plans(plan, 0)


def test_phase2_no_dense_host_alloc_with_store(tmp_path, monkeypatch):
    """Acceptance: with an output store, phase 2 must never allocate the
    dense (N, N) host map — np.zeros is guarded for the whole run."""
    from repro.core.pipeline import run_causal_inference

    N = 24
    ts = dummy_brain(N, 220, seed=1)
    real_zeros = np.zeros

    def guarded(shape, *args, **kwargs):
        if tuple(np.atleast_1d(shape)) == (N, N):
            raise AssertionError("dense NxN host allocation in streaming mode")
        return real_zeros(shape, *args, **kwargs)

    monkeypatch.setattr(np, "zeros", guarded)
    out = run_causal_inference(
        ts, EDMConfig(E_max=4, lib_block=4, target_tile=8),
        out_dir=str(tmp_path / "rho"),
    )
    monkeypatch.undo()
    base = run_causal_inference(ts, EDMConfig(E_max=4, lib_block=4))
    np.testing.assert_array_equal(np.asarray(out.rho), np.asarray(base.rho))


# ---------------------------------------------------------- TileWriter (2D)
def test_tile_writer_2d_manifest_roundtrip(tmp_path):
    N = 9
    rho = np.arange(N * N, dtype=np.float32).reshape(N, N)
    w = TileWriter(tmp_path / "w", N)
    w.write_tile(0, 0, rho[:4, :5])
    w.write_tile(0, 5, rho[:4, 5:])
    w.write_block(4, rho[4:])  # legacy full-width block interoperates
    assert w.covered().all()
    np.testing.assert_array_equal(w.assemble(), rho)
    # a fresh writer over the same dir sees the same state (resume)
    w2 = TileWriter(tmp_path / "w", N)
    assert w2.chunk_plan(4) == []
    np.testing.assert_array_equal(w2.assemble(), rho)
    # memmap assembly is identical and lands at the requested path
    mm = w2.assemble(mmap_path=tmp_path / "w" / "causal_map" / "data.npy")
    assert isinstance(mm, np.memmap)
    np.testing.assert_array_equal(np.asarray(mm), rho)


def test_tile_writer_partial_columns_not_covered(tmp_path):
    w = TileWriter(tmp_path / "w", 6)
    w.write_tile(0, 0, np.ones((6, 4), np.float32))
    assert not w.covered().any()  # cols 4..5 missing on every row
    w.write_tile(0, 4, np.ones((3, 2), np.float32))
    cov = w.covered()
    np.testing.assert_array_equal(cov, [True] * 3 + [False] * 3)


def test_tile_writer_col_order_persisted_and_checked(tmp_path):
    N = 8
    rng = np.random.default_rng(0)
    rho = rng.standard_normal((N, N)).astype(np.float32)
    order = rng.permutation(N)
    w = TileWriter(tmp_path / "w", N)
    w.ensure_col_order(order)
    rho_sorted = rho[:, order]  # tiles are written in on-disk (sorted) order
    w.write_tile(0, 0, rho_sorted[:, :5])
    w.write_tile(0, 5, rho_sorted[:, 5:])
    np.testing.assert_array_equal(w.assemble(), rho)  # permutation undone
    # resume with the same order is fine; a different one must refuse
    TileWriter(tmp_path / "w", N).ensure_col_order(order)
    with pytest.raises(ValueError, match="column-order mismatch"):
        TileWriter(tmp_path / "w", N).ensure_col_order(np.roll(order, 1))


# --------------------------------------------- chunk_plan fragmentation fix
def test_chunk_plan_skips_covered_islands(tmp_path):
    """Elastic resume can leave covered islands mid-range; planned spans
    must be trimmed to uncovered runs, not re-span covered rows."""
    w = RowBlockWriter(tmp_path / "w", 20)
    w.write_block(6, np.zeros((4, 20), np.float32))  # island: rows 6..9
    assert w.chunk_plan(8) == [(0, 6), (10, 8), (18, 2)]
    # the old behaviour would have produced [(0, 8), ...] — recomputing
    # (and rewriting) covered rows 6..7 inside the first span
    w.write_block(0, np.zeros((6, 20), np.float32))
    assert w.chunk_plan(8) == [(10, 8), (18, 2)]
    w.write_block(10, np.zeros((10, 20), np.float32))
    assert w.chunk_plan(8) == []


# ------------------------------------- degenerate-distance simplex weights
def test_simplex_weights_uniform_over_tied_neighbours():
    """d1 == 0 (duplicate points): cppEDM weights the tied neighbours
    uniformly; the exponential form would underflow to a delta."""
    sqd = jnp.asarray([[0.0, 0.0, 0.0, 4.0, 9.0]])
    w = np.asarray(simplex_weights(sqd, 5))
    np.testing.assert_allclose(w[0], [1 / 3, 1 / 3, 1 / 3, 0.0, 0.0], atol=1e-6)
    # k_valid masks ties beyond the valid neighbour count too
    w2 = np.asarray(simplex_weights(sqd, 2))
    np.testing.assert_allclose(w2[0], [0.5, 0.5, 0.0, 0.0, 0.0], atol=1e-6)
    # regular rows are untouched by the tie branch
    sqd_reg = jnp.asarray([[1.0, 4.0, 9.0]])
    w3 = np.asarray(simplex_weights(sqd_reg, 3))
    assert w3[0, 0] > w3[0, 1] > w3[0, 2] > 0
    np.testing.assert_allclose(w3.sum(), 1.0, rtol=1e-6)
    # scale invariance: a tiny-amplitude row (d1 > 0 but << any absolute
    # eps) must weight exactly like its rescaled counterpart — the tie
    # branch only fires on EXACT zeros, never on small-but-real distances
    w4 = np.asarray(simplex_weights(jnp.asarray(sqd_reg) * 1e-20, 3))
    np.testing.assert_allclose(w4, w3, rtol=1e-5)


@pytest.mark.parametrize("target_tile", [0, 4])
def test_dead_and_duplicate_neurons_finite_causal_map(target_tile):
    """End-to-end: constant (dead) and duplicated series must yield a
    finite causal map — no NaN/Inf reaches pearson."""
    rng = np.random.default_rng(7)
    ts = dummy_brain(8, 240, seed=7).copy()
    ts[0] = 0.0                    # dead neuron: all distances are 0
    ts[1] = 3.14                   # dead at a nonzero level
    ts[3] = ts[2]                  # exact duplicate pair
    ts = jnp.asarray(ts)
    cfg = EDMConfig(E_max=4, target_tile=target_tile)
    rhos, optE = simplex_batch(ts, cfg)
    assert np.isfinite(np.asarray(rhos)).all()
    rho = np.asarray(ccm_matrix(ts, optE, cfg))
    assert np.isfinite(rho).all()
    # dead neurons are unpredictable: 0 skill by the pearson convention
    assert rho[0, 0] == 0.0 and rho[1, 1] == 0.0


# ------------------------------------------------------- k validation fixes
def test_k_override_zero_rejected():
    with pytest.raises(ValueError, match="k_override"):
        EDMConfig(k_override=0)
    with pytest.raises(ValueError, match="k_override"):
        EDMConfig(k_override=-3)
    assert EDMConfig(k_override=5).k_max == 5
    assert EDMConfig(E_max=7).k_max == 8  # None -> tracks E_max


def test_all_E_layout_refused_and_oracle_named():
    """Phase 2 has one table layout; bucketed=False names the oracle."""
    with pytest.raises(ValueError, match="ccm_naive"):
        EDMConfig(bucketed=False)
    assert EDMConfig().bucketed


def test_k_exceeding_library_points_raises_clear_error():
    """Short series with large optE/k must fail with a diagnosable error,
    not crash inside lax.top_k."""
    x = jnp.asarray(np.linspace(0, 1, 16), jnp.float32)
    with pytest.raises(ValueError, match="library points"):
        ccm_row_tables_bucketed(  # Lp=8 < k=9
            x, EDMConfig(E_max=8), BucketPlan(buckets=(8,), counts=(1,))
        )
    with pytest.raises(ValueError, match="library points"):
        ccm_row_tables_bucketed(
            x, EDMConfig(E_max=3, k_override=500),
            BucketPlan(buckets=(2,), counts=(1,)),
        )
    # k_override=1 is explicit and honoured (the old `or` idiom could not
    # distinguish unset from small-but-set)
    idx, w = ccm_row_tables_bucketed(
        x, EDMConfig(E_max=3, k_override=1), BucketPlan(buckets=(2,), counts=(1,))
    )
    assert idx.shape[-1] == 1
