"""Execution-engine layer: registry, backend agreement, optE bucketing,
and the double-buffered chunk stream (DESIGN.md SS3/SS5)."""
import jax
import numpy as np
import pytest

import jax.numpy as jnp

from repro import engine as engines
from repro.core import (
    EDMConfig,
    ccm_block_bucketed,
    ccm_library_row_bucketed,
    ccm_matrix,
    ccm_naive,
    all_futures,
    knn,
    make_bucket_plan,
    simplex_batch,
)
from repro.data.synthetic import dummy_brain


# ---------------------------------------------------------------- registry
def test_registry_has_at_least_three_backends():
    names = engines.available_engines()
    assert len(names) >= 3
    for required in ("reference", "pallas-interpret", "pallas-compiled"):
        assert required in names
        assert engines.get_engine(required).name == required


def test_unknown_engine_raises():
    with pytest.raises(KeyError, match="unknown engine"):
        engines.get_engine("nope")


def test_register_custom_backend():
    class Custom(engines.ReferenceEngine):
        name = "custom-test"

    engines.register(Custom())
    try:
        assert "custom-test" in engines.available_engines()
        assert isinstance(engines.get_engine("custom-test"), Custom)
    finally:
        engines._REGISTRY.pop("custom-test", None)


def test_use_kernels_deprecation_shim():
    with pytest.warns(DeprecationWarning, match="use_kernels is deprecated"):
        cfg = EDMConfig(use_kernels=True)
    assert cfg.engine == "pallas-compiled"
    with pytest.warns(DeprecationWarning):
        cfg = EDMConfig(use_kernels=False)
    assert cfg.engine == "reference"


# ---------------------------------------------------- oracle check harness
def _off_chip(name: str) -> bool:
    """pallas-compiled compiles for the TPU only: off the chip it must
    refuse (never fall back to the interpreter)."""
    return name == "pallas-compiled" and jax.default_backend() != "tpu"


@pytest.mark.parametrize("name", ["reference", "pallas-interpret", "pallas-compiled"])
def test_engine_ops_vs_oracle(name):
    from repro.engine.check import check_engine

    if _off_chip(name):
        with pytest.raises(RuntimeError, match="pallas-interpret"):
            check_engine(name, E_max=5, Lq=96, Lc=96, seed=1)
        return
    errs = check_engine(name, E_max=5, Lq=96, Lc=96, seed=1)
    assert set(errs) == {
        "knn_tables", "knn_tables_bucketed", "knn_tables_prefix", "ccm_lookup",
    }


def test_all_engines_agree_on_synthetic_32x400():
    """Acceptance: every registered backend reproduces the reference causal
    map on a 32x400 synthetic dataset to <= 1e-4 max |drho| (off the chip,
    pallas-compiled refuses instead)."""
    cfg_ref = EDMConfig(E_max=5, engine="reference")
    ts = jnp.asarray(dummy_brain(32, 400, seed=11))
    _, optE = simplex_batch(ts, cfg_ref)
    rho_ref = np.asarray(ccm_matrix(ts, optE, cfg_ref))
    for name in engines.available_engines():
        cfg = EDMConfig(E_max=5, engine=name)
        if _off_chip(name):
            with pytest.raises(RuntimeError, match="backend is 'cpu'"):
                ccm_matrix(ts, optE, cfg)
            continue
        rho = np.asarray(ccm_matrix(ts, optE, cfg))
        err = np.abs(rho - rho_ref).max()
        assert err <= 1e-4, f"engine {name}: max |drho| {err}"


# ---------------------------------------------------------------- bucketing
def test_bucket_plan_groups_targets():
    optE = np.asarray([3, 1, 3, 7, 1, 1], np.int32)
    plan, order = make_bucket_plan(optE)
    assert plan.buckets == (1, 3, 7)
    assert plan.counts == (3, 2, 1)
    assert plan.offsets == (0, 3, 5)
    assert plan.n_targets == 6
    np.testing.assert_array_equal(optE[order], np.sort(optE))
    # stable: within-bucket original order preserved
    np.testing.assert_array_equal(order, [1, 4, 5, 0, 2, 3])


def test_bucketed_tables_match_all_E_rows():
    rng = np.random.default_rng(2)
    V = jnp.asarray(rng.standard_normal((8, 140)), jnp.float32)
    buckets = (2, 5, 8)
    idx_b, sqd_b = knn.knn_tables_bucketed_dense(V, V, 9, True, buckets)
    idx_a, sqd_a = knn.knn_tables_dense(V, V, 9, True, impl="unroll")
    assert idx_b.shape == (3, 140, 9)
    for b, E in enumerate(buckets):
        np.testing.assert_array_equal(np.asarray(idx_b[b]), np.asarray(idx_a[E - 1]))
        np.testing.assert_allclose(
            np.asarray(sqd_b[b]), np.asarray(sqd_a[E - 1]), rtol=1e-6, atol=1e-8
        )


def test_bucketed_rebuild_impl_matches_all_E_rebuild():
    """cfg.knn_impl='rebuild' must reach the bucketed builder too (matmul
    -form distances per bucket), matching knn_tables_dense's rebuild rows."""
    rng = np.random.default_rng(4)
    V = jnp.asarray(rng.standard_normal((8, 120)), jnp.float32)
    buckets = (3, 6)
    idx_b, sqd_b = knn.knn_tables_bucketed_dense(V, V, 7, True, buckets, impl="rebuild")
    idx_a, sqd_a = knn.knn_tables_dense(V, V, 7, True, impl="rebuild")
    for b, E in enumerate(buckets):
        np.testing.assert_array_equal(np.asarray(idx_b[b]), np.asarray(idx_a[E - 1]))
        np.testing.assert_allclose(
            np.asarray(sqd_b[b]), np.asarray(sqd_a[E - 1]), rtol=1e-6, atol=1e-8
        )


def test_bucketed_ccm_equals_all_E_and_counts_table_rows():
    """Acceptance: bucketed phase 2 == the all-E oracle ccm_naive (<= 1e-5)
    while building kNN tables only for the distinct optE values (counted)."""
    cfg = EDMConfig(E_max=7)
    # L=311 gives this test a unique trace shape so the trace-time table
    # counters below actually fire (jit caches earlier shapes).
    ts = jnp.asarray(dummy_brain(12, 311, seed=7))
    _, optE = simplex_batch(ts, cfg)
    optE_np = np.asarray(optE)
    n_buckets = len(np.unique(optE_np))
    assert n_buckets < cfg.E_max  # workload actually exercises the saving

    knn.reset_table_counters()
    rho_b = np.asarray(ccm_matrix(ts, optE, cfg))
    assert knn.TABLE_ROWS_BUILT["bucketed"] == n_buckets  # one vmap trace
    assert knn.TABLE_ROWS_BUILT["all_E"] == 0

    rho_a = np.asarray(ccm_naive(ts, optE, cfg))
    np.testing.assert_allclose(rho_b, rho_a, rtol=0, atol=1e-5)


def test_bucketed_row_handles_target_block_chunking():
    """Segment chunking (target_block < bucket size) must not change rho."""
    cfg_small = EDMConfig(E_max=4, target_block=3)
    cfg_big = EDMConfig(E_max=4, target_block=4096)
    ts = jnp.asarray(dummy_brain(10, 260, seed=3))
    _, optE = simplex_batch(ts, cfg_big)
    plan, order = make_bucket_plan(np.asarray(optE))
    ts_fut = all_futures(ts, cfg_big)[jnp.asarray(order)]
    a = ccm_block_bucketed(ts, ts_fut, cfg_small, plan)
    b = ccm_block_bucketed(ts, ts_fut, cfg_big, plan)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ccm_lookup_kernel_crosschecks_simplex_forecast():
    """kernels/ccm_lookup (wired in via the pallas engines) == batched
    knn.simplex_forecast on one shared table."""
    from repro.kernels.ccm_lookup.ops import ccm_lookup

    rng = np.random.default_rng(5)
    V = jnp.asarray(rng.standard_normal((5, 120)), jnp.float32)
    idx, sqd = knn.knn_tables_dense(V, V, 6, True)
    idx, w = knn.tables_with_weights(idx, sqd)
    Y = jnp.asarray(rng.standard_normal((9, 120)), jnp.float32)
    got = np.asarray(
        ccm_lookup(idx[3], w[3], Y, block_b=4, block_t=64, interpret=True)
    )
    want = np.asarray(
        jnp.stack([knn.simplex_forecast(idx[3], w[3], y) for y in Y])
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_bucketed_row_un_jitted_counts_rows():
    """Direct (un-jitted) bucketed row: table rows built == len(buckets)."""
    cfg = EDMConfig(E_max=6)
    ts = jnp.asarray(dummy_brain(6, 205, seed=9))
    optE = np.asarray([2, 2, 4, 4, 4, 1], np.int32)
    plan, order = make_bucket_plan(optE)
    ts_fut = all_futures(ts, cfg)[jnp.asarray(order)]
    knn.reset_table_counters()
    row = ccm_library_row_bucketed(ts[0], ts_fut, cfg, plan)
    assert row.shape == (6,)
    assert knn.TABLE_ROWS_BUILT["bucketed"] == len(plan.buckets) == 3


# ---------------------------------------------------------- chunk streaming
def test_chunk_streamer_orders_and_bounds_inflight():
    from repro.runtime.stream import ChunkStreamer

    drained = []
    s = ChunkStreamer(lambda tag, v: drained.append((tag, int(v))), depth=2)
    for i in range(5):
        s.submit(i, np.asarray(i * 10))
        assert len(s) <= 2
    s.flush()
    assert drained == [(i, i * 10) for i in range(5)]


def test_chunk_streamer_discards_on_error():
    from repro.runtime.stream import ChunkStreamer

    drained = []
    with pytest.raises(RuntimeError):
        with ChunkStreamer(lambda t, v: drained.append(t), depth=3) as s:
            s.submit(0, np.asarray(0))
            raise RuntimeError("boom")
    assert drained == []  # stale chunks not flushed on failure


def test_pipeline_stream_depths_agree(tmp_path):
    """depth=1 (sync legacy) and depth=3 produce bit-identical maps and
    resume manifests."""
    from repro.core.pipeline import run_causal_inference

    ts = dummy_brain(9, 220, seed=13)
    outs = {}
    for depth in (1, 3):
        out = run_causal_inference(
            ts,
            EDMConfig(E_max=4, lib_block=2, stream_depth=depth),
            out_dir=str(tmp_path / f"d{depth}"),
        )
        outs[depth] = out.rho
    np.testing.assert_array_equal(outs[1], outs[3])
