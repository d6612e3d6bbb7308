"""Causal-significance subsystem tests (DESIGN.md SS9).

Covers: prefix-snapshot kNN tables (one-sweep vs per-size rebuild,
bit-identical, both engines, plus a candidate-mask oracle), surrogate
null models (spectrum preservation, determinism), BH-FDR against the
scipy oracle, the deprecated ccm_convergence wrapper, the hardened
pearson, and the end-to-end significance pipeline (coupled-logistic
edge survives FDR, decoupled pair does not; streaming store matches the
in-memory path bit-for-bit and resumes).
"""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import knn
from repro.core.stats import pearson
from repro.core.types import EDMConfig


# ------------------------------------------------- prefix-snapshot tables
@pytest.fixture(scope="module")
def lag_pair():
    rng = np.random.default_rng(0)
    Vq = jnp.asarray(rng.standard_normal((6, 120)), jnp.float32)
    perm = jnp.asarray(rng.permutation(120).astype(np.int32))
    return Vq, perm


@pytest.mark.parametrize("engine", ["reference", "pallas-interpret"])
@pytest.mark.parametrize("tile_c", [13, 64])
@pytest.mark.parametrize("permuted", [False, True])
def test_prefix_snapshot_bit_identity(lag_pair, engine, tile_c, permuted):
    """Engine-op prefix tables == old-style per-size rebuild, bit for bit
    (indices AND float32 distances), under dividing and non-dividing
    tiles, natural and permuted candidate order, on both engines (the
    reference engine runs the ONE-sweep snapshot builder, the Pallas
    engines the base-class per-size fallback)."""
    from repro.engine import get_engine

    Vq, perm = lag_pair
    col_ids = perm if permuted else None
    cfg = EDMConfig(E_max=6, engine=engine, knn_tile_c=tile_c)
    buckets, lib_sizes, k = (1, 3, 6), (25, 60, 120), 7
    got = get_engine(engine).knn_tables_prefix(
        Vq, Vq, k, buckets=buckets, lib_sizes=lib_sizes,
        exclude_self=True, cfg=cfg, col_ids=col_ids,
    )
    want = knn.knn_tables_prefix_rebuild(
        Vq, Vq, k, True, buckets, lib_sizes, tile_c, col_ids=col_ids
    )
    assert got[0].shape == (3, 3, 120, 7)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_prefix_matches_candidate_mask_oracle(lag_pair):
    """Each snapshot equals an independent single-E build restricted to
    the prefix subset via candidate_mask (tie-free gaussian data, so the
    permuted-order tie rule cannot differ from the natural one)."""
    Vq, perm = lag_pair
    lib_sizes = (25, 60, 120)
    idx, sqd = knn.knn_tables_prefix_streaming(
        Vq, Vq, 7, True, (3,), lib_sizes, 13, col_ids=perm
    )
    perm_np = np.asarray(perm)
    for s, Ls in enumerate(lib_sizes):
        member = np.zeros(120, bool)
        member[perm_np[:Ls]] = True
        oi, od = knn.knn_table_single_E(
            Vq, Vq, 3, 7, True, candidate_mask=jnp.asarray(member)
        )
        np.testing.assert_array_equal(np.asarray(idx[s, 0]), np.asarray(oi))
        np.testing.assert_array_equal(np.asarray(sqd[s, 0]), np.asarray(od))


def test_prefix_full_size_row_equals_bucketed_tables(lag_pair):
    """The last snapshot of a natural-order full-length prefix IS the
    plain bucketed table set."""
    Vq, _ = lag_pair
    pi, pd = knn.knn_tables_prefix_streaming(
        Vq, Vq, 7, True, (1, 3, 6), (30, 120), 64
    )
    bi, bd = knn.knn_tables_bucketed_dense(Vq, Vq, 7, True, (1, 3, 6))
    np.testing.assert_array_equal(np.asarray(pi[-1]), np.asarray(bi))
    np.testing.assert_array_equal(np.asarray(pd[-1]), np.asarray(bd))


def test_prefix_validation_errors(lag_pair):
    Vq, _ = lag_pair
    with pytest.raises(ValueError, match="ascending"):
        knn.knn_tables_prefix_streaming(Vq, Vq, 7, True, (3,), (60, 25), 64)
    with pytest.raises(ValueError, match="exceeds candidate count"):
        knn.knn_tables_prefix_streaming(Vq, Vq, 7, True, (3,), (25, 300), 64)
    with pytest.raises(ValueError, match="too small"):
        knn.knn_tables_prefix_streaming(Vq, Vq, 7, True, (3,), (7, 120), 64)
    with pytest.raises(ValueError, match="buckets"):
        knn.knn_tables_prefix_streaming(Vq, Vq, 7, True, (6, 3), (25,), 64)


# ------------------------------------------------------------- surrogates
def test_shuffle_surrogates_preserve_values():
    from repro.inference import random_shuffle

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal(200), jnp.float32)
    s = np.asarray(random_shuffle(jax.random.PRNGKey(0), x, 5))
    assert s.shape == (5, 200)
    for row in s:
        np.testing.assert_allclose(np.sort(row), np.sort(np.asarray(x)))
    assert not np.array_equal(s[0], s[1])  # distinct draws


def test_phase_surrogates_preserve_spectrum():
    """FFT phase randomization: power spectrum (and hence mean and
    autocovariance) preserved, series itself changed."""
    from repro.inference import phase_randomized

    rng = np.random.default_rng(2)
    for L in (200, 201):  # even L exercises the Nyquist-bin branch
        x = np.cumsum(rng.standard_normal(L)).astype(np.float32)
        s = np.asarray(phase_randomized(jax.random.PRNGKey(3), jnp.asarray(x), 4))
        P0 = np.abs(np.fft.rfft(x)) ** 2
        P1 = np.abs(np.fft.rfft(s, axis=-1)) ** 2
        np.testing.assert_allclose(P1, np.broadcast_to(P0, P1.shape), rtol=2e-3)
        np.testing.assert_allclose(s.mean(axis=-1), x.mean(), rtol=1e-3)
        assert np.abs(s - x).max() > 0.1  # actually randomized


def test_surrogate_futures_deterministic_per_series_id():
    """The fold_in(key, series_id) derivation makes the draw independent
    of tile composition: the same series under the same id yields the
    same futures whether batched alone or with others."""
    from repro.inference import surrogate_futures

    rng = np.random.default_rng(3)
    cfg = EDMConfig(E_max=4)
    ts = jnp.asarray(rng.standard_normal((3, 100)), jnp.float32)
    key = jax.random.PRNGKey(9)
    ids = jnp.asarray([5, 2, 7], jnp.int32)
    full = np.asarray(surrogate_futures(key, ts, ids, n=4, kind="phase", cfg=cfg))
    solo = np.asarray(
        surrogate_futures(key, ts[1:2], ids[1:2], n=4, kind="phase", cfg=cfg)
    )
    np.testing.assert_array_equal(full.reshape(3, 4, -1)[1], solo.reshape(4, -1))
    # different id -> different draw
    other = np.asarray(
        surrogate_futures(
            key, ts[1:2], jnp.asarray([8], jnp.int32), n=4, kind="phase", cfg=cfg
        )
    )
    assert not np.array_equal(solo, other)


def _phase_surrogates_f64(key, x, n):
    """Float64 NumPy phase surrogates from the keys and phases the
    program draws: np.fft.irfft of the phase-randomised spectrum."""
    L = x.shape[-1]
    nf = L // 2 + 1
    phases = np.stack([
        np.asarray(jax.random.uniform(k, (nf,), minval=0.0,
                                      maxval=2.0 * jnp.pi))
        for k in jax.random.split(key, n)
    ]).astype(np.float64)
    X = np.fft.rfft(x.astype(np.float64))
    Xs = np.abs(X) * np.exp(1j * phases)
    Xs[:, 0] = X[0]
    if L % 2 == 0:
        Xs[:, -1] = X[-1]
    return np.fft.irfft(Xs, n=L)


@pytest.mark.parametrize("L", [200, 201])  # even L has a Nyquist bin
def test_phase_surrogates_match_float64_irfft(L):
    """The real inverse-DFT product matches a float64 irfft of the same
    spectrum to float32 rounding of the series' scale."""
    from repro.inference import phase_randomized

    rng = np.random.default_rng(5)
    x = np.cumsum(rng.standard_normal(L)).astype(np.float32)
    key = jax.random.PRNGKey(11)
    s = np.asarray(phase_randomized(key, jnp.asarray(x), 6))
    assert s.shape == (6, L) and s.dtype == np.float32
    np.testing.assert_allclose(s, _phase_surrogates_f64(key, x, 6), rtol=0,
                               atol=1e-5 * np.abs(x).max())


@pytest.mark.parametrize("L", [200, 201])
def test_surrogate_futures_are_future_values_of_phase_surrogates(L):
    """The batched entry's (t * n, Lp) rows are each target's phase
    surrogates (drawn under fold_in(key, series id)) at the future
    positions, whether the product starts there or at sample 0."""
    from repro.core import future_values
    from repro.inference import phase_randomized, surrogate_futures

    rng = np.random.default_rng(6)
    cfg = EDMConfig(E_max=5, tau=2, Tp=1)
    ts = np.cumsum(rng.standard_normal((3, L)), axis=-1).astype(np.float32)
    ids = np.asarray([4, 0, 9], np.int32)
    key = jax.random.PRNGKey(12)
    Lp = cfg.n_points(L)
    got = np.asarray(surrogate_futures(key, jnp.asarray(ts), jnp.asarray(ids),
                                       n=5, kind="phase", cfg=cfg))
    assert got.shape == (3 * 5, Lp)
    for i in range(3):
        k = jax.random.fold_in(key, int(ids[i]))
        full = phase_randomized(k, jnp.asarray(ts[i]), 5)
        want = np.asarray(jax.vmap(
            lambda s: future_values(s, cfg.E_max, cfg.tau, cfg.Tp, Lp))(full))
        np.testing.assert_allclose(got[5 * i:5 * (i + 1)], want, rtol=0,
                                   atol=1e-5 * np.abs(ts[i]).max())
        np.testing.assert_allclose(
            want, _phase_surrogates_f64(k, ts[i], 5)[
                :, (cfg.E_max - 1) * cfg.tau + cfg.Tp:][:, :Lp],
            rtol=0, atol=1e-5 * np.abs(ts[i]).max())


# ----------------------------------------------------------------- BH-FDR
def test_bh_adjust_matches_scipy_oracle():
    sp = pytest.importorskip("scipy.stats")
    from repro.inference import bh_adjust

    rng = np.random.default_rng(4)
    for n in (1, 7, 100, 1000):
        p = rng.uniform(size=n)
        p[: n // 3] **= 4  # some small p-values
        np.testing.assert_allclose(
            bh_adjust(p), sp.false_discovery_control(p, method="bh"),
            rtol=1e-12,
        )


def test_bh_threshold_consistent_with_adjust():
    from repro.inference import bh_adjust, bh_threshold

    rng = np.random.default_rng(5)
    p = rng.uniform(size=500) ** 2
    for alpha in (0.01, 0.05, 0.2):
        thr, n = bh_threshold(p, alpha)
        assert n == 500
        np.testing.assert_array_equal(p <= thr, bh_adjust(p) <= alpha)


def test_bh_threshold_discrete_matches_dense():
    """The streaming per-value-count BH pass == the sorted-scan BH pass
    on the expanded array, for discrete empirical p-values (with ties)."""
    from repro.inference import bh_threshold, bh_threshold_discrete

    rng = np.random.default_rng(6)
    m = 19
    for _ in range(5):
        counts = rng.integers(0, 40, size=m + 1)
        p = np.repeat(np.arange(1, m + 2) / (m + 1), counts)
        for alpha in (0.01, 0.05, 0.3):
            thr_d, n_d = bh_threshold_discrete(counts, m, alpha)
            thr, n = bh_threshold(p, alpha)
            assert n_d == n
            assert thr_d == pytest.approx(thr, abs=1e-12)


# ---------------------------------------------------------------- pearson
def test_pearson_degenerate_and_overflow_finite():
    """Constant (dead-neuron) and variance-overflow series must yield
    rho = 0, never NaN/Inf, so significance masks stay finite."""
    rng = np.random.default_rng(7)
    b = jnp.asarray(rng.standard_normal(300), jnp.float32)
    const = jnp.zeros(300)
    big = jnp.asarray((rng.standard_normal(300) * 1e20), jnp.float32)
    cases = [
        pearson(const, b), pearson(b, const), pearson(const, const),
        pearson(jnp.full((300,), 7.5), b),
        pearson(big, big), pearson(big, b),
    ]
    out = np.asarray(jnp.stack(cases))
    assert np.isfinite(out).all(), out
    assert out[0] == out[1] == out[2] == 0.0
    # sane values unaffected
    assert float(pearson(b, b)) == pytest.approx(1.0, abs=1e-5)


# ------------------------------------------- deprecated wrapper + stats
def test_ccm_convergence_deprecated_wrapper(coupled_pair):
    """Same signature, now routed through the batched prefix path: warns,
    matches ccm_convergence_pair exactly, still shows convergence."""
    from repro.core import ccm_convergence
    from repro.inference import ccm_convergence_pair

    cfg = EDMConfig(E_max=4)
    x, y = jnp.asarray(coupled_pair[0]), jnp.asarray(coupled_pair[1])
    key = jax.random.PRNGKey(0)
    with pytest.warns(DeprecationWarning):
        rhos = np.asarray(ccm_convergence(y, x, 3, (40, 150, 700), cfg, key))
    direct = np.asarray(ccm_convergence_pair(y, x, 3, (40, 150, 700), cfg, key))
    np.testing.assert_array_equal(rhos, direct)
    assert rhos.shape == (3,)
    assert rhos[-1] > rhos[0]


def test_convergence_stats_known_curves():
    from repro.inference import convergence_stats

    curves = jnp.asarray(
        [[0.1, 0.5, 0.3], [0.2, 0.4, 0.3], [0.3, 0.3, 0.3], [0.4, 0.2, 0.3]]
    )  # (S=4, 3 pairs): increasing / decreasing / flat
    drho, trend = (np.asarray(v) for v in convergence_stats(curves))
    np.testing.assert_allclose(drho, [0.3, 0.3, 0.0], atol=1e-7)
    np.testing.assert_allclose(trend, [1.0, -1.0, 0.0], atol=1e-7)


# ----------------------------------------------------------- end to end
@pytest.fixture(scope="module")
def sig_system():
    """4 series: x drives y (true edge x->y); a, b independent."""
    from repro.core.pipeline import run_causal_inference
    from repro.data.synthetic import coupled_logistic

    x, y = coupled_logistic(600, beta_xy=0.0, beta_yx=0.12, seed=3)
    a, b = coupled_logistic(600, beta_xy=0.0, beta_yx=0.0, seed=12)
    ts = np.stack([x, y, a, b])
    cfg = EDMConfig(E_max=5)
    res = run_causal_inference(ts, cfg)
    return ts, cfg, res


def test_significance_end_to_end_fdr(sig_system):
    """The true coupled-logistic edge survives BH-FDR; the decoupled pair
    produces no edge in either direction."""
    from repro.inference import SignificanceConfig, run_significance

    ts, cfg, res = sig_system
    sig = SignificanceConfig(
        lib_sizes=(60, 150, 300, 570), n_surrogates=299, alpha=0.05, seed=0
    )
    out = run_significance(ts, res.optE, np.asarray(res.rho), cfg, sig)
    assert out.n_tests == 12  # diagonal excluded
    assert np.isfinite(out.pvals).all()
    assert np.isfinite(out.drho).all() and np.isfinite(out.trend).all()
    edges = {(int(e["src"]), int(e["dst"])) for e in out.edges}
    assert (0, 1) in edges, (edges, out.pvals)  # x -> y survives
    for pair in [(2, 3), (3, 2)]:  # decoupled pair: nothing
        assert pair not in edges
    # self-prediction converges: diagonal trend is maximal for the
    # chaotic series (strictly increasing rho with library size)
    assert out.trend[1, 1] == pytest.approx(1.0)


def test_significance_store_matches_memory_and_resumes(sig_system, tmp_path):
    """Streaming-store run == in-memory run bit-for-bit (non-dividing
    column tiles, multiple chunks); a rerun over the complete store
    resumes via the recount path and reproduces the same edges."""
    import json

    from repro.inference import SignificanceConfig, run_significance

    ts, _, res = sig_system
    cfg = EDMConfig(E_max=5, lib_block=2, target_tile=3)
    sig = SignificanceConfig(
        lib_sizes=(60, 300, 570), n_surrogates=39, alpha=0.2, seed=1
    )
    rho = np.asarray(res.rho)
    mem = run_significance(ts, res.optE, rho, cfg, sig)
    disk = run_significance(
        ts, res.optE, rho, cfg, sig, out_dir=str(tmp_path)
    )
    for a in ("rho_conv", "rho_trend", "pvals"):
        assert (tmp_path / a / "data.npy").exists()
        assert (tmp_path / a / "meta.json").exists()
    emeta = json.loads((tmp_path / "edges" / "meta.json").read_text())
    assert emeta["n_edges"] == len(disk.edges)
    assert emeta["seed"] == 1
    np.testing.assert_array_equal(np.asarray(disk.pvals), mem.pvals)
    np.testing.assert_array_equal(np.asarray(disk.drho), mem.drho)
    np.testing.assert_array_equal(np.asarray(disk.trend), mem.trend)
    np.testing.assert_array_equal(disk.edges, mem.edges)

    # resume over the complete store: nothing recomputed, same outputs
    again = run_significance(
        ts, res.optE, rho, cfg, sig, out_dir=str(tmp_path)
    )
    assert again.p_threshold == mem.p_threshold
    np.testing.assert_array_equal(np.asarray(again.pvals), mem.pvals)
    np.testing.assert_array_equal(again.edges, mem.edges)


def test_significance_seed_reproducibility(sig_system):
    from repro.inference import SignificanceConfig, run_significance

    ts, cfg, res = sig_system
    rho = np.asarray(res.rho)
    outs = [
        run_significance(
            ts, res.optE, rho, cfg,
            SignificanceConfig(lib_sizes=(60, 570), n_surrogates=9, seed=s),
        )
        for s in (0, 0, 1)
    ]
    np.testing.assert_array_equal(outs[0].pvals, outs[1].pvals)
    np.testing.assert_array_equal(np.asarray(outs[0].drho), outs[1].drho)
    assert not np.array_equal(outs[0].pvals, outs[2].pvals)


# ------------------------------------ the sig unit against a plain reference
def _plain():
    """The benchmark's plain significance reference (benchmarks/chip),
    which imports nothing of the program."""
    import pathlib
    import sys

    here = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    if str(here) not in sys.path:
        sys.path.insert(0, str(here))
    import reference
    import reference_sig

    return reference, reference_sig


SIG_ROWS = 16


@pytest.fixture(scope="module")
def sig_case():
    """48 seeded random series x 300 steps, three optE buckets, and the
    observed rho of the first SIG_ROWS library rows."""
    from repro.core import ccm
    from repro.core.pipeline import default_mesh, run_phase2_chunks
    from repro.data.synthetic import dummy_brain

    N = 48
    ts = dummy_brain(N, 300, seed=11)
    optE = np.random.default_rng(4).choice([2, 3, 5], N).astype(np.int32)
    cfg = EDMConfig(E_max=6, lib_block=8)
    fut = np.asarray(ccm.all_futures(jnp.asarray(ts), cfg))
    rho = np.zeros((SIG_ROWS, N), np.float32)
    run_phase2_chunks(ts, fut, optE, cfg, default_mesh(),
                      [(0, 8), (8, 8)], rho=rho)
    return ts, optE, rho


def _sig_maps(tmp_path, case, engine, monkeypatch=None, tile=None):
    """(drho, trend, pvals) of rows [0, SIG_ROWS) through the sig unit
    entry, m 9, lib_sizes (40, 120, Lp); ``tile`` forces the null tile by
    a device budget that fits it and no more."""
    from repro.core.pipeline import default_mesh
    from repro.data.store import TileWriter
    from repro.inference import SignificanceConfig
    from repro.inference import pipeline as ip

    ts, optE, rho = case
    N = ts.shape[0]
    cfg = EDMConfig(E_max=6, lib_block=8, engine=engine)
    Lp = cfg.n_points(ts.shape[1])
    sig = SignificanceConfig(lib_sizes=(40, 120, Lp), n_surrogates=9, seed=7)
    if tile is not None:
        budget = ip.sig_tile_bytes(
            tile, m=9, L=ts.shape[1], Lp=Lp, rows=8, k=6, n_buckets=3,
            n_sizes=3, cfg=cfg)
        monkeypatch.setattr(ip, "_device_budget", lambda mesh: budget)
    runner = ip.SignificanceChunkRunner(ts, optE, cfg, sig, default_mesh())
    assert runner.T == (tile or N)
    ws = []
    for name in ("rho_conv", "rho_trend", "pvals"):
        w = TileWriter(tmp_path / name, SIG_ROWS, N, stage="sig")
        w.ensure_col_order(runner.order)
        ws.append(w)
    ip.run_sig_chunks(runner, [(0, 8), (8, 8)], rho, *ws)
    return [w.assemble() for w in ws]


@pytest.mark.parametrize("engine", ["reference", "pallas-interpret"])
def test_sig_unit_matches_plain_reference(tmp_path, sig_case, engine):
    reference, reference_sig = _plain()
    ts, optE, _ = sig_case
    drho, trend, pv = _sig_maps(tmp_path, sig_case, engine)
    m, E_max, Lp = 9, 6, 294
    kw = dict(E_max=E_max, tau=1, Tp=1, exclude_self=True)
    lib = ts[:SIG_ROWS]
    fut = reference.futures(jnp.asarray(ts), E_max, 1, 1)
    rho_ref = reference.rho_rows(lib, fut, optE, **kw)
    surr = reference_sig.surrogates(7, jnp.asarray(ts), np.arange(48), m)
    null = reference.rho_rows(
        lib, reference.futures(surr.reshape(-1, 300), E_max, 1, 1),
        np.repeat(optE, m), **kw).reshape(SIG_ROWS, 48, m)
    p_ref = reference_sig.pvalues(rho_ref, null)
    curves = reference_sig.curves(lib, fut, optE,
                                  reference_sig.permutation(7, Lp),
                                  (40, 120, Lp), **kw)
    drho_ref, trend_ref, gaps = reference_sig.convergence(curves)

    tie = np.abs(null - rho_ref[..., None]).min(axis=-1) < 1e-4
    assert tie.sum() < 0.1 * tie.size
    # p = j / (m + 1): compare the exceedance counts j
    np.testing.assert_array_equal(np.rint(pv * (m + 1))[~tie],
                                  np.rint(p_ref * (m + 1))[~tie])
    assert gaps.min() > 0  # no near-tie in the curves here
    np.testing.assert_array_equal(trend, trend_ref)
    np.testing.assert_allclose(drho, drho_ref, atol=1e-5)
    assert len(np.unique(p_ref)) > m // 2  # the null is not degenerate


def test_sig_values_do_not_depend_on_the_null_tile(tmp_path, sig_case,
                                                   monkeypatch):
    base = _sig_maps(tmp_path / "untiled", sig_case, "reference")
    tiled = _sig_maps(tmp_path / "tiled", sig_case, "reference",
                      monkeypatch, tile=7)
    for name, a, b in zip(("drho", "trend", "pvals"), base, tiled):
        if name == "drho":
            np.testing.assert_allclose(a, b, atol=1e-6)
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_null_tile_rule_at_fish1_on_one_chip():
    """Fish1_Normo (53,053 x 1,450), m 99, lib_sizes (100, 400, 1430) on
    a 16 GB chip: the untiled surrogate batch does not fit, the chosen
    tile does, and is a few thousand targets."""
    from repro.inference.pipeline import (
        HBM_PLAN_SHARE, sig_null_tile, sig_tile_bytes,
    )

    N, L = 53_053, 1_450
    cfg = EDMConfig(E_max=20)
    shape = dict(m=99, L=L, Lp=cfg.n_points(L), rows=cfg.lib_block, k=10,
                 n_buckets=7, n_sizes=3, cfg=cfg)
    budget = int(HBM_PLAN_SHARE * 16e9)
    T = sig_null_tile(budget, N, **shape)
    assert sig_tile_bytes(T, **shape) <= budget
    assert sig_tile_bytes(T + 1, **shape) > budget
    assert sig_tile_bytes(N, **shape) > 16e9
    assert 1_000 < T < 10_000 and 5 <= -(-N // T) <= 30
    # the futures alone of the untiled batch: 30.0 GB
    assert N * 99 * 1_430 * 4 == pytest.approx(30.0e9, rel=0.01)
    assert sig_null_tile(budget, 2_000, **shape) == 2_000  # target_tile cap
    assert sig_null_tile(None, N, **shape) == N  # no counters: untiled
    assert sig_null_tile(1, N, **shape) == 1
