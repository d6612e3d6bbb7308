"""Pallas kernel validation (interpret mode on CPU) vs pure-jnp oracles:
shape/dtype sweeps and equivalence of the full kernel-backed CCM row
against the reference path (hypothesis property tests:
tests/test_properties.py)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ccm_lookup.ops import ccm_lookup
from repro.kernels.ccm_lookup.ref import ccm_lookup_ref
from repro.kernels.knn_topk.ops import knn_topk_streaming
from repro.kernels.knn_topk.ref import knn_topk_ref


@pytest.mark.parametrize(
    "E_max,Lq,Lc,k,exclude_self,tile_c",
    [
        (1, 64, 64, 2, False, 32),
        (4, 100, 100, 5, True, 48),
        (6, 200, 150, 7, False, 64),
        (3, 129, 257, 4, False, 96),  # non-multiple of block/tile sizes
        (8, 50, 300, 9, False, 512),  # tile wider than the library
        (20, 130, 130, 21, True, 64),  # paper-scale E_max and k
    ],
)
def test_knn_topk_streaming_vs_oracle(E_max, Lq, Lc, k, exclude_self, tile_c):
    """The streaming (candidate-tiled, Lc-independent VMEM) kernel against
    the dense lax.top_k oracle — bit-identical indices at every tile
    width; full tie/merge coverage is in test_knn_streaming.py."""
    rng = np.random.default_rng(E_max * 1000 + Lq)
    Vq = jnp.asarray(rng.standard_normal((E_max, Lq)), jnp.float32)
    Vc = Vq if exclude_self else jnp.asarray(
        rng.standard_normal((E_max, Lc)), jnp.float32
    )
    idx, d = knn_topk_streaming(
        Vq, Vc, k, exclude_self=exclude_self, block_q=64, tile_c=tile_c,
        interpret=True,
    )
    ridx, rd = knn_topk_ref(Vq, Vc, k, exclude_self)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(d), np.asarray(rd), rtol=1e-5, atol=1e-5)


def test_knn_topk_sorted_and_self_excluded():
    rng = np.random.default_rng(7)
    V = jnp.asarray(rng.standard_normal((4, 90)), jnp.float32)
    idx, d = knn_topk_streaming(V, V, 5, exclude_self=True, tile_c=32,
                                interpret=True)
    d = np.asarray(d)
    idx = np.asarray(idx)
    assert np.all(np.diff(d, axis=-1) >= -1e-6)  # ascending distances
    rows = np.arange(90)
    for e in range(4):
        assert not np.any(idx[e] == rows[:, None])  # self never a neighbour


def _lookup_inputs(seed, R, B, Lq, Lp, k):
    rng = np.random.default_rng(seed)
    lead = () if R is None else (R,)
    idx = jnp.asarray(rng.integers(0, Lp, size=lead + (Lq, k)), jnp.int32)
    w = jnp.asarray(rng.uniform(size=lead + (Lq, k)), jnp.float32)
    Y = jnp.asarray(rng.standard_normal((B, Lp)), jnp.float32)
    return idx, w, Y


@pytest.mark.parametrize(
    "B,Lq,Lp,k,R,block_b",
    [
        pytest.param(1, 50, 80, 3, None, 16, id="1-50-80-3"),
        pytest.param(37, 200, 300, 9, None, 16, id="37-200-300-9"),
        pytest.param(64, 256, 256, 21, None, 16, id="64-256-256-21"),
        # default block_b: B across every sublane boundary of the target
        # tile (S 1 up to 128 targets, 2 up to 256 — 300 pads to 512 —
        # 4 up to 512, 8 from 1,025: 1,100 pads to 2,048)
        pytest.param(1, 40, 60, 4, None, None, id="B1-S1"),
        pytest.param(37, 40, 60, 4, None, None, id="B37-S1"),
        pytest.param(129, 40, 60, 4, None, None, id="B129-S2"),
        pytest.param(300, 40, 60, 4, None, None, id="B300-S2"),
        pytest.param(1100, 40, 60, 4, None, None, id="B1100-S8"),
        pytest.param(2048, 40, 60, 4, None, None, id="B2048-S8"),
        # R tables sharing the futures (vmap folds into the table axis),
        # a time axis of several blocks and a partial target block
        pytest.param(600, 300, 120, 7, 3, None, id="R3-B600"),
        pytest.param(2100, 70, 90, 5, 2, 1024, id="R2-B2100-block_b1024"),
    ],
)
def test_ccm_lookup_vs_oracle(B, Lq, Lp, k, R, block_b):
    idx, w, Y = _lookup_inputs(B, R, B, Lq, Lp, k)
    kw = {"block_t": 64} if block_b is not None else {}
    if block_b is not None:
        kw["block_b"] = block_b
    look = functools.partial(ccm_lookup, interpret=True, **kw)
    if R is None:
        got, want = look(idx, w, Y), ccm_lookup_ref(idx, w, Y)
    else:
        got = jax.vmap(look, in_axes=(0, 0, None))(idx, w, Y)
        want = jnp.stack([ccm_lookup_ref(i, v, Y) for i, v in zip(idx, w)])
    assert got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("B", [300, 2048])
def test_ccm_lookup_bit_identical_to_j_order_sum(B):
    """The kernel sums w[t, j] * y[idx[t, j]] in j order in float32, like
    this NumPy loop, bit for bit.  The weights are powers of two, so every
    product is exact and the adds are the only roundings, whether or not
    a backend fuses a multiply into its add (XLA's CPU backend, running
    the interpreter, does)."""
    Lq, Lp, k = 90, 140, 9
    idx, _, Y = _lookup_inputs(B, 2, B, Lq, Lp, k)
    rng = np.random.default_rng(B + 1)
    w = jnp.asarray(2.0 ** -rng.integers(0, 6, size=(2, Lq, k)), jnp.float32)
    got = np.asarray(jax.vmap(
        functools.partial(ccm_lookup, interpret=True), in_axes=(0, 0, None)
    )(idx, w, Y))
    idx_np, w_np, Y_np = np.asarray(idx), np.asarray(w), np.asarray(Y)
    want = np.zeros((2, B, Lq), np.float32)
    for j in range(k):
        want += w_np[:, None, :, j] * Y_np[:, idx_np[:, :, j]].transpose(1, 0, 2)
    np.testing.assert_array_equal(got, want)
    rev = np.zeros_like(want)  # the order shows in the bits
    for j in reversed(range(k)):
        rev += w_np[:, None, :, j] * Y_np[:, idx_np[:, :, j]].transpose(1, 0, 2)
    assert np.any(rev != want)


def test_lookup_tile_picks_full_tiles_within_vmem():
    """S never exceeds cdiv(B, 128) nor block_b / 128; the double-buffered
    futures and output blocks fit the limit it returns, which stays under
    the v5e's 128 MiB VMEM at both paper widths; a full phase-2 block
    (target_block 2,048) gets the full (8, 128) tile."""
    from repro.kernels.ccm_lookup.ccm_lookup import lookup_tile

    for Lp in (1430, 8508):
        for B in (1, 2, 100, 128, 129, 256, 257, 300, 512, 513, 1024,
                  1025, 1100, 2048, 50_000):
            S, limit = lookup_tile(B, Lp)
            assert S in (1, 2, 4, 8)
            assert S <= -(-B // 128)
            assert 2 * (Lp + 256) * S * 128 * 4 <= limit <= 128 * 2**20
            if B <= 128:
                assert S == 1
        assert lookup_tile(2048, Lp)[0] == 8
        assert lookup_tile(2048, Lp, block_b=256)[0] == 2
        assert lookup_tile(2048, Lp, block_b=16)[0] == 1
    assert lookup_tile(300, 1430)[0] == 2 and lookup_tile(512, 1430)[0] == 4
    # a width where the full tile would overflow the budget steps down
    S, limit = lookup_tile(2048, 40_000)
    assert S < 8 and 2 * (40_000 + 256) * S * 128 * 4 <= limit


def test_kernel_backed_ccm_row_matches_reference(small_network):
    """engine='pallas-interpret' routes tables + lookup through the Pallas
    kernels; the causal map must match the reference engine."""
    from repro.core import EDMConfig, ccm_matrix, simplex_batch

    ts, _ = small_network
    ts = jnp.asarray(ts)
    _, optE = simplex_batch(ts, EDMConfig(E_max=4))
    rho_ref = ccm_matrix(ts, optE, EDMConfig(E_max=4, engine="reference"))
    rho_ker = ccm_matrix(ts, optE, EDMConfig(E_max=4, engine="pallas-interpret"))
    np.testing.assert_allclose(
        np.asarray(rho_ref), np.asarray(rho_ker), rtol=1e-5, atol=1e-5
    )


# ------------------------------------------------------------- flash_attn
@pytest.mark.parametrize(
    "B,Sq,Sk,H,K,dh,causal,bq,bk",
    [
        (2, 128, 128, 4, 2, 64, True, 64, 64),
        (1, 256, 256, 6, 6, 32, True, 128, 128),
        (2, 64, 64, 8, 4, 16, False, 32, 32),
        (1, 96, 96, 2, 1, 8, True, 32, 32),  # non-power-of-two seq
    ],
)
def test_flash_attn_vs_oracle(B, Sq, Sk, H, K, dh, causal, bq, bk):
    from repro.kernels.flash_attn.ops import flash_attn
    from repro.kernels.flash_attn.ref import flash_attn_ref

    rng = np.random.default_rng(Sq + H)
    q = jnp.asarray(rng.standard_normal((B, Sq, H, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Sk, K, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Sk, K, dh)), jnp.float32)
    o = flash_attn(q, k, v, causal=causal, block_q=bq, block_k=bk,
                   interpret=True)
    r = flash_attn_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-5, atol=2e-5)


def test_flash_attn_matches_model_sdpa():
    """The kernel's numerics contract == the model's dense/chunked paths."""
    from repro.kernels.flash_attn.ops import flash_attn
    from repro.models.layers import _sdpa_chunked, _sdpa_dense

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 128, 2, 32)), jnp.float32)
    a = _sdpa_dense(q, k, v, causal=True)
    b = _sdpa_chunked(q, k, v, causal=True, chunk=64)
    c = flash_attn(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-5, atol=2e-5)


def test_knn_impl_variants_agree():
    """scan / unroll / blocked:g dense-oracle variants produce identical
    tables (SSPerf HC3)."""
    from repro.core.knn import knn_tables_dense

    rng = np.random.default_rng(3)
    V = jnp.asarray(rng.standard_normal((8, 150)), jnp.float32)
    i0, d0 = knn_tables_dense(V, V, 9, True, impl="scan")
    for impl in ("unroll", "blocked:4", "blocked:2"):
        i1, d1 = knn_tables_dense(V, V, 9, True, impl=impl)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-6, atol=1e-8)
