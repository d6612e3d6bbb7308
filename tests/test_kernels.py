"""Pallas kernel validation (interpret mode on CPU) vs pure-jnp oracles:
shape/dtype sweeps and equivalence of the full kernel-backed CCM row
against the reference path (hypothesis property tests:
tests/test_properties.py)."""
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


@pytest.mark.parametrize("B,Lq,Lp,k", [(1, 50, 80, 3), (37, 200, 300, 9), (64, 256, 256, 21)])
def test_ccm_lookup_vs_oracle(B, Lq, Lp, k):
    rng = np.random.default_rng(B)
    idx = jnp.asarray(rng.integers(0, Lp, size=(Lq, k)), jnp.int32)
    w = jnp.asarray(rng.uniform(size=(Lq, k)), jnp.float32)
    Y = jnp.asarray(rng.standard_normal((B, Lp)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(
            ccm_lookup(idx, w, Y, block_b=16, block_t=64, interpret=True)
        ),
        np.asarray(ccm_lookup_ref(idx, w, Y)),
        rtol=1e-5, atol=1e-6,
    )


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
