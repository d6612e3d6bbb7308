"""Hypothesis property tests (embedding invariants, weight distributions,
kernel oracles, quantization bounds).

hypothesis is an OPTIONAL dev dependency (requirements-dev.txt): this
module is skipped wholesale when it is absent so the rest of the tier-1
suite still collects and runs (the seed hard-imported hypothesis from
three modules, erroring collection everywhere).
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax.numpy as jnp  # noqa: E402


# ---------------------------------------------------------------- embedding
@given(
    E=st.integers(1, 6),
    tau=st.integers(1, 3),
    L=st.integers(40, 120),
)
@settings(max_examples=15, deadline=None)
def test_embedding_point_invariant(E, tau, L):
    """Every embedded point's coordinates are exact series values."""
    from repro.core import delay_embed

    rng = np.random.default_rng(E * 100 + tau)
    x = rng.standard_normal(L).astype(np.float32)
    Lp = L - (E - 1) * tau
    emb = np.asarray(delay_embed(jnp.asarray(x), E, tau))
    t = rng.integers(0, Lp)
    p = t + (E - 1) * tau
    np.testing.assert_array_equal(emb[t], x[[p - k * tau for k in range(E)]])


# ------------------------------------------------------------------ weights
@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_simplex_weights_are_a_distribution(seed):
    from repro.core import simplex_weights

    rng = np.random.default_rng(seed)
    k = rng.integers(2, 22)
    d = np.sort(rng.uniform(0, 10, size=(4, k)).astype(np.float32), axis=-1)
    w = np.asarray(simplex_weights(jnp.asarray(d**2), k))
    assert np.all(w >= 0)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-5)
    # nearest neighbour never gets less weight than the farthest
    assert np.all(w[:, 0] + 1e-6 >= w[:, -1])


# ------------------------------------------------------------------ kernels
@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_knn_topk_property(seed):
    from repro.kernels.knn_topk.ops import knn_topk_streaming
    from repro.kernels.knn_topk.ref import knn_topk_ref

    rng = np.random.default_rng(seed)
    E_max = int(rng.integers(1, 8))
    Lq = int(rng.integers(16, 150))
    Lc = int(rng.integers(E_max + 3, 150))
    k = int(rng.integers(1, min(8, Lc - 1)))
    tile_c = int(rng.integers(8, 150))
    Vq = jnp.asarray(rng.standard_normal((E_max, Lq)), jnp.float32)
    Vc = jnp.asarray(rng.standard_normal((E_max, Lc)), jnp.float32)
    idx, d = knn_topk_streaming(Vq, Vc, k, block_q=32, tile_c=tile_c,
                                interpret=True)
    ridx, rd = knn_topk_ref(Vq, Vc, k, False)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(rd))


# ------------------------------------------------------------- optimization
@given(st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_quantize_roundtrip_error_bound(seed):
    from repro.optim import grad_compress

    g = jnp.asarray(np.random.default_rng(seed).standard_normal(64), jnp.float32)
    q, scale = grad_compress.quantize(g)
    err = jnp.abs(grad_compress.dequantize(q, scale) - g)
    assert float(err.max()) <= float(scale) * 0.5 + 1e-6
