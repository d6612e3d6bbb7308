"""Shared oracle-checking harness: every engine op vs the reference
engine (the engine-layer contract of DESIGN.md SS5).

Used by tests and by ``python -m repro.engine.check`` as a smoke check on
new backends: random EDM-shaped inputs, max-abs deviation per op, hard
assert against per-op tolerances (indices must match exactly; distances
and forecasts to float32 round-off).
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.core.types import EDMConfig
from repro.engine import get_engine

# op name -> (atol on values); kNN indices are compared exactly.
TOLERANCES = {
    "knn_tables": 1e-5,
    "knn_tables_bucketed": 1e-5,
    "knn_tables_prefix": 0.0,  # one-sweep vs rebuild is a BIT-identity claim
    "ccm_lookup": 1e-5,
}


def check_engine(
    name: str,
    E_max: int = 6,
    Lq: int = 120,
    Lc: int = 120,
    n_targets: int = 7,
    seed: int = 0,
    cfg: EDMConfig | None = None,
) -> dict[str, float]:
    """Run every op of engine ``name`` against the reference engine.

    Returns {op: max_abs_err} on success; raises AssertionError on any
    index mismatch or tolerance violation.
    """
    cfg = cfg or EDMConfig(E_max=E_max)
    ref = get_engine("reference")
    eng = get_engine(name)
    rng = np.random.default_rng(seed)
    Vq = jnp.asarray(rng.standard_normal((E_max, Lq)), jnp.float32)
    Vc = Vq if Lq == Lc else jnp.asarray(
        rng.standard_normal((E_max, Lc)), jnp.float32
    )
    k = E_max + 1
    errs: dict[str, float] = {}

    def _cmp(op, got, want):
        gi, gd = got
        wi, wd = want
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi), err_msg=op)
        err = float(np.max(np.abs(np.asarray(gd) - np.asarray(wd))))
        assert err <= TOLERANCES[op], f"{name}.{op}: max err {err}"
        errs[op] = err
        return gi, gd

    exclude = Lq == Lc
    idx, sqd = _cmp(
        "knn_tables",
        eng.knn_tables(Vq, Vc, k, exclude_self=exclude, cfg=cfg),
        ref.knn_tables(Vq, Vc, k, exclude_self=exclude, cfg=cfg),
    )

    buckets = tuple(sorted({1, max(1, E_max // 2), E_max}))
    _cmp(
        "knn_tables_bucketed",
        eng.knn_tables_bucketed(
            Vq, Vc, k, buckets=buckets, exclude_self=exclude, cfg=cfg
        ),
        ref.knn_tables_bucketed(
            Vq, Vc, k, buckets=buckets, exclude_self=exclude, cfg=cfg
        ),
    )

    lib_sizes = tuple(
        sorted({max(k + 2, Lc // 4), max(k + 3, Lc // 2), Lc})
    )
    _cmp(
        "knn_tables_prefix",
        eng.knn_tables_prefix(
            Vq, Vc, k, buckets=buckets, lib_sizes=lib_sizes,
            exclude_self=exclude, cfg=cfg,
        ),
        ref.knn_tables_prefix(
            Vq, Vc, k, buckets=buckets, lib_sizes=lib_sizes,
            exclude_self=exclude, cfg=cfg,
        ),
    )

    from repro.core import knn

    _, w = knn.tables_with_weights(idx, sqd)
    Y = jnp.asarray(rng.standard_normal((n_targets, Lc)), jnp.float32)
    got = np.asarray(eng.ccm_lookup(idx[-1], w[-1], Y))
    want = np.asarray(ref.ccm_lookup(idx[-1], w[-1], Y))
    err = float(np.max(np.abs(got - want)))
    assert err <= TOLERANCES["ccm_lookup"], f"{name}.ccm_lookup: max err {err}"
    errs["ccm_lookup"] = err
    return errs


def main() -> None:  # pragma: no cover - CLI smoke entry
    import jax

    from repro.engine import available_engines

    for name in available_engines():
        if name == "pallas-compiled" and jax.default_backend() != "tpu":
            print(name, "not run: compiles for a TPU only")
            continue
        errs = check_engine(name)
        print(name, {k: f"{v:.2e}" for k, v in errs.items()})


if __name__ == "__main__":  # pragma: no cover
    main()
