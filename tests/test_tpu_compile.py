"""Compile guard: the main-path Pallas kernels and the bucketed phase-2
chunk compile for a TPU v5e chip at paper widths (Fish1_Normo Lp 1,430;
Subject11 Lp 8,508), with ``interpret=False``.

Nothing runs: the chip is described, not attached, and the TPU compiler
refuses here what it would refuse on the chip (block shapes that break
the (8, 128) tiling rule, ops Mosaic cannot lower, VMEM or HBM
overflow).  The topology is described inside a fixture only — never at
import, in a skip condition or in a parametrize argument — because one
process at a time may load the TPU library.  Keep these tests in this
one file.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

FISH1_N, FISH1_L = 53_053, 1_450  # configs/edm_datasets.py
LP_FISH1, LP_SUBJECT11 = 1_430, 8_508  # L - (E_max - 1) - Tp at E_max 20
E_MAX, K = 20, 21
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel is in
    return compiled


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("Lp", [LP_FISH1, LP_SUBJECT11])
@pytest.mark.parametrize("tile", ["calibrated", 384])
def test_stream_kernel_compiles_for_v5e(one_chip, Lp, tile):
    from repro.core.knn import calibrate_knn_tile
    from repro.kernels.knn_topk.knn_topk import knn_topk_stream_pallas

    tile_c = calibrate_knn_tile(Lp) if tile == "calibrated" else tile
    _compile(
        lambda V: knn_topk_stream_pallas(
            V, V, K, True, tile_c=tile_c, interpret=False
        ),
        _spec((E_MAX, Lp), one_chip),
    )


@pytest.mark.parametrize("Lp", [LP_FISH1, LP_SUBJECT11])
def test_prefix_kernel_compiles_for_v5e(one_chip, Lp):
    from repro.core.knn import calibrate_knn_tile
    from repro.kernels.knn_topk.knn_topk import knn_topk_prefix_pallas

    lib_sizes = (Lp // 4, Lp // 2, Lp)
    _compile(
        lambda V: knn_topk_prefix_pallas(
            V, V, K, True, (3, 8, 20), lib_sizes,
            tile_c=calibrate_knn_tile(Lp), interpret=False,
        ),
        _spec((E_MAX, Lp), one_chip),
    )


@pytest.mark.parametrize("Lp,blocks", [
    pytest.param(LP_FISH1, "block_b=32", id=str(LP_FISH1)),
    pytest.param(LP_SUBJECT11, "block_b=32", id=str(LP_SUBJECT11)),
    pytest.param(LP_FISH1, "default", id=f"{LP_FISH1}-default"),
    pytest.param(LP_SUBJECT11, "default", id=f"{LP_SUBJECT11}-default"),
])
def test_ccm_lookup_kernel_compiles_for_v5e(one_chip, Lp, blocks):
    """block_b=32: one table, 300 targets in single-sublane blocks.
    default: the blocks the wrapper picks by itself for a phase-2 call,
    8 tables (a lib_block of rows) x target_block 2,048 targets — (8, 128)
    target tiles, whose Subject11 futures block overflows the default
    scoped VMEM unless the kernel raises its limit."""
    from repro.kernels.ccm_lookup.ccm_lookup import (
        ccm_lookup_pallas, lookup_tile,
    )

    if blocks == "default":
        tables, B, kw = (8, Lp, K), 2048, {}
        assert lookup_tile(B, Lp)[0] == 8
    else:
        tables, B, kw = (Lp, K), 300, {"block_b": 32}
    _compile(
        lambda i, w, y: ccm_lookup_pallas(i, w, y, interpret=False, **kw),
        _spec(tables, one_chip, jnp.int32),
        _spec(tables, one_chip),
        _spec((B, Lp), one_chip),
    )


def test_bucketed_phase2_chunk_fits_v5e_at_fish1_width(topo, monkeypatch):
    """The pallas-compiled bucketed phase-2 chunk (what edm_run runs per
    row chunk) at Fish1_Normo width: one chip's mesh, every optE bucket
    present, fits the chip's 16 GB HBM."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import EDMConfig, ccm, pipeline
    from repro.engine.pallas import PallasEngine

    # jax.default_backend() is the CPU here; steer the engine to compile
    # its kernels natively for the described chip.
    monkeypatch.setattr(PallasEngine, "_interpret", lambda self: False)
    mesh = Mesh(np.array(topo.devices[:1]), ("workers",))
    cfg = EDMConfig(E_max=E_MAX, engine="pallas-compiled")
    optE = (np.arange(FISH1_N) % E_MAX + 1).astype(np.int32)
    plan, _ = ccm.make_bucket_plan(optE)
    fn = pipeline.make_ccm_chunk_fn_bucketed(mesh, cfg, plan)
    Lp = cfg.n_points(FISH1_L)
    assert Lp == LP_FISH1
    rows = _spec((cfg.lib_block, FISH1_L),
                 NamedSharding(mesh, P("workers", None)))
    fut = _spec((FISH1_N, Lp), NamedSharding(mesh, P(None, None)))
    compiled = fn.lower(rows, fut).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The kernels keep their own names in the compiled program (the names
    # the device trace gives their operations), whatever wraps them.
    kernels = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        if m is None:
            continue
        name = re.sub(r"(\.\d+)+$", "", m.group(1))
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels.add(name)
        else:
            assert "ccm_lookup" not in name and "knn_topk_stream" not in name
    assert kernels == {"ccm_lookup", "knn_topk_stream"}, kernels
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB"


def test_sig_null_tile_keeps_surrogates_named_and_fits_v5e(topo,
                                                          monkeypatch):
    """The significance stage's tile step at Fish1_Normo width, m 99, at
    the null tile its memory rule picks for a 16 GB chip: surrogate
    generation compiles to its own module under its trace name, the
    null call that consumes the batch holds no FFT, and the bytes of the
    two programs stay inside what the rule planned for."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import EDMConfig, ccm
    from repro.engine.pallas import PallasEngine
    from repro.inference import pipeline as ip
    from repro.inference import surrogates

    monkeypatch.setattr(PallasEngine, "_interpret", lambda self: False)
    mesh = Mesh(np.array(topo.devices[:1]), ("workers",))
    cfg = EDMConfig(E_max=E_MAX, engine="pallas-compiled")
    m, Lp = 99, LP_FISH1
    optE = (np.arange(FISH1_N) % 7 + 3).astype(np.int32)  # E 3..9
    plan, _ = ccm.make_bucket_plan(optE)
    shape = dict(m=m, L=FISH1_L, Lp=Lp, rows=cfg.lib_block,
                 k=ccm._bucket_k(cfg, plan), n_buckets=len(plan.buckets),
                 n_sizes=3, cfg=cfg)
    budget = int(ip.HBM_PLAN_SHARE * HBM_BYTES)
    T = ip.sig_null_tile(budget, FISH1_N, **shape)
    assert 1_000 < T < 10_000

    repl = NamedSharding(mesh, P(None, None))
    surr = surrogates.surrogate_futures.lower(
        _spec((2,), NamedSharding(mesh, P(None)), jnp.uint32),
        _spec((T, FISH1_L), repl),
        _spec((T,), NamedSharding(mesh, P(None)), jnp.int32),
        m, "phase", cfg).compile()
    text = surr.as_text()
    assert text.startswith(f"HloModule {surrogates.TRACE_NAME},")
    assert " fft(" not in text  # lowered to convolutions on the chip

    seg_plan = ccm.make_tile_plans(plan, T)[0][1]
    tables = NamedSharding(mesh, P("workers", None, None, None))
    nb, kb = shape["n_buckets"], shape["k"]
    null = ip.make_null_tile_fn(mesh, cfg, m)(seg_plan).lower(
        _spec((cfg.lib_block, nb, Lp, kb), tables, jnp.int32),
        _spec((cfg.lib_block, nb, Lp, kb), tables),
        _spec((T * m, Lp), repl),
        _spec((cfg.lib_block, T), NamedSharding(mesh, P("workers", None))),
    ).compile()
    assert "tpu_custom_call" in null.as_text()
    assert "surrogate" not in null.as_text().split("\n", 1)[0]
    s, n = surr.memory_analysis(), null.memory_analysis()
    # a batch made while the previous tile's null call still holds its
    # own, then the null call's working set
    peak = max(s.temp_size_in_bytes + s.output_size_in_bytes,
               n.temp_size_in_bytes) + n.argument_size_in_bytes
    assert peak <= ip.sig_tile_bytes(T, **shape) <= budget
