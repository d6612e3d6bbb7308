"""The significance cell on the host's CPU at a tiny size: a sound run is
correct, and the bfloat16 control and a fault in the p-values make
``correct`` come out false; the prefix kernel's counts made by hand; the
readers of the sig stage's own spans clipped to the window and silent
without their span."""
from __future__ import annotations

import argparse
import json
import types

import jax
import numpy as np
import pytest

from chipbench_tiny import HERE, REPO, make_root

import bench
import readings
from repro.inference import pipeline as sig_pipeline

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "fish1_normo.significance"
TRAFFIC = {
    "driver": "sig_share", "engine": "reference", "bucketed": True,
    "target_tile": 0, "devices": 1, "n_surrogates": 9, "surrogate": "phase",
    "lib_sizes": [40, 80, 144], "alpha": 0.05,
    "check": {"rows": 4, "targets": 30, "near_tie": 1e-4,
              "drho_max_abs_diff_limit": 1e-4},
}
NEW = ["knn_topk_prefix.time_share", "knn_topk_prefix_roofline",
       "surrogates.time_share", "sig_store_write_share", "sig_prep_s"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("tiny_sig"))
    (root / "benchmarks" / "chip" / "traffic" / "sig_cpu.json").write_text(
        json.dumps(TRAFFIC))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.significance", "config": "tiny",
                              "traffic": "sig_cpu", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def args(seed=3141592653, seconds=0.5, trace=0):
    return argparse.Namespace(workload="tiny.significance", seed=seed,
                              seconds=seconds, trace=trace)


def cpu():
    return jax.devices("cpu")[:1]


def test_sound_run_is_correct(root):
    line = bench.run(args(), root, devices=cpu())
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert list(line)[-1] == "check"
    assert set(line["metrics"]) == {"pairs_per_s", "setup_s"}
    assert line["metrics"]["pairs_per_s"]["value"] > 0
    ck = line["check"]
    assert ck["pval_mismatches"]["value"] == 0
    assert ck["trend_mismatches"]["value"] == 0
    assert ck["drho_max_abs_diff"]["value"] <= 1e-4
    assert ck["pval_near_ties"]["value"] <= ck["pval_near_ties"]["limit"]


def test_control_fails(root):
    out = readings.reading(root, "tiny.significance", 7, 0.5,
                           readings.CONTROL, devices=cpu())
    assert out["correct"] is False
    assert out["drho_max_abs_diff"] > 1e-4


def test_fault_in_the_pvalues_fails(root, monkeypatch):
    make = sig_pipeline.make_null_tile_fn

    def patched(mesh, cfg, m):
        for_plan = make(mesh, cfg, m)
        return lambda seg_plan: (
            lambda *a: for_plan(seg_plan)(*a).at[:, 3].set(1.0 / (m + 1)))

    monkeypatch.setattr(sig_pipeline, "make_null_tile_fn", patched)
    line = bench.run(args(), root, devices=cpu())
    assert line["correct"] is False
    assert line["check"]["pval_mismatches"]["value"] > 0


def test_prefix_counts_by_hand():
    pre = bench.load_module(HERE / "metrics" / "knn_topk_prefix_roofline.py")
    # 2 rows, Lp 10, buckets E in {2, 3}, sizes (4, 8): E_top 3, n_E 2,
    # Lc 8.  ops per row: 10 * 8 * (3 * 3 + 2) = 880; bytes per row:
    # vectors 2 * 3 * 10 * 4 = 240, permutation 8 * 4 = 32, tables
    # 2 sizes * 2 E * 10 * 4 * 8 = 1,280.
    assert pre.counts(2, 10, [2, 3], (4, 8)) == (1760.0, 3104.0)


def reader(name):
    return bench.load_module(HERE / "metrics" / f"{name}.py").read


def window(spans, anchor=100.0, window_s=10.0):
    return types.SimpleNamespace(spans=spans, anchor=anchor,
                                 window_s=window_s)


def test_store_share_is_clipped_to_the_window():
    spans = [
        ("sig/drain", 98.0, 101.0, {"gather_s": 0.5}),   # 1 s store inside
        ("sig/drain", 103.0, 106.0, {"gather_s": 1.0}),  # 2 s
        ("sig/drain", 109.0, 112.0, {"gather_s": 0.5}),  # 0.5 s inside
        ("sig/drain", 110.0, 111.0, {"gather_s": 0.0}),  # after
        ("phase2/drain", 100.0, 110.0, {"gather_s": 0.0}),
    ]
    assert reader("sig_store_write_share")(window(spans)) == \
        pytest.approx(35.0)


def test_prep_from_the_first_unit():
    attrs = {"prep_s": 1.25, "first_dispatch_s": 3.5, "null_tile": 64,
             "surrogate_bytes": 10, "chunks": 4, "rows": 32}
    spans = [("sig/unit", 60.0, 112.0, attrs),
             ("sig/unit", 111.0, 115.0, {**attrs, "prep_s": 0.0}),
             ("phase2/unit", 50.0, 55.0, {"prep_s": 9.0})]
    assert reader("sig_prep_s")(window(spans)) == pytest.approx(1.25)


@pytest.mark.parametrize("metric", ["sig_store_write_share", "sig_prep_s"])
def test_no_reading_without_the_span(metric):
    others = [("phase2/drain", 100.0, 101.0, {"gather_s": 0.5}),
              ("phase2/unit", 60.0, 112.0, {"prep_s": 1.0})]
    assert reader(metric)(window(others)) is None
    assert reader(metric)(window([])) is None
    assert reader(metric)(types.SimpleNamespace(anchor=0.0,
                                                window_s=1.0)) is None


@pytest.mark.parametrize("metric", ["knn_topk_prefix.time_share",
                                    "knn_topk_prefix_roofline",
                                    "surrogates.time_share"])
def test_trace_metrics_silent_without_their_operations(metric):
    w = types.SimpleNamespace(
        trace={"devices": [{"complete": True, "ops": {"ccm_lookup": 1.0}}],
               "complete_devices": 1, "window_s": 10.0},
        modules={"jit_other": 2.0}, chunks=2, chunk_rows=8, devices=1,
        Lp=10, optE_counts={2: 3}, lib_sizes=(4, 8),
        peaks={"f32_vector_ops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert reader(metric)(w) is None


def test_surrogate_share_from_the_modules_line():
    w = types.SimpleNamespace(
        trace={"complete_devices": 1, "window_s": 10.0},
        modules={"jit_surrogate_futures": 2.5, "jit_other": 4.0})
    assert reader("surrogates.time_share")(w) == pytest.approx(25.0)


@pytest.mark.parametrize("metric", NEW)
def test_declared_for_the_significance_cell(metric):
    (m,) = [m for m in SPEC["per_layer"] if m["name"] == metric]
    assert (HERE / "metrics" / f"{metric}.py").is_file()
    assert m["workloads"] == [CELL]
    assert m["moves"] == ("setup_s" if metric == "sig_prep_s"
                          else "pairs_per_s")
    assert metric in {p["name"] for p, _ in
                      bench.resolve(SPEC, CELL)["per_layer"]}


def test_cell_is_the_fish1_share_on_one_chip():
    r = bench.resolve(SPEC, CELL)
    assert r["cell"]["chips"] == 1 and r["traffic"]["devices"] == 1
    assert r["config"]["N"] == 53_053 and r["config"]["library_rows"] == 104
    t = r["traffic"]
    assert (t["n_surrogates"], t["surrogate"], t["lib_sizes"], t["alpha"]) \
        == (99, "phase", [100, 400, 1430], 0.05)
    assert {m["name"] for m in r["end_to_end"]} == {"pairs_per_s",
                                                     "setup_s"}
    # m = 99 surrogates: BH at 0.05 can reject the smallest p-value once
    # a fifth of the tests sit on it (n / (q (m + 1)))
    assert 1 / (0.05 * (t["n_surrogates"] + 1)) == pytest.approx(0.2)
    assert np.all(np.diff(t["lib_sizes"]) > 0)


def test_configuration_of_its_own_states_the_analysis_it_runs():
    r = bench.resolve(SPEC, CELL)
    configs = {c["name"]: c for c in SPEC["configs"]}
    own = configs[r["cell"]["config"]]
    assert own["file"] != configs["fish1_normo"]["file"]
    assert own["source"] != configs["fish1_normo"]["source"]
    assert sum(c["config"] == own["name"] for c in SPEC["workloads"]) == 1
    phase2 = json.loads((REPO / configs["fish1_normo"]["file"]).read_text())
    c, t = r["config"], r["traffic"]
    for key in ("N", "L", "library_rows", "E_max", "tau", "Tp",
                "exclude_self", "lib_block", "stream_depth", "precision",
                "optE_histogram"):
        assert c[key] == phase2[key], key
    assert {k: t[k] for k in c["significance"]} == c["significance"]
    assert t["check"] == c["check"]
    assert set(own["reduced"]) == set(c["reduced"])


@pytest.mark.parametrize("stated", [{"n_surrogates": 19},
                                    {"lib_sizes": [40, 144]}])
def test_traffic_that_is_not_the_stated_analysis_is_refused(tmp_path,
                                                           stated):
    root = make_root(tmp_path)
    bench_dir = root / "benchmarks" / "chip"
    (bench_dir / "traffic" / "sig_cpu.json").write_text(json.dumps(TRAFFIC))
    analysis = {k: TRAFFIC[k] for k in ("n_surrogates", "surrogate",
                                        "lib_sizes", "alpha")}
    config = json.loads((bench_dir / "configs" / "tiny.json").read_text())
    config.update(significance={**analysis, **stated},
                  check=TRAFFIC["check"])
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(config))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.significance", "config": "tiny",
                              "traffic": "sig_cpu", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="not the configuration's"):
        bench.run(args(), root, devices=cpu())
