"""BENCHMARK.json resolves by name, keeps to its own format, and the
kernels' operation and byte counts match counts made by hand."""
from __future__ import annotations

import json
import re

import pytest

from chipbench_tiny import HERE, REPO

import bench

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_cell_resolves_by_name(cell):
    r = bench.resolve(SPEC, cell)
    assert r["driver"].is_file()
    assert r["traffic"]["devices"] <= r["cell"]["chips"]
    for key in ("N", "L", "library_rows", "E_max", "optE_histogram", "check"):
        assert key in r["config"]
    for m, path in r["per_layer"]:
        assert callable(bench.load_module(path).read), m["name"]
    names = {m["name"] for m in r["end_to_end"]}
    assert {"setup_s", "pairs_per_s"} <= names


def test_spec_format():
    assert SPEC["command"][1] == "benchmarks/chip/bench.py"
    assert all((REPO / p).is_dir() for p in SPEC["paths"])
    entries = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
               + SPEC["per_layer"])
    assert all(NAME.match(e["name"]) for e in entries)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["moves"] for m in SPEC["per_layer"]}
    assert layers <= {m["name"] for m in SPEC["end_to_end"]}
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(e["why"]) <= 200
    for c in SPEC["configs"]:
        assert (REPO / c["file"]).is_file()


def test_device_without_peaks_is_refused():
    with pytest.raises(bench.Refused, match="no entry in peaks.json"):
        bench.device_peaks("TPU v0 unknown")
    peaks = bench.device_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9


def test_host_cpu_is_refused_without_chip():
    args = type("A", (), {"workload": SPEC["workloads"][0]["name"],
                          "seed": 1, "seconds": 1.0, "trace": 0})
    with pytest.raises(bench.Refused, match="no TPU"):
        bench.run(args)


def test_knn_counts_by_hand():
    knn = bench.load_module(HERE / "metrics" / "knn_topk_roofline.py")
    # 2 rows, Lp 10, buckets E in {2, 3}: E_top 3, n_E 2.
    # ops per row: 10 * 10 * (3 * 3 + 2) = 1,100; bytes per row: vectors
    # 2 * 3 * 10 * 4 = 240, tables 2 * 10 * 4 * 8 = 640.
    assert knn.counts(2, 10, [2, 3]) == (2200.0, 1760.0)


def test_lookup_counts_by_hand():
    look = bench.load_module(HERE / "metrics" / "ccm_lookup_roofline.py")
    # 2 rows, 1 device chunk, 5 targets (3 at E 2, 2 at E 3), Lp 10:
    # ops per row: 3 * 2 * 3 * 10 + 2 * 2 * 4 * 10 = 340; bytes: futures
    # 5 * 10 * 4 = 200, tables 2 rows * (10 * 3 + 10 * 4) * 8 = 1,120,
    # rho 2 * 5 * 4 = 40.
    assert look.counts(2, 1, 5, 10, {2: 3, 3: 2}) == (680.0, 1360.0)
