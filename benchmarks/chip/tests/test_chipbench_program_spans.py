"""The readers of the program's own spans: shares of the window clipped
to it, set-up costs from the work unit's span, no reading where the
program emits no such span, and each one declared in BENCHMARK.json."""
from __future__ import annotations

import json
import types

import pytest

from chipbench_tiny import HERE, REPO

import bench

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = ["fish1_normo.ccm", "subject11.ccm", "fish1_normo.ccm_mesh4"]
SHARES = {"host_slack_share": "phase2/device_wait",
          "d2h_copy_share": "phase2/d2h_copy",
          "unsort_share": "phase2/unsort"}
UNIT = {"phase2_prep_s": "prep_s",
        "phase2_first_dispatch_s": "first_dispatch_s"}


def reader(name):
    return bench.load_module(HERE / "metrics" / f"{name}.py").read


def window(spans, anchor=100.0, window_s=10.0):
    return types.SimpleNamespace(spans=spans, anchor=anchor,
                                 window_s=window_s)


@pytest.mark.parametrize("metric", sorted(SHARES))
def test_share_is_clipped_to_the_window(metric):
    span = SHARES[metric]
    spans = [
        (span, 99.0, 101.0, {}),         # 1 s of it inside
        (span, 103.0, 105.0, {}),        # 2 s
        (span, 109.5, 111.0, {}),        # 0.5 s
        (span, 111.0, 112.0, {}),        # after the window
        ("phase2/drain", 100.0, 110.0, {}),  # another span
    ]
    assert reader(metric)(window(spans)) == pytest.approx(35.0)


@pytest.mark.parametrize("metric", sorted(SHARES) + sorted(UNIT))
def test_no_reading_without_the_span(metric):
    others = [("phase2/drain", 100.0, 101.0, {"gather_s": 0.5}),
              ("phase2/chunk", 101.0, 102.0, {"row0": 0})]
    assert reader(metric)(window(others)) is None
    assert reader(metric)(window([])) is None
    assert reader(metric)(types.SimpleNamespace(anchor=0.0,
                                                window_s=1.0)) is None


@pytest.mark.parametrize("metric", sorted(UNIT))
def test_unit_costs_from_the_unit_span(metric):
    key = UNIT[metric]
    attrs = {"prep_s": 1.25, "first_dispatch_s": 3.5, "chunks": 40,
             "rows": 320, "futures_bytes": 10}
    # the unit starts in set-up, before the window, and ends after it
    spans = [("phase2/unit", 60.0, 112.0, attrs),
             ("phase2/unit", 111.0, 115.0, {**attrs, key: 99.0}),
             ("phase2/dispatch", 101.0, 101.1, {"row0": 0})]
    assert reader(metric)(window(spans)) == pytest.approx(attrs[key])


@pytest.mark.parametrize("metric", sorted(SHARES) + sorted(UNIT))
def test_declared_for_the_three_cells(metric):
    (m,) = [m for m in SPEC["per_layer"] if m["name"] == metric]
    assert (HERE / "metrics" / f"{metric}.py").is_file()
    assert m["workloads"] == CELLS
    assert m["source"] == "program_span"
    assert m["layer"] == "pipeline and store"
    assert m["moves"] == ("setup_s" if metric in UNIT else "pairs_per_s")
    for cell in CELLS:
        assert metric in {p["name"] for p, _ in
                          bench.resolve(SPEC, cell)["per_layer"]}
