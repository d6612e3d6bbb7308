"""trace_reduce on events worked out by hand, and on a small trace
recorded on a TPU v5 lite chip (tests/data/small_trace.*): phase-2
chunks of 8 library rows x 1,024 targets, L 300, E_max 8, with the
program's telemetry spans of the same window."""
from __future__ import annotations

import json

import pytest

from chipbench_tiny import HERE

import trace_reduce as tr

DATA = HERE / "tests" / "data"


def test_union_and_self_times_by_hand():
    assert tr.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [(0, 3), (5, 9)]
    # loop 0..10 holds a kernel 2..5; the loop's self time is 7 ns
    got = tr.self_times([("loop", 0, 10), ("kernel", 2, 5), ("copy", 20, 30)])
    assert got == pytest.approx({"loop": 7e-9, "kernel": 3e-9, "copy": 1e-8})


def test_window_by_hand():
    # device 0: loop [0, 10] holding kernel [2, 5], copy [20, 30];
    # device 1: one op [0, 30].  Window [0, 50]; host: a drain [8, 25]
    # holding a write [12, 16].
    ops = [[("loop", 0, 10), ("kernel", 2, 5), ("copy", 20, 30)],
           [("loop", 0, 30)]]
    spans = [("phase2/drain", 8, 25), ("store/write_block", 12, 16)]
    r = tr.reduce_window(ops, 0, 50, spans)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["complete_devices"] == 2
    assert [d["busy_s"] for d in r["devices"]] == pytest.approx([20e-9, 30e-9])
    assert r["busy_s"] == pytest.approx(25e-9)
    assert r["busy_s_fullest"] == pytest.approx(30e-9)
    assert tr.kernel_seconds(r, "kernel") == pytest.approx(3e-9)
    # gap [10, 20] on device 0: drain 2 + 4 ns, write 4 ns -> the drain;
    # gaps [30, 50] on both devices: no span, 40 ns over 2 devices.
    gaps = {g[0].split(" (")[0]: g[1] for g in r["breakdown"]["idle_gaps"]}
    assert gaps == pytest.approx({tr.NO_SPAN: 20e-9, "phase2/drain": 5e-9})
    top = r["breakdown"]["device_ops"]
    assert top[0][0] == "loop" and top[0][1] == pytest.approx(18.5e-9)


def test_device_whose_trace_stops_early_is_left_out():
    # device 1's operations end at 10 of a 50 ns window, more than
    # CUT of the window before device 0's: it is not counted as idle.
    r = tr.reduce_window([[("op", 0, 50)], [("op", 0, 10)]], 0, 50)
    assert r["complete_devices"] == 1
    assert [d["complete"] for d in r["devices"]] == [True, False]
    assert r["busy_s"] == pytest.approx(50e-9)
    assert tr.kernel_seconds(r, "op") == pytest.approx(50e-9)
    assert r["breakdown"]["idle_gaps"] == []


def test_clipping_to_the_window():
    r = tr.reduce_window([[("op", -10, 10), ("op", 40, 60)]], 0, 50)
    assert r["devices"][0]["busy_s"] == pytest.approx(20e-9)


def test_op_names():
    assert tr.op_name("%ccm_lookup.46 = f32[8] custom-call(...)") == "ccm_lookup"
    assert tr.op_name("%pad.58.clone = f32[2] pad(...)") == "pad"
    assert tr.op_name("%vmap_jit_knn_topk_streaming__.7 = (...)") == \
        "vmap_jit_knn_topk_streaming__"


@pytest.fixture(scope="module")
def recorded():
    window = json.loads((DATA / "small_trace.window.json").read_text())
    return tr.load(DATA / "small_trace.xplane.pb"), window


def test_recorded_trace(recorded):
    profile, w = recorded
    r = tr.reduce_profile(profile, w["anchor"], w["end"], w["spans"], 1)
    # Window: 18,978,108 ns from the anchor.  Busy: 2,080,831 ns, the
    # union of the 291 operations clipped to it (counted once more at
    # 1 ns resolution, one bin per nanosecond, when the trace was taken).
    assert r["window_s"] == pytest.approx(0.018978108, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.002080831, abs=1e-12)
    # The two kernels are custom calls with nothing nested in them: their
    # time is the plain sum of their events' durations.
    assert tr.kernel_seconds(r, "ccm_lookup") == pytest.approx(0.001412031,
                                                             abs=1e-12)
    assert tr.kernel_seconds(r, "knn_topk_stream") == pytest.approx(
        0.00059856, abs=1e-12)
    ops = r["breakdown"]["device_ops"]
    assert [nm for nm, _ in ops[:2]] == ["vmap_jit_ccm_lookup__",
                                         "vmap_jit_knn_topk_streaming__"]
    assert sum(r["devices"][0]["ops"].values()) == pytest.approx(
        r["busy_s"], rel=1e-9)
    gaps = {g[0].split(" (")[0]: g[1] for g in r["breakdown"]["idle_gaps"]}
    # The longest gap, 10.79 ms after the last chunk's work, falls after
    # every span had closed; 6.10 ms fell while the host drained a block.
    assert gaps[tr.NO_SPAN] == pytest.approx(0.010792556, abs=1e-9)
    assert gaps["phase2/drain"] == pytest.approx(0.006104532, abs=1e-9)
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_anchor_is_required(recorded):
    profile, _ = recorded
    assert tr.anchor_ns(profile) > 0
    with pytest.raises(ValueError, match="device planes"):
        tr.device_ops(profile, 2)
