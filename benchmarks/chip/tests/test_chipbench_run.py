"""Whole runs of the benchmark on the host's CPU at a tiny size: a sound
run is correct, and the control and each fault the cell can have make
``correct`` come out false."""
from __future__ import annotations

import jax
import pytest

from chipbench_tiny import args, make_root

import bench
import readings
from repro.core import pipeline


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


def cpu():
    return jax.devices("cpu")[:1]


def test_sound_run_is_correct(root):
    line = bench.run(args(), root, devices=cpu())
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert list(line)[-1] == "check"
    m = line["metrics"]
    assert m["pairs_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert m["chunk_p90_s"]["unit"] == "s"
    assert line["check"]["rho_max_abs_diff"]["value"] <= 1e-4
    assert line["device"]["count"] == 1


def test_control_fails(root):
    out = readings.reading(root, "tiny.ccm", 5, 0.5, readings.CONTROL,
                           devices=cpu())
    assert out["correct"] is False
    assert out["rho_max_abs_diff"] > 1e-4


def _broken(monkeypatch, wrap):
    make = pipeline.make_ccm_chunk_fn_bucketed

    def patched(mesh, cfg, plan):
        return wrap(make(mesh, cfg, plan))

    monkeypatch.setattr(pipeline, "make_ccm_chunk_fn_bucketed", patched)


def _altered(fn):
    return lambda rows, fut: fn(rows, fut).at[:, 5].add(0.25)


def _half_left_out(fn):
    def half(rows, fut):
        out = fn(rows, fut)
        return out.at[out.shape[0] // 2:].set(0.0)
    return half


def _state_unchanged(fn):
    last = []

    def stale(rows, fut):
        out = fn(rows, fut)
        last.append(out)
        return last[-2] if len(last) > 1 else out
    return stale


@pytest.mark.parametrize("fault", [_altered, _half_left_out,
                                   _state_unchanged])
def test_fault_fails(root, monkeypatch, fault):
    _broken(monkeypatch, fault)
    line = bench.run(args(), root, devices=cpu())
    assert line["correct"] is False
    assert line["check"]["rho_max_abs_diff"]["value"] > 1e-4
