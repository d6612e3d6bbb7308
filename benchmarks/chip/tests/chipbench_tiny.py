"""A tiny copy of the benchmark's layout for runs on the host's CPU.

The root holds the real drivers, metrics and program (linked), a cell
``tiny.ccm`` of 64 targets x 150 time steps with the ``reference`` engine,
and a ``peaks.json`` entry for the CPU, so that a test can drive a whole
run without a chip.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
for p in (HERE, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

CONFIG = {
    "N": 64, "L": 150, "library_rows": 12, "E_max": 6, "tau": 1, "Tp": 1,
    "exclude_self": True, "lib_block": 4, "stream_depth": 2,
    "precision": "float32",
    "optE_histogram": {"counts": {"2": 3, "3": 4, "4": 3}},
    "check": {"rows": 6, "targets": 0, "rho_max_abs_diff_limit": 1e-4},
}
TRAFFIC = {"driver": "ccm_share", "engine": "reference", "bucketed": True,
           "target_tile": 0, "devices": 1}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    bench_dir = tmp / "benchmarks" / "chip"
    (bench_dir / "configs").mkdir(parents=True)
    (bench_dir / "traffic").mkdir()
    for name in ("drivers", "metrics"):
        (bench_dir / name).symlink_to(HERE / name)
    (tmp / "src").symlink_to(REPO / "src")
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (bench_dir / "traffic" / "ccm_cpu.json").write_text(json.dumps(TRAFFIC))
    peaks = json.loads((HERE / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"f32_vector_ops_per_s": 1e11,
                               "hbm_bytes_per_s": 1e10}
    (bench_dir / "peaks.json").write_text(json.dumps(peaks))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "benchmarks/chip/configs/tiny.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": "tiny.ccm", "config": "tiny",
                          "traffic": "ccm_cpu", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def args(seed: int = 5, seconds: float = 0.5, trace: int = 0):
    return argparse.Namespace(workload="tiny.ccm", seed=seed,
                              seconds=seconds, trace=trace)
