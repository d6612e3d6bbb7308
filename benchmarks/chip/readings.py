"""Readings of the numbers that decide ``correct``, over many seeds.

    python3 benchmarks/chip/readings.py --workload fish1_normo.ccm \\
        --seeds 101-112 --seconds 2
    python3 benchmarks/chip/readings.py --workload fish1_normo.ccm \\
        --seeds 201-203 --seconds 2 --control

Each seed is one run of the cell's driver in this process, with a short
window at the cell's own load, and prints one JSON line with the compared
numbers.  ``--control`` runs the program with its lower-precision path
switched on (``dist_dtype="bfloat16"``: kNN distances accumulated in
bfloat16 where the configuration states float32).  The limits in the
configuration files are set between the sound runs' largest reading and
the control's smallest (PERF.md).  The benchmark's own runs never run
this script.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
CONTROL = {"dist_dtype": "bfloat16"}


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def reading(root, workload: str, seed: int, seconds: float, overrides: dict,
            devices=None) -> dict:
    """The compared numbers of one run of ``workload``."""
    import bench

    _, res, _ = bench.drive(root, workload, seed, seconds, False, devices,
                            overrides)
    return {"seed": seed, "correct": res.correct, "attempted": res.attempted,
            **{k: v for k, (v, _) in res.check.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112,300")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import bench

    bench.use_compile_cache()
    for s in seeds(args.seeds):
        out = reading(bench.ROOT, args.workload, s, args.seconds,
                      CONTROL if args.control else {})
        out["control"] = args.control
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
