"""Chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python3 benchmarks/chip/bench.py --workload fish1_normo.ccm \\
        --seed 7 --seconds 40 --trace 0

Everything a cell needs is found by name:

- the cell in ``BENCHMARK.json`` (``workloads``), with its configuration
  (``configs[].file``, a JSON file of sizes) and its traffic mix
  (``traffic/<traffic>.json``, a data file);
- the traffic file names its driver, ``drivers/<driver>.py``, which sets
  the cell up, measures the window and checks the output;
- each per-layer metric is read by ``metrics/<metric>.py``.

So a later change adds a cell, a configuration or a metric as new files
and never edits one.  With ``--trace 0`` the last line of standard output
holds the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace, the program's own telemetry spans and
the device's memory counters of the same run.  The numbers that decide
``correct`` are printed, each beside its limit, as the last lines of
standard error and under ``check``, the last key of the result line.

The run exits non-zero and prints no result when JAX finds no TPU, fewer
chips than the cell asks for, or no program beside ``BENCHMARK.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = HERE.relative_to(ROOT)  # the benchmark's files, under a root
CACHE_DIR = ROOT / ".jax_cache"


class Refused(Exception):
    """The run cannot be made here; no result is printed."""


def load_module(path: pathlib.Path, name: str | None = None):
    """Import a file of the benchmark by its path."""
    if not path.is_file():
        raise Refused(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        name or "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str, root: pathlib.Path = ROOT) -> dict:
    """Everything one cell needs, found by name from ``spec``."""
    here = root / BENCH
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (here / "traffic" / f"{cell['traffic']}.json").read_text())
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m for m in spec["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "driver": here / "drivers" / f"{traffic['driver']}.py",
        "end_to_end": end_to_end,
        "per_layer": [(m, here / "metrics" / f"{m['name']}.py")
                      for m in per_layer],
    }


def device_peaks(kind: str, root: pathlib.Path = ROOT) -> dict:
    """The peaks of one device kind; a kind not in the table is an error."""
    table = json.loads((root / BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} has no entry in peaks.json")
    return table[kind]


def require_chips(n: int):
    """The first ``n`` TPU devices, or Refused."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise Refused(f"the cell asks for {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def read_layers(per_layer, window) -> dict:
    """Per-layer metrics from their readers; a reader that finds nothing
    to read returns None and its metric is left out."""
    out = {}
    for m, path in per_layer:
        value = load_module(path).read(window)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(res, metrics: dict, devices, trace: bool) -> dict:
    dev = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": res.memory_peak_bytes,
    }
    line = {"correct": res.correct, "attempted": res.attempted,
            "failed": res.failed, "metrics": metrics, "device": dev}
    if trace and res.trace is not None:
        dev["busy_s"] = res.trace["busy_s"]
        dev["window_s"] = res.trace["window_s"]
        line["breakdown"] = res.trace["breakdown"]
    line["check"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in res.check.items()}
    return line


def drive(root: pathlib.Path, workload: str, seed: int, seconds: float,
          trace: bool, devices=None, overrides=None):
    """Set up, measure and check one cell through its driver: (what
    ``resolve`` found, the driver's result, the devices).  ``devices``
    skips the look for a chip (tests on the host); ``overrides`` change
    the program's configuration (the control of ``readings.py``)."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    r = resolve(spec, workload, root)
    if not (root / "src" / "repro").is_dir():
        raise Refused("no program (src/repro) beside BENCHMARK.json")
    for p in (root / "src", root / BENCH):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    if devices is None:
        devices = require_chips(r["cell"]["chips"])
    from compiles import CompileCounter

    ctx = types.SimpleNamespace(
        cell=r["cell"], config=r["config"], traffic=r["traffic"], seed=seed,
        seconds=seconds, trace=trace, devices=devices,
        counter=CompileCounter(), t_start=T_START,
        peaks=device_peaks(devices[0].device_kind, root), log=log,
        cfg_overrides=overrides or {},
    )
    driver = load_module(r["driver"])
    with tempfile.TemporaryDirectory(prefix="chipbench") as tmp:
        ctx.tmp = pathlib.Path(tmp)
        return r, driver.run(ctx), devices


def run(args, root: pathlib.Path = ROOT, devices=None) -> dict:
    """One run of one cell; returns the result line."""
    r, res, devices = drive(root, args.workload, args.seed, args.seconds,
                            bool(args.trace), devices)
    if args.trace:
        metrics = read_layers(r["per_layer"], res.window)
    else:
        metrics = {m["name"]: {"value": res.end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in r["end_to_end"] if m["name"] in res.end_to_end}
    for name, (value, limit) in res.check.items():
        log(f"check {name} {value!r} limit {limit!r}")
    return result_line(res, metrics, devices, bool(args.trace))


def use_compile_cache() -> None:
    """JAX's persistent compile cache in the checkout, at a fixed path
    (the path is part of the cache's key), whatever the environment says:
    every program, however quick to compile, and no eviction, which needs
    a time stamp beside every entry and fails on an entry without one."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    use_compile_cache()
    try:
        line = run(args)
    except Refused as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
