"""Telemetry spine + autotuner (DESIGN.md SS11): record schema, sink
protocol (memory / stdout / crash-safe JSONL), byte-invisibility of
sinks to pipeline outputs, and the recorded-timing autotuner deriving
tuned geometry knobs that reproduce byte-identical artifacts."""
import io
import json

import numpy as np
import pytest

from repro.runtime import autotune, telemetry


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with no sinks installed — telemetry is
    process-global state."""
    telemetry.shutdown()
    telemetry.set_identity("main")
    yield
    telemetry.shutdown()
    telemetry.set_identity("main")


# ---------------------------------------------------------------- schema
def test_span_and_counter_records_validate(tmp_path):
    mem = telemetry.MemorySink()
    telemetry.configure(mem, worker="w7")
    telemetry.counter("queue", "claim", uid="sig_0", lease_age_s=0.0)
    with telemetry.span("phase2", "chunk", row0=0) as t:
        t["rows"] = 8  # attrs discovered mid-span merge into the record
    assert len(mem.records) == 2
    for rec in mem.records:
        assert telemetry.validate(rec) == [], rec
        assert rec["worker"] == "w7"
    c, s = mem.records
    assert c["kind"] == "counter" and c["value"] == 1.0
    assert s["kind"] == "span" and s["dur_s"] >= 0
    assert s["attrs"] == {"row0": 0, "rows": 8}
    assert s["seq"] > c["seq"]  # per-process monotonic


def test_validate_rejects_malformed_records():
    good = {"v": 1, "kind": "counter", "stage": "queue", "name": "x",
            "t": 0.0, "value": 1.0, "worker": "w", "pid": 1, "seq": 1,
            "attrs": {}}
    assert telemetry.validate(good) == []
    assert telemetry.validate({**good, "stage": "warp"})  # unknown stage
    assert telemetry.validate({**good, "kind": "gauge"})
    assert telemetry.validate({**good, "v": 99})
    bad = dict(good)
    del bad["worker"]
    assert any("worker" in e for e in telemetry.validate(bad))
    span = {**good, "kind": "span"}
    span.pop("value")
    assert telemetry.validate(span)  # span without dur_s
    assert telemetry.validate({**span, "dur_s": -1.0})
    assert telemetry.validate({**good, "attrs": {"x": object()}})


def test_disabled_telemetry_is_a_noop():
    assert not telemetry.enabled()
    telemetry.counter("queue", "claim")  # must not raise
    with telemetry.span("sig", "chunk") as t:
        t["rows"] = 4  # the yielded dict is a harmless scratch pad
    telemetry.flush()


# ----------------------------------------------------------------- sinks
def test_stdout_sink_greppable_lines():
    buf = io.StringIO()
    telemetry.configure(telemetry.StdoutSink(file=buf))
    telemetry.counter("fleet", "run_config", 3.0, workers=3)
    line = buf.getvalue().strip()
    assert line.startswith("telemetry,fleet,run_config,3.000000,")
    assert json.loads(line.split(",", 4)[4]) == {"workers": 3}


def test_jsonl_sink_crash_safe_and_reloads_previous_generation(tmp_path):
    p = tmp_path / "telemetry" / "w0.jsonl"
    sink = telemetry.JsonlSink(p, flush_every=1)
    telemetry.configure(sink, worker="w0")
    telemetry.counter("queue", "claim", uid="a")
    telemetry.counter("queue", "done", uid="a")
    # every generation on disk is complete, parseable JSONL
    recs = telemetry.read_jsonl(p)
    assert [r["name"] for r in recs] == ["claim", "done"]
    assert all(telemetry.validate(r) == [] for r in recs)

    # relaunch after SIGKILL: a new sink on the same path preloads the
    # previous generation, so the rewrite never loses records
    telemetry.configure()  # simulate death without another flush
    sink2 = telemetry.JsonlSink(p, flush_every=1)
    telemetry.configure(sink2, worker="w0")
    telemetry.counter("sig", "done", uid="b")
    names = [r["name"] for r in telemetry.read_jsonl(p)]
    assert names == ["claim", "done", "done"]

    # a torn trailing line (foreign non-atomic writer) is tolerated
    with open(p, "a") as f:
        f.write('{"v": 1, "kind": "cou')
    assert len(telemetry.read_jsonl(p)) == 3


def test_jsonl_sink_batches_flushes(tmp_path):
    p = tmp_path / "w.jsonl"
    telemetry.configure(telemetry.JsonlSink(p, flush_every=100))
    telemetry.counter("queue", "claim")
    assert telemetry.read_jsonl(p) == []  # buffered, not yet durable
    telemetry.flush()
    assert len(telemetry.read_jsonl(p)) == 1


def test_configure_from_env(tmp_path, monkeypatch, capsys):
    default = tmp_path / "telemetry" / "main.jsonl"
    monkeypatch.setenv("EDM_TELEMETRY", "off")
    telemetry.configure_from_env(default_path=default, worker="m")
    assert not telemetry.enabled()

    monkeypatch.setenv("EDM_TELEMETRY", f"jsonl:{tmp_path / 'x.jsonl'}")
    telemetry.configure_from_env(default_path=default, worker="m")
    telemetry.counter("fleet", "run_config")
    telemetry.flush()
    assert len(telemetry.read_jsonl(tmp_path / "x.jsonl")) == 1

    monkeypatch.delenv("EDM_TELEMETRY")
    telemetry.configure_from_env(default_path=default, worker="m")
    telemetry.counter("fleet", "run_config")
    telemetry.flush()
    assert len(telemetry.read_jsonl(default)) == 1

    telemetry.configure_from_env(default_path=None, worker="m")
    assert not telemetry.enabled()  # no default, no env -> disabled


# ------------------------------------------- byte-invisibility + autotune
def _small_run(out_dir, cfg=None, telemetry_on=False):
    from repro.core.pipeline import run_causal_inference
    from repro.core.types import EDMConfig
    from repro.data.synthetic import dummy_brain
    from repro.inference import SignificanceConfig, run_significance

    ts = dummy_brain(10, 200, seed=3)
    cfg = cfg or EDMConfig(E_max=3, lib_block=5, target_tile=4)
    sig = SignificanceConfig(lib_sizes=(30, 60), n_surrogates=4, seed=0)
    if telemetry_on:
        telemetry.configure(
            telemetry.JsonlSink(
                telemetry.worker_jsonl(out_dir, "main"), flush_every=1),
            worker="main",
        )
    res = run_causal_inference(ts, cfg, out_dir=str(out_dir))
    run_significance(ts, np.asarray(res.optE), np.asarray(res.rho),
                     cfg, sig, out_dir=str(out_dir))
    telemetry.shutdown()
    return ts, cfg, sig


def test_sinks_byte_invisible_and_all_stages_recorded(tmp_path):
    """The tentpole invariant: a JSONL-sink run produces byte-identical
    artifacts to a sink-disabled run, and its records are schema-valid
    and cover every pipeline stage the run walked."""
    _small_run(tmp_path / "off", telemetry_on=False)
    _small_run(tmp_path / "on", telemetry_on=True)
    for art in ("causal_map", "rho_conv", "rho_trend", "pvals", "edges"):
        a = np.load(tmp_path / "on" / art / "data.npy")
        b = np.load(tmp_path / "off" / art / "data.npy")
        assert a.tobytes() == b.tobytes(), f"{art} differs with sink on"
    # a sink-disabled run writes no telemetry at all
    assert not (tmp_path / "off" / "telemetry").exists()

    recs = [r for _, r in telemetry.iter_store_records(tmp_path / "on")]
    assert recs, "sink-enabled run recorded nothing"
    for r in recs:
        assert telemetry.validate(r) == [], r
    span_stages = {r["stage"] for r in recs if r["kind"] == "span"}
    for stage in ("phase1", "phase2", "assemble", "sig", "finalize"):
        assert stage in span_stages, f"no span recorded for {stage}"
    # store + stream layers report through the same spine
    names = {(r["stage"], r["name"]) for r in recs}
    assert ("store", "manifest_commit") in names or any(
        n in ("write_tile", "write_block") for _, n in names
    )


def test_autotune_recommend_write_load_apply_roundtrip(tmp_path):
    """replay -> recommend from recorded timings; tuned.json roundtrip;
    apply_to_cfg stamps the shapes; a rerun under the tuned shapes is
    byte-identical (the invariant that makes autotuning safe)."""
    import dataclasses

    out = tmp_path / "run"
    _, cfg, _ = _small_run(out, telemetry_on=True)

    tuned = autotune.recommend(out)
    assert tuned is not None and tuned["v"] == autotune.TUNED_VERSION
    rec = tuned["recommend"]
    assert rec.get("chunk_rows", 0) >= autotune.CHUNK_ROWS_MIN
    ev = tuned["evidence"]
    assert ev["chunks"] > 0 and ev["chunk_rows_done"] > 0

    p = autotune.write_tuned(out, tuned)
    assert p.name == "tuned.json" and p.parent == out
    assert autotune.load_tuned(out) == tuned
    assert autotune.load_tuned(tmp_path) is None  # absent store
    p.write_text("{broken")
    assert autotune.load_tuned(out) is None  # torn file never applies
    autotune.write_tuned(out, tuned)

    cfg2 = autotune.apply_to_cfg(cfg, tuned, n_devices=1)
    if rec.get("chunk_rows"):
        assert cfg2.lib_block == rec["chunk_rows"]
    if rec.get("target_tile"):
        assert cfg2.target_tile == rec["target_tile"]
    if rec.get("knn_tile_c"):
        assert cfg2.knn_tile_c == rec["knn_tile_c"]

    # geometry is bit-invisible: rerun under the tuned shapes == original
    clamped = dataclasses.replace(
        cfg2, lib_block=min(cfg2.lib_block, 10),
        target_tile=min(cfg2.target_tile, 10),
    )
    _small_run(tmp_path / "tuned", cfg=clamped, telemetry_on=False)
    for art in ("causal_map", "rho_conv", "pvals"):
        a = np.load(tmp_path / "tuned" / art / "data.npy")
        b = np.load(out / art / "data.npy")
        assert a.tobytes() == b.tobytes(), f"{art} differs under tuning"


def test_autotune_no_telemetry_returns_none(tmp_path):
    assert autotune.recommend(tmp_path) is None
    with pytest.raises(SystemExit, match="no chunk telemetry"):
        autotune.main([str(tmp_path)])


def test_autotune_decision_rules(tmp_path):
    """Synthetic telemetry exercising each band of the decision rules
    (no pipeline run needed — the tuner replays records, not stores)."""
    def store_with(records):
        import shutil
        d = tmp_path / "synth"
        if d.exists():
            shutil.rmtree(d)
        p = telemetry.worker_jsonl(d, "w0")
        p.parent.mkdir(parents=True)
        base = {"v": 1, "t": 0.0, "worker": "w0", "pid": 1, "attrs": {}}
        p.write_text("".join(
            json.dumps({**base, "seq": i, **r}) + "\n"
            for i, r in enumerate(records)
        ))
        return d

    chunk = {"kind": "span", "stage": "sig", "name": "chunk",
             "attrs": {"rows": 8, "chunk_rows": 8, "tile": 32,
                       "n_tiles": 4}}
    write = {"kind": "span", "stage": "store", "name": "write_tile"}
    cal = {"kind": "counter", "stage": "engine", "name": "knn_tile",
           "value": 256.0, "attrs": {"Lc": 400}}
    nrec = {"kind": "span", "stage": "assemble", "name": "causal_map",
            "dur_s": 0.1, "attrs": {"N": 512}}

    # 2 rows/s -> chunk_rows grows toward TARGET_CHUNK_S of compute
    d = store_with([{**chunk, "dur_s": 4.0}, nrec, cal])
    t = autotune.recommend(d)["recommend"]
    assert t["chunk_rows"] == 40  # 2 rows/s * 20 s, rounded to 8s
    assert t["knn_tile_c"] == 256

    # write-dominated tiles (ratio > HI) -> target_tile doubles
    d = store_with([{**chunk, "dur_s": 4.0},
                    {**write, "dur_s": 0.5}, nrec])
    assert autotune.recommend(d)["recommend"]["target_tile"] == 64

    # negligible write cost with several tiles/chunk -> tile halves
    d = store_with([{**chunk, "dur_s": 40.0},
                    {**write, "dur_s": 0.0001}, nrec])
    assert autotune.recommend(d)["recommend"]["target_tile"] == 16

    # recommendations never exceed the run's N
    small = {**chunk, "attrs": {**chunk["attrs"]}}
    nsmall = {**nrec, "attrs": {"N": 24}}
    d = store_with([{**small, "dur_s": 8.0}, nsmall])
    assert autotune.recommend(d)["recommend"]["chunk_rows"] <= 24


def test_compile_cache_probe(tmp_path, monkeypatch):
    """compile_cache_entries counts the directory the placement helper
    chose: none configured -> None; no env var -> the one fixed
    in-checkout directory; env var set -> that directory, and the helper
    sets nothing itself."""
    import pathlib

    import jax

    from repro.runtime import platform

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert telemetry.compile_cache_entries() is None
        repo = pathlib.Path(__file__).resolve().parents[1]
        assert platform.enable_compile_cache() == repo / ".jax_cache"
        assert platform.compile_cache_dir() == repo / ".jax_cache"
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    cache = tmp_path / "cache"
    cache.mkdir()
    (cache / "a").write_text("")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    assert platform.enable_compile_cache() == cache
    assert jax.config.jax_compilation_cache_dir == saved[keys[0]]
    assert telemetry.compile_cache_entries() == 1
    mem = telemetry.MemorySink()
    telemetry.configure(mem)
    (cache / "b").write_text("")
    telemetry.emit_compile_cache("phase1", before=1)
    (rec,) = mem.records
    assert rec["name"] == "compile_cache" and rec["value"] == 1.0
    assert rec["attrs"] == {"entries": 2, "new": 1}


def test_compile_cache_second_run_compiles_less(tmp_path):
    """Two processes placing the cache with enable_compile_cache under the
    same JAX_COMPILATION_CACHE_DIR: the first writes entries there, the
    second finds them and compiles nothing fresh."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.runtime import platform, telemetry
        platform.enable_compile_cache()
        before = telemetry.compile_cache_entries()
        jax.jit(lambda x: jnp.sin(x) * 3 + x)(jnp.ones(17)).block_until_ready()
        print("NEW", telemetry.compile_cache_entries() - before)
    """)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    new = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        new.append(int(r.stdout.split("NEW")[-1]))
    assert new[0] > 0
    assert new[1] < new[0]
    assert any((tmp_path / "jc").iterdir())


# ------------------------------------------------------ phase-2 spans
def _phase2_unit(tmp_path, sink, target_tile=0):
    """A tiny bucketed ``run_phase2_chunks`` at stream_depth 2 through a
    TileWriter, its last chunk partial; returns (plan, written bytes)."""
    import jax.numpy as jnp

    from repro.core import EDMConfig, ccm
    from repro.core.pipeline import default_mesh, run_phase2_chunks
    from repro.data.store import TileWriter
    from repro.data.synthetic import dummy_brain

    N, rows = 4096, 11
    ts = dummy_brain(N, 120, seed=5)
    cfg = EDMConfig(E_max=4, lib_block=2, stream_depth=2,
                    target_tile=target_tile)
    fut = np.asarray(ccm.all_futures(jnp.asarray(ts), cfg))
    optE = (np.arange(N) % 4 + 1).astype(np.int32)
    mesh = default_mesh()
    chunk = mesh.size * cfg.lib_block
    plan = [(r, min(chunk, rows - r)) for r in range(0, rows, chunk)]
    if sink is not None:
        telemetry.configure(sink)
    writer = TileWriter(tmp_path / "rho", N)
    run_phase2_chunks(ts, fut, optE, cfg, mesh, plan, writer=writer)
    telemetry.configure()
    written = b"".join(f.read_bytes()
                       for f in sorted(writer.dir.glob("*_*.npy")))
    return plan, written


def _interval(rec):
    return rec["mono"] - rec["dur_s"], rec["mono"]


def _within(inner, outer, eps=1e-4):
    a, b = _interval(inner)
    c, d = _interval(outer)
    return c - eps <= a and b <= d + eps


def test_phase2_drain_splits_into_wait_copy_unsort_and_store(tmp_path):
    from repro.runtime.trace import _tag_row0

    mem = telemetry.MemorySink()
    plan, _ = _phase2_unit(tmp_path, mem)
    spans = [r for r in mem.records if r["kind"] == "span"]
    for r in spans:
        assert telemetry.validate(r) == [], r
    drains = [r for r in spans if r["name"] == "drain"]
    assert [_tag_row0(d["attrs"]) for d in drains] == [r for r, _ in plan]
    children = ("device_wait", "d2h_copy", "unsort", "write_block",
                "manifest_commit")
    for d in drains:
        row0 = _tag_row0(d["attrs"])
        inside = [r for r in spans if r["name"] in children
                  and _within(r, d)]
        by_name = {}
        for r in inside:
            by_name.setdefault(r["name"], []).append(r)
        assert sorted(by_name) == sorted(children), by_name
        assert all(len(v) == 1 for v in by_name.values()), by_name
        (wait,), (copy,) = by_name["device_wait"], by_name["d2h_copy"]
        for r in (wait, copy, by_name["unsort"][0]):
            assert r["stage"] == "phase2" and _tag_row0(r["attrs"]) == row0
        assert _interval(wait)[1] <= _interval(copy)[0] + 1e-4
        assert copy["attrs"]["bytes"] == d["attrs"]["bytes"] > 0
        # gather_s is the wait and the copy, as before the split
        assert d["attrs"]["gather_s"] == pytest.approx(
            wait["dur_s"] + copy["dur_s"], abs=1e-4)
        covered = sum(r["dur_s"] for r in inside)
        assert covered >= 0.95 * d["dur_s"], (covered, d["dur_s"])


def test_phase2_span_totals_leave_the_split_out(tmp_path):
    """The per-stage span totals (trace, history; `edm_fleet status` sums
    the same way) hold the spans the phase-2 path emitted before the
    drain, dispatch and unit were split out, and no more."""
    from repro.runtime import history, trace

    out = tmp_path / "run"
    _phase2_unit(tmp_path,
                 telemetry.JsonlSink(telemetry.worker_jsonl(out, "w0")))
    spans = [r for _, r in telemetry.iter_store_records(out)
             if r["kind"] == "span"]
    names = {r["name"] for r in spans}
    assert telemetry.NESTED_SPANS <= names
    before_split = {"chunk", "device_put", "drain", "write_block",
                    "manifest_commit"}
    assert names - telemetry.NESTED_SPANS == before_split
    want: dict = {}
    for r in spans:
        if r["name"] in before_split:
            want[r["stage"]] = want.get(r["stage"], 0.0) + r["dur_s"]
    nested = sum(r["dur_s"] for r in spans
                 if r["name"] in telemetry.NESTED_SPANS)
    assert nested > 0.5 * want["phase2"]

    rec = history.build_record(out)
    assert rec["total_span_s"] == pytest.approx(sum(want.values()),
                                                abs=1e-5)
    for stage, s in want.items():
        assert rec["stages"][stage]["span_s"] == pytest.approx(s, abs=1e-5)
    tr = trace.assemble_trace(out)
    assert tr["span_totals"] == pytest.approx(want, abs=1e-6)


@pytest.mark.parametrize("target_tile", [0, 1500])
def test_phase2_dispatch_nests_in_chunk_and_one_unit_per_call(
        tmp_path, target_tile):
    mem = telemetry.MemorySink()
    plan, _ = _phase2_unit(tmp_path, mem, target_tile)
    spans = [r for r in mem.records if r["kind"] == "span"
             and r["stage"] == "phase2"]
    chunks = [r for r in spans if r["name"] == "chunk"]
    dispatches = [r for r in spans if r["name"] == "dispatch"]
    # untiled: one call a chunk; tiled: the tables and each column tile
    per_chunk = 1 + (-(-4096 // target_tile) if target_tile else 0)
    assert len(chunks) == len(plan)
    assert len(dispatches) == per_chunk * len(plan)
    for i, c in enumerate(chunks):
        for d in dispatches[i * per_chunk:(i + 1) * per_chunk]:
            assert d["attrs"]["row0"] == c["attrs"]["row0"]
            assert _within(d, c)
    (unit,) = [r for r in spans if r["name"] == "unit"]
    a = unit["attrs"]
    assert a["chunks"] == len(plan)
    assert a["rows"] == sum(n for _, n in plan)
    assert a["prep_s"] > 0 and a["first_dispatch_s"] > 0
    first = sum(d["dur_s"] for d in dispatches[:per_chunk])
    assert a["first_dispatch_s"] == pytest.approx(first, abs=1e-3)
    assert a["futures_bytes"] > 0
    assert all(_within(r, unit) for r in spans)


@pytest.mark.parametrize("engine,sublanes", [("pallas-interpret", 2),
                                              ("reference", None)])
def test_phase2_unit_carries_lookup_sublanes(engine, sublanes):
    """Where the engine runs the lookup kernel, the unit span says how
    many sublanes of targets each neighbour step adds, as the kernel's
    wrapper picks them: 300 targets, so a 256-target (2, 128) tile."""
    import jax.numpy as jnp

    from repro.core import EDMConfig, ccm
    from repro.core.pipeline import default_mesh, run_phase2_chunks
    from repro.data.synthetic import dummy_brain

    N = 300
    ts = dummy_brain(N, 60, seed=3)
    cfg = EDMConfig(E_max=3, lib_block=2, engine=engine)
    fut = np.asarray(ccm.all_futures(jnp.asarray(ts), cfg))
    optE = (np.arange(N) % 3 + 1).astype(np.int32)
    mesh = default_mesh()
    rho = np.zeros((N, N), np.float32)
    mem = telemetry.MemorySink()
    telemetry.configure(mem)
    try:
        run_phase2_chunks(ts, fut, optE, cfg, mesh,
                          [(0, mesh.size * cfg.lib_block)], rho=rho)
    finally:
        telemetry.configure()
    (unit,) = [r for r in mem.records
               if r["kind"] == "span" and r["name"] == "unit"]
    assert unit["attrs"].get("lookup_sublanes") == sublanes
    assert telemetry.validate(unit) == []


def test_phase2_without_sink_records_nothing_and_writes_the_same(
        tmp_path, monkeypatch):
    import jax

    entered = []
    real = jax.profiler.TraceAnnotation
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: entered.append(name) or real(name))
    seq = telemetry._seq
    _, off = _phase2_unit(tmp_path / "off", None)
    assert telemetry._seq == seq and entered == []
    mem = telemetry.MemorySink()
    _, on = _phase2_unit(tmp_path / "on", mem)
    assert off == on
    assert len(entered) == len(mem.records) - sum(
        r["kind"] == "counter" for r in mem.records)


# --------------------------------------------------------- sig spans
def _sig_unit(tmp_path, sink, budget=None, monkeypatch=None):
    """A tiny significance unit (both stages) through ``run_sig_chunks``
    and the three TileWriters, its last chunk partial; returns (plan,
    written bytes, runner)."""
    from repro.core import EDMConfig
    from repro.core.pipeline import default_mesh
    from repro.data.store import TileWriter
    from repro.data.synthetic import dummy_brain
    from repro.inference import pipeline as ip
    from repro.inference import SignificanceConfig

    N, rows = 40, 7
    ts = dummy_brain(N, 120, seed=8)
    cfg = EDMConfig(E_max=4, lib_block=2, stream_depth=2)
    sig = SignificanceConfig(lib_sizes=(30, 60, 116), n_surrogates=5,
                             seed=4)
    optE = (np.arange(N) % 3 + 2).astype(np.int32)
    rho = np.random.default_rng(0).uniform(-0.2, 0.6, (N, N)).astype(
        np.float32)
    if budget is not None:
        monkeypatch.setattr(ip, "_device_budget", lambda mesh: budget)
    mesh = default_mesh()
    if sink is not None:
        telemetry.configure(sink)
    runner = ip.SignificanceChunkRunner(ts, optE, cfg, sig, mesh)
    writers = []
    for name in ("rho_conv", "rho_trend", "pvals"):
        w = TileWriter(tmp_path / name, N, stage="sig")
        w.ensure_col_order(runner.order)
        writers.append(w)
    plan = [(r, min(runner.chunk, rows - r))
            for r in range(0, rows, runner.chunk)]
    ip.run_sig_chunks(runner, plan, rho, *writers)
    telemetry.configure()
    written = b"".join(f.read_bytes() for w in writers
                       for f in sorted(w.dir.glob("tile_*.npy")))
    return plan, written, runner


def test_sig_unit_and_dispatch_nest_under_their_parents(tmp_path,
                                                        monkeypatch):
    from repro.core import EDMConfig
    from repro.inference import pipeline as ip

    mem = telemetry.MemorySink()
    # a budget that leaves room for 16 of the 40 targets: three tiles
    budget = ip.sig_tile_bytes(
        16, m=5, L=120, Lp=116, rows=2, k=5, n_buckets=3, n_sizes=3,
        cfg=EDMConfig(E_max=4, lib_block=2, stream_depth=2))
    plan, _, runner = _sig_unit(tmp_path, mem, budget, monkeypatch)
    assert runner.T == 16 and len(runner.tile_plans) == 3
    spans = [r for r in mem.records if r["kind"] == "span"
             and r["stage"] == "sig"]
    for r in spans:
        assert telemetry.validate(r) == [], r
    (unit,) = [r for r in spans if r["name"] == "unit"]
    a = unit["attrs"]
    assert a["null_tile"] == 16
    assert a["surrogate_bytes"] == 16 * 5 * 116 * 4
    assert a["chunks"] == len(plan) and a["rows"] == 7
    assert a["prep_s"] > 0 and a["first_dispatch_s"] > 0
    # everything but the writers' closing manifest commits
    inner = [r for r in spans if r is not unit]
    assert inner[-1]["name"] == "manifest_commit"
    assert all(_within(r, unit) for r in inner[:-3])
    chunks = [r for r in spans if r["name"] == "chunk"]
    dispatches = [r for r in spans if r["name"] == "dispatch"]
    # per chunk: both tables, then per tile the conv call, the
    # surrogates and the null call
    per_chunk = ["conv_tables", "full_tables"] + [
        "conv_tile", "surrogates", "null_tile"] * 3
    assert len(chunks) == len(plan)
    assert [d["attrs"]["what"] for d in dispatches] == per_chunk * len(plan)
    for i, c in enumerate(chunks):
        for d in dispatches[i * len(per_chunk):(i + 1) * len(per_chunk)]:
            assert d["attrs"]["row0"] == c["attrs"]["row0"]
            assert _within(d, c)
    first = sum(d["dur_s"] for d in dispatches[:len(per_chunk)])
    assert a["first_dispatch_s"] == pytest.approx(first, abs=1e-3)


def test_sig_runner_reports_its_prep_once():
    from repro.core import EDMConfig
    from repro.data.synthetic import dummy_brain
    from repro.inference import SignificanceChunkRunner, SignificanceConfig

    ts = dummy_brain(12, 80, seed=1)
    cfg = EDMConfig(E_max=3, lib_block=4)
    runner = SignificanceChunkRunner(
        ts, np.full(12, 2, np.int32), cfg,
        SignificanceConfig(lib_sizes=(), n_surrogates=3, seed=0))
    mem = telemetry.MemorySink()
    telemetry.configure(mem)
    rho = np.zeros((12, 12), np.float32)
    for _ in range(2):
        runner.run([(0, 4)], rho, lambda tag, block: None)
    telemetry.configure()
    units = [r["attrs"] for r in mem.records if r["name"] == "unit"]
    assert units[0]["prep_s"] > 0 and units[1]["prep_s"] == 0.0
    assert [u["null_tile"] for u in units] == [12, 12]  # no budget on CPU


def test_sig_without_sink_records_nothing_and_writes_the_same(tmp_path):
    seq = telemetry._seq
    _, off, _ = _sig_unit(tmp_path / "off", None)
    assert telemetry._seq == seq
    _, on, _ = _sig_unit(tmp_path / "on", telemetry.MemorySink())
    assert off == on and len(off) > 0


def test_span_lands_in_the_profiler_host_plane_on_the_trace_clock(
        tmp_path):
    """A span under a sink is also an event of the profiler's host plane;
    its start there, measured from the benchmark's anchor annotation,
    agrees with where the sink's record puts it."""
    import importlib.util
    import pathlib
    import time

    import jax

    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
            / "chip" / "trace_reduce.py")
    spec = importlib.util.spec_from_file_location("trace_reduce_t", path)
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)

    mem = telemetry.MemorySink()
    telemetry.configure(mem)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        anchor = time.monotonic()
        with jax.profiler.TraceAnnotation(tr.ANCHOR):
            pass
        time.sleep(0.02)
        with telemetry.span("phase2", "device_wait", row0=8):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    prof = tr.load(tr.find_xplane(tmp_path / "trace"))
    events = [ev for p in prof.planes if p.name.startswith("/host")
              for ln in p.lines for ev in ln.events
              if ev.name == "phase2/device_wait"]
    assert len(events) == 1
    (rec,) = mem.records
    got = (events[0].start_ns - tr.anchor_ns(prof)) * 1e-9
    want = rec["mono"] - rec["dur_s"] - anchor
    assert got == pytest.approx(want, abs=1e-3)
    assert got > 0.015


def test_telemetry_never_imports_jax():
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import sys
        from repro.runtime import telemetry
        mem = telemetry.MemorySink()
        telemetry.configure(mem)
        with telemetry.span("fleet", "stage"):
            pass
        assert len(mem.records) == 1
        print("JAX", "jax" in sys.modules)
    """)
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split()[-2:] == ["JAX", "False"]
