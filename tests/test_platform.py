"""runtime/platform.py (DESIGN.md SS14): execution tiers, XLA-flag
merging, and the env-driven multi-host mesh contract.

The pieces that must run BEFORE a jax backend exists (flag latching,
jax.distributed.initialize) are exercised in subprocesses; the pure
spec/parsing logic runs in-process.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.runtime import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str, extra_env: dict | None = None):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], capture_output=True,
        text=True, env=env, timeout=600, cwd=REPO,
    )


def test_tier_registry():
    """Every tier names a registered engine; the gpu tier carries the
    tuned async-collective/latency-hiding flag set SS14 relies on."""
    from repro import engine

    assert platform.available_tiers() == ("cpu", "gpu", "tpu")
    for name in platform.available_tiers():
        engine.get_engine(platform.default_engine(name))  # must resolve
    gpu = platform.TIERS["gpu"]
    assert any("latency_hiding" in f for f in gpu.xla_flags)
    assert any("async_collectives" in f for f in gpu.xla_flags)
    assert platform.default_engine("cpu") == "reference"
    assert platform.default_engine("gpu") == "pallas-compiled"
    with pytest.raises(KeyError, match="unknown platform tier"):
        platform.apply_platform("cuda")


def test_distributed_spec_from_env():
    """The EDM_* contract: unset -> None; complete -> parsed spec;
    partial or out-of-range -> a refusal (a guessed rank would deadlock
    the whole mesh)."""
    assert platform.distributed_spec_from_env({}) is None
    spec = platform.distributed_spec_from_env({
        "EDM_COORDINATOR": "head:1234",
        "EDM_NUM_PROCESSES": "8",
        "EDM_PROCESS_ID": "3",
        "EDM_LOCAL_DEVICE_IDS": "0,1",
    })
    assert spec == {
        "coordinator": "head:1234",
        "num_processes": 8,
        "process_id": 3,
        "local_device_ids": (0, 1),
    }
    with pytest.raises(ValueError, match="missing"):
        platform.distributed_spec_from_env({"EDM_COORDINATOR": "head:1"})
    with pytest.raises(ValueError, match="outside world size"):
        platform.distributed_spec_from_env({
            "EDM_COORDINATOR": "head:1",
            "EDM_NUM_PROCESSES": "2",
            "EDM_PROCESS_ID": "2",
        })


def test_apply_platform_after_backend_warns():
    """The suite's jax backend is already live, so a tier application
    here must WARN that flags cannot latch (rather than silently doing
    nothing)."""
    import jax

    jax.devices()  # ensure the backend is up
    with pytest.warns(RuntimeWarning, match="NOT take effect"):
        platform.apply_platform("cpu")


def test_apply_platform_latches_flags_and_devices():
    """Fresh process: cpu tier + device spoof land in XLA_FLAGS before
    backend init, the backend sees the spoofed device count, and
    describe() reports tier + census."""
    r = _run_sub("""
        from repro.runtime import platform
        rec = platform.apply_platform("cpu", cpu_devices=3)
        assert rec["tier"] == "cpu" and rec["engine"] == "reference"
        import os
        assert "--xla_force_host_platform_device_count=3" in \\
            os.environ["XLA_FLAGS"]
        import jax
        assert len(jax.devices()) == 3, jax.devices()
        d = platform.describe()
        assert d["tier"]["tier"] == "cpu"
        assert d["devices"]["global"] == 3
        print("latch OK")
    """)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "latch OK" in r.stdout


def test_init_distributed_single_process_mesh():
    """jax.distributed.initialize via the EDM_* env (1-process world on
    a local coordinator): the mesh forms, init is idempotent, a
    conflicting re-init refuses, and the SS14 sharded builder runs on
    the resulting global device view bit-identically."""
    r = _run_sub("""
        import socket
        s = socket.socket(); s.bind(("localhost", 0))
        port = s.getsockname()[1]; s.close()
        import os
        os.environ["EDM_COORDINATOR"] = f"localhost:{port}"
        os.environ["EDM_NUM_PROCESSES"] = "1"
        os.environ["EDM_PROCESS_ID"] = "0"
        from repro.runtime import platform
        platform.apply_platform("cpu", cpu_devices=2)
        info = platform.init_distributed()
        assert info["num_processes"] == 1 and info["process_id"] == 0
        assert platform.init_distributed() == info  # idempotent
        try:
            platform.init_distributed({"coordinator": "x:1",
                                       "num_processes": 2, "process_id": 1})
        except RuntimeError as e:
            assert "already initialized" in str(e)
        else:
            raise AssertionError("conflicting re-init must refuse")
        import jax, numpy as np, jax.numpy as jnp
        assert jax.process_count() == 1 and len(jax.devices()) == 2
        from repro.core import EDMConfig, knn
        from repro.core.pipeline import knn_tables_library_sharded
        rng = np.random.default_rng(7)
        Vq = jnp.asarray(rng.standard_normal((4, 90)), jnp.float32)
        cfg = EDMConfig(E_max=4)
        mi, md = knn_tables_library_sharded(Vq, Vq, 5, cfg, exclude_self=True)
        i0, d0 = knn.knn_tables_all_E_streaming(Vq, Vq, 5, True, tile_c=32)
        np.testing.assert_array_equal(np.asarray(mi), np.asarray(i0))
        np.testing.assert_array_equal(np.asarray(md), np.asarray(d0))
        print("distributed mesh OK")
    """)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "distributed mesh OK" in r.stdout


def test_fleet_spec_platform_opt_in(tmp_path):
    """fleet.json records the platform tier + distributed opt-in, and a
    worker process applies them from the spec before its first jax touch
    (apply_spec_platform in a fresh interpreter)."""
    import numpy as np

    from repro.core.types import EDMConfig
    from repro.data import store
    from repro.launch import edm_fleet

    ds = tmp_path / "dataset"
    store.save_dataset(ds, np.random.default_rng(0)
                       .standard_normal((8, 60)).astype(np.float32), {})
    out = tmp_path / "fleet"
    spec = edm_fleet.init_fleet(out, ds, EDMConfig(E_max=3),
                                platform="cpu", distributed=False)
    assert spec["platform"] == "cpu"
    assert spec["distributed"] is False
    raw = json.loads((out / "fleet.json").read_text())
    assert raw["platform"] == "cpu"
    r = _run_sub(f"""
        from repro.launch import edm_fleet
        from repro.runtime import platform
        edm_fleet.apply_spec_platform({str(out)!r})
        rec = platform.current()
        assert rec is not None and rec["tier"] == "cpu", rec
        print("spec opt-in OK")
    """)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "spec opt-in OK" in r.stdout


def test_fleet_driver_stays_off_the_backend(tmp_path):
    """edm_run --workers 1 --platform cpu: the driver learns the device
    count from a probe child and never initializes a jax backend, so on a
    TPU host every chip stays free for its worker."""
    out = tmp_path / "fleet"
    r = _run_sub(f"""
        import sys
        from jax._src import xla_bridge
        from repro.launch import edm_run
        sys.argv = ["edm_run", "--synthetic", "12x200", "--e-max", "3",
                    "--workers", "1", "--platform", "cpu",
                    "--out", {str(out)!r}]
        edm_run.main()
        print("driver backend initialized:",
              xla_bridge.backends_are_initialized())
    """)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "driver backend initialized: False" in r.stdout
    assert (out / "causal_map" / "data.npy").exists()


def test_tpu_host_refuses_more_workers_than_it_can_hold(tmp_path,
                                                        monkeypatch):
    """A TPU worker holds every chip it sees: a local fleet of two on a
    TPU host is refused up front instead of hanging on a held chip."""
    from repro.launch import edm_run

    monkeypatch.setattr(platform, "probe_devices", lambda tier=None: ("tpu", 1))
    monkeypatch.setattr(sys, "argv", [
        "edm_run", "--synthetic", "8x100", "--e-max", "3", "--workers", "2",
        "--no-telemetry", "--out", str(tmp_path / "out"),
    ])
    with pytest.raises(SystemExit, match="--workers 2 on a TPU host"):
        edm_run.main()
    assert not (tmp_path / "out" / "fleet.json").exists()


def test_probe_devices_reports_the_child_view():
    plat, n = platform.probe_devices("cpu")
    assert plat == "cpu" and n >= 1
