"""Execution-platform tiers + multi-host mesh bring-up (DESIGN.md SS14).

One place answers "what chip, what flags, what engine, how many hosts"
BEFORE the first jax backend touch:

  * :data:`TIERS` — named platform tiers (``cpu`` / ``gpu`` / ``tpu``):
    jax platform name, x64 default, the tier's tuned XLA flags, and the
    default execution engine the registry should select
    (``repro.engine``).  The ``gpu`` tier carries the
    latency-hiding/async-collective flag set that keeps the SS14 shard
    merge (ppermute butterfly) overlapped with the per-shard streaming
    builds.
  * :func:`apply_platform` — applies a tier (env XLA_FLAGS + jax.config)
    idempotently; ``edm_run --platform`` and fleet workers call it first
    thing.
  * :func:`init_distributed` — env-driven ``jax.distributed.initialize``
    (EDM_COORDINATOR / EDM_NUM_PROCESSES / EDM_PROCESS_ID) so one
    logical mesh spans processes and hosts; every process then sees the
    GLOBAL device list and ``pipeline.default_mesh()`` becomes the
    paper's flat cross-host worker grid.
  * :func:`spoof_cpu_devices` — the CI/dev lever: N virtual CPU devices
    in one process (XLA host-platform device-count spoof) so multi-shard
    collectives run anywhere.
  * :func:`probe_devices` — platform and local device count as a child
    process sees them, so a fleet driver can size its fleet while it
    stays off the backend itself (one process per chip).
  * :func:`enable_compile_cache` — THE persistent-compilation-cache
    placement every entry point calls before its first compile.

Everything here is wall-clock/topology only — byte-invisible to outputs
(the bit-identity contracts of SS8/SS14 hold on every tier).
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import warnings
from dataclasses import dataclass, field

_X64_FLAG = "jax_enable_x64"

#: Env var contract for multi-host bring-up (mirrored in
#: docs/OPERATIONS.md; fleet workers read the same three).
ENV_COORDINATOR = "EDM_COORDINATOR"      # host:port of process 0
ENV_NUM_PROCESSES = "EDM_NUM_PROCESSES"  # world size
ENV_PROCESS_ID = "EDM_PROCESS_ID"        # this process's rank
ENV_LOCAL_DEVICE_IDS = "EDM_LOCAL_DEVICE_IDS"  # optional, e.g. "0,1"


@dataclass(frozen=True)
class Tier:
    """One named execution tier: everything that must be decided before
    the jax backend initializes."""

    name: str
    platform: str          # jax_platforms
    engine: str            # default repro.engine registry key
    x64: bool = False
    xla_flags: tuple[str, ...] = field(default_factory=tuple)
    notes: str = ""


TIERS: dict[str, Tier] = {
    t.name: t
    for t in (
        Tier(
            name="cpu",
            platform="cpu",
            engine="reference",
            notes="portable default; jnp reference engine, no extra flags",
        ),
        Tier(
            name="gpu",
            platform="gpu",
            engine="pallas-compiled",
            xla_flags=(
                # Tuned GPU set: fuse the softmax-shaped reductions and
                # small GEMMs into Triton, run collectives (the SS14
                # shard-merge ppermutes) async on the highest-priority
                # stream, and let the latency-hiding scheduler overlap
                # them with the streaming kNN builds.
                "--xla_gpu_enable_triton_softmax_fusion=true",
                "--xla_gpu_triton_gemm_any=True",
                "--xla_gpu_enable_async_collectives=true",
                "--xla_gpu_enable_latency_hiding_scheduler=true",
                "--xla_gpu_enable_highest_priority_async_stream=true",
            ),
            notes="tuned CUDA tier: Triton fusions + async collectives "
            "overlapping the SS14 shard merge",
        ),
        Tier(
            name="tpu",
            platform="tpu",
            engine="pallas-compiled",
            notes="native Pallas kernels; collectives on the ICI mesh",
        ),
    )
}


def available_tiers() -> tuple[str, ...]:
    return tuple(sorted(TIERS))


def default_engine(tier: str) -> str:
    """The engine registry key a tier selects (``edm_run --platform``
    uses this whenever --engine is not given explicitly)."""
    return TIERS[tier].engine


def _backend_initialized() -> bool:
    """True once the jax runtime has instantiated a backend — after which
    XLA_FLAGS / platform changes are silently ignored by jax."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _merge_xla_flags(flags: tuple[str, ...]) -> str:
    """Append tier flags to $XLA_FLAGS, dropping duplicates (by flag
    name, tier value wins) and preserving caller-provided extras."""
    have = os.environ.get("XLA_FLAGS", "").split()
    names = {f.split("=")[0] for f in flags}
    kept = [f for f in have if f.split("=")[0] not in names]
    merged = " ".join(kept + list(flags))
    os.environ["XLA_FLAGS"] = merged
    return merged


_APPLIED: dict | None = None


def apply_platform(
    tier: str, *, x64: bool | None = None, cpu_devices: int | None = None
) -> dict:
    """Apply a :data:`TIERS` entry: XLA_FLAGS env + ``jax_platforms``
    (the one platform the backend may initialize) + x64 mode.  MUST run
    before the first jax backend touch (device query, first op); a later
    call warns and changes nothing at the runtime level.  Returns {tier,
    platform, engine, x64, xla_flags} — the record edm_run stamps into
    telemetry.

    ``cpu_devices`` (cpu tier only) spoofs N host devices for local
    multi-shard runs — the same knob CI's scale-smoke uses.
    """
    global _APPLIED
    if tier not in TIERS:
        raise KeyError(f"unknown platform tier {tier!r}; "
                       f"available: {available_tiers()}")
    t = TIERS[tier]
    if _backend_initialized():
        warnings.warn(
            f"apply_platform({tier!r}) after the jax backend initialized: "
            "XLA flags / platform name will NOT take effect this process",
            RuntimeWarning,
            stacklevel=2,
        )
    if cpu_devices is not None:
        if t.platform != "cpu":
            raise ValueError("cpu_devices spoof only applies to the cpu tier")
        spoof_cpu_devices(cpu_devices)
    flags = _merge_xla_flags(t.xla_flags) if t.xla_flags \
        else os.environ.get("XLA_FLAGS", "")
    import jax

    jax.config.update("jax_platforms", t.platform)
    use_x64 = t.x64 if x64 is None else x64
    jax.config.update(_X64_FLAG, use_x64)
    _APPLIED = {
        "tier": t.name,
        "platform": t.platform,
        "engine": t.engine,
        "x64": use_x64,
        "xla_flags": flags,
    }
    return dict(_APPLIED)


def current() -> dict | None:
    """The record of the last :func:`apply_platform`, or None."""
    return dict(_APPLIED) if _APPLIED is not None else None


def spoof_cpu_devices(n: int) -> None:
    """Present ``n`` virtual CPU devices in this process (must run before
    backend init).  Dev/CI only: lets shard_map collectives — the SS14
    merge butterfly included — execute real multi-device code paths on a
    laptop or a CI runner."""
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    _merge_xla_flags((f"--xla_force_host_platform_device_count={n}",))


def probe_devices(tier: str | None = None, timeout: float = 300.0):
    """(platform, local device count) as a fresh child process sees them.

    A process that initializes the TPU backend holds every chip it sees
    until it exits, so a driver that spawns workers must not touch the
    backend itself: it asks a short-lived child instead, which exits —
    releasing the chips — before any worker starts.  ``tier`` applies a
    :data:`TIERS` platform to the child, as fleet workers do."""
    env = dict(os.environ)
    if tier is not None:
        env["JAX_PLATFORMS"] = TIERS[tier].platform
    code = ("import jax; "
            "print(jax.default_backend(), jax.local_device_count())")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"device probe failed: {r.stderr.strip()[-2000:]}")
    plat, n = r.stdout.split()[-2:]
    return plat, int(n)


# ------------------------------------------------- compile-cache placement
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: The one in-checkout cache directory used when CACHE_ENV is unset (in
#: .gitignore).  Fixed, because the path is part of what a later run
#: must find again: a directory built from --out, a temporary name, a
#: pid or the time never hits.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> pathlib.Path | None:
    """The persistent compilation cache directory in force: the
    environment's, else the one :func:`enable_compile_cache` placed, else
    None (no cache)."""
    d = os.environ.get(CACHE_ENV)
    if d:
        return pathlib.Path(d)
    import jax

    d = jax.config.jax_compilation_cache_dir
    return pathlib.Path(d) if d else None


def enable_compile_cache() -> pathlib.Path:
    """Place JAX's persistent compilation cache; call before the first
    compile.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads
    it and nothing else is set here.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`, caching every compile (no minimum compile
    time), so a second run in the same checkout compiles less."""
    d = os.environ.get(CACHE_ENV)
    if d:
        return pathlib.Path(d)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR


# ------------------------------------------------------- multi-host mesh
def distributed_spec_from_env(env=None) -> dict | None:
    """Read the EDM_* multi-host contract from ``env`` (default
    os.environ).  Returns {coordinator, num_processes, process_id
    [, local_device_ids]} or None when EDM_COORDINATOR is unset (the
    single-process default).  Partial settings raise — a worker joining
    a mesh with a guessed rank would deadlock the whole fleet."""
    env = os.environ if env is None else env
    coord = env.get(ENV_COORDINATOR)
    if not coord:
        return None
    missing = [v for v in (ENV_NUM_PROCESSES, ENV_PROCESS_ID)
               if not env.get(v)]
    if missing:
        raise ValueError(
            f"{ENV_COORDINATOR} is set but {missing} missing: a multi-host "
            "mesh needs coordinator, world size AND rank"
        )
    spec = {
        "coordinator": coord,
        "num_processes": int(env[ENV_NUM_PROCESSES]),
        "process_id": int(env[ENV_PROCESS_ID]),
    }
    if not 0 <= spec["process_id"] < spec["num_processes"]:
        raise ValueError(f"process_id {spec['process_id']} outside world "
                         f"size {spec['num_processes']}")
    ids = env.get(ENV_LOCAL_DEVICE_IDS)
    if ids:
        spec["local_device_ids"] = tuple(int(i) for i in ids.split(","))
    return spec


_DISTRIBUTED: dict | None = None


def init_distributed(spec: dict | None = None) -> dict | None:
    """Join (or form) the multi-host mesh via jax.distributed.

    ``spec`` defaults to :func:`distributed_spec_from_env`; None (no
    EDM_COORDINATOR) is the single-process no-op.  After a successful
    init every process sees the GLOBAL device list, so
    ``pipeline.default_mesh()`` — and with it the SS14 candidate-shard
    collective — spans hosts with no further code changes.  Idempotent:
    a second call with the same spec returns the first record; a
    CONFLICTING second call raises (one process, one mesh).
    """
    global _DISTRIBUTED
    spec = distributed_spec_from_env() if spec is None else dict(spec)
    if spec is None:
        return None
    if _DISTRIBUTED is not None:
        if _DISTRIBUTED == spec:
            return dict(_DISTRIBUTED)
        raise RuntimeError(
            f"jax.distributed already initialized with {_DISTRIBUTED}; "
            f"conflicting spec {spec}"
        )
    import jax

    jax.distributed.initialize(
        coordinator_address=spec["coordinator"],
        num_processes=spec["num_processes"],
        process_id=spec["process_id"],
        local_device_ids=spec.get("local_device_ids"),
    )
    _DISTRIBUTED = spec
    return dict(_DISTRIBUTED)


def distributed_info() -> dict | None:
    """The spec this process joined the mesh with, or None."""
    return dict(_DISTRIBUTED) if _DISTRIBUTED is not None else None


def describe() -> dict:
    """Telemetry snapshot: applied tier + mesh membership + live device
    census (device census only if the backend already initialized — this
    never forces initialization)."""
    out: dict = {"tier": current(), "distributed": distributed_info()}
    if _backend_initialized():
        import jax

        out["devices"] = {
            "platform": jax.devices()[0].platform,
            "global": len(jax.devices()),
            "local": len(jax.local_devices()),
            "process_index": jax.process_index(),
        }
    return out
