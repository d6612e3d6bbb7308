import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first backend init, and the dry-run needs 512 placeholder CPU
# devices to build the production meshes (16x16 and 2x16x16).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct stand-ins (no allocation) and record memory / cost /
collective-roofline analysis.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --cell train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod]   # every applicable cell
Results are appended to benchmarks/results/dryrun/<name>.json.
"""
import argparse
import dataclasses
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    ARCHS,
    cell_applicable,
    get_config,
    input_specs,
    shape_cell,
)
from repro.configs.base import SHAPE_CELLS, TrainConfig
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    TrainState,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)
from repro.models import transformer as T
from repro.sharding import policy as POL

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# Giant MoE archs train with Adafactor (factored moments) — DESIGN.md SS6.
_ADAFACTOR_ARCHS = {"dbrx-132b", "grok-1-314b"}


def optimized_cfg(cfg, cell):
    """Beyond-paper optimized configuration (SSPerf): chunked flash-in-XLA
    attention (with per-chunk remat), sequence-parallel attention for
    prefill of indivisible-head archs, last-position-only serving prefill,
    tighter SSD chunks, bf16 Adam moments."""
    kw = {}
    if cfg.n_heads > 0:
        kw["attn_impl"] = "chunked"
        kw["attn_chunk"] = 1024
        # seq-parallel attention: a prefill win for archs whose heads don't
        # divide TP-16; in training its backward all-gathers outweigh the
        # savings (measured: qwen2-1.5b train 0.3x) — prefill only.
        if cfg.n_heads % 16 != 0 and cell.kind == "prefill":
            kw["attn_seq_shard"] = True
    if cfg.ssm_state > 0 and cell.kind == "train":
        kw["ssm_chunk"] = 64  # halves the SSD decay-matrix footprint
    if cell.kind == "prefill":
        kw["prefill_last_only"] = True
    return dataclasses.replace(cfg, **kw)


def optimized_policy_kw(cfg, cell) -> dict:
    """Sub-1B archs at train_4k: replicate weights, use the model axis as
    extra batch parallelism (TP only replicates their attention compute).
    Serving cells keep TP: their global batch (32/128) does not divide the
    256-way grid — dp_only would replicate the whole batch per device
    (measured: whisper prefill 135x REGRESSION before this guard)."""
    from repro.sharding.policy import estimate_params

    if (
        cell.kind == "train"
        and cell.global_batch % 256 == 0
        and estimate_params(cfg) < 1_000_000_000
    ):
        return {"dp_only": True, "fsdp": False}
    return {}


def train_config_for(arch: str) -> TrainConfig:
    return TrainConfig(
        optimizer="adafactor" if arch in _ADAFACTOR_ARCHS else "adamw",
        schedule="wsd" if arch == "minicpm-2b" else "cosine",
        remat=True,
    )


def optimized_train_config_for(arch: str) -> TrainConfig:
    return dataclasses.replace(train_config_for(arch), moment_dtype="bfloat16")


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)


def _opt_specs(policy, p_specs, p_shapes, tc: TrainConfig):
    if tc.optimizer == "adamw":
        return {
            "m": p_specs,
            "v": p_specs,
            "count": P(),
        }
    # adafactor: factored accumulators drop one dim of the param spec
    def acc_spec(spec, shape):
        if len(shape.shape) >= 2:
            return {
                "vr": P(*spec[:-1]),
                "vc": P(*(list(spec[:-2]) + [spec[-1]])),
            }
        return {"v": spec}

    return {
        "acc": jax.tree.map(
            acc_spec, p_specs, p_shapes,
            is_leaf=lambda x: isinstance(x, P),
        ),
        "count": P(),
    }


def _unit_layers(cfg) -> int:
    """Layers per repeating unit (for depth-reduced cost extrapolation)."""
    if cfg.family == "hybrid":
        return len(cfg.hybrid_pattern)
    if cfg.family == "vlm":
        return cfg.cross_attn_period
    return 1


def _depth_cfg(cfg, units: int, scan: bool):
    """Config with `units` repeating units and optionally unrolled layers."""
    kw = {"n_layers": units * _unit_layers(cfg), "scan_layers": scan}
    if cfg.family == "audio":
        kw["n_enc_layers"] = units
        kw["n_layers"] = units
    return dataclasses.replace(cfg, **kw)


def _n_units(cfg) -> int:
    if cfg.family == "audio":
        return cfg.n_layers  # enc and dec scale together
    return cfg.n_layers // _unit_layers(cfg)


def _build_lowered(cfg, cell, mesh, policy, tc, key):
    """Lower the step function of one cell under explicit shardings."""
    from repro.sharding.ctx import sharding_ctx

    batch_sds = input_specs(cfg, cell)
    batch_specs = POL.batch_specs(policy, batch_sds, cell.kind)
    if cell.kind == "train":
        state_sds = jax.eval_shape(lambda: TrainState.create(cfg, tc, key))
        p_specs = POL.param_specs(policy, state_sds.params)
        o_specs = _opt_specs(policy, p_specs, state_sds.params, tc)
        state_specs = TrainState(params=p_specs, opt=o_specs, step=P())
        step = make_train_step(cfg, tc)
        jitted = jax.jit(
            step,
            in_shardings=(_named(mesh, state_specs), _named(mesh, batch_specs)),
            donate_argnums=(0,),
        )
        with mesh, sharding_ctx(mesh, policy):
            return jitted.lower(state_sds, batch_sds)
    params_sds = jax.eval_shape(lambda: T.init_params(cfg, key))
    p_specs = POL.param_specs(policy, params_sds)
    if cell.kind == "prefill":
        step = make_prefill_step(cfg, policy)
        jitted = jax.jit(
            step,
            in_shardings=(_named(mesh, p_specs), _named(mesh, batch_specs)),
        )
        with mesh, sharding_ctx(mesh, policy):
            return jitted.lower(params_sds, batch_sds)
    cache_sds = jax.eval_shape(
        lambda: T.init_cache(cfg, cell.global_batch, cell.seq_len)
    )
    c_specs = POL.cache_specs_tree(policy, cache_sds, cfg)
    step = make_decode_step(cfg)
    jitted = jax.jit(
        step,
        in_shardings=(
            _named(mesh, p_specs),
            _named(mesh, batch_specs),
            _named(mesh, c_specs),
        ),
        donate_argnums=(2,),
    )
    with mesh, sharding_ctx(mesh, policy):
        return jitted.lower(params_sds, batch_sds, cache_sds)


def _cost_vector(compiled) -> dict:
    rl = RL.from_compiled(compiled)
    return {
        "flops": rl.flops_per_chip,
        "bytes": rl.bytes_per_chip,
        **{f"coll:{k}": v for k, v in rl.coll_by_kind.items()},
    }


def lower_cell(arch: str, cell_name: str, multi_pod: bool = False, cfg=None,
               policy_kw: dict | None = None, variant: str = ""):
    """Lower + compile one (arch x shape x mesh) cell; return results dict.

    Three compiles: (1) the full scan-over-layers program — the runnability
    proof and the memory analysis; (2)+(3) unrolled depth-1/-2 variants whose
    cost difference gives exact per-layer-unit flops/bytes/collectives
    (XLA cost_analysis counts while bodies once, so the full-depth numbers
    must be extrapolated: total = d1 + (units-1) * (d2 - d1)).
    """
    base_cfg = cfg or get_config(arch)
    cfg = base_cfg
    cell = shape_cell(cell_name)
    ok, why = cell_applicable(cfg, cell)
    if not ok:
        return {"arch": arch, "cell": cell_name, "skipped": why,
                "mesh": "2x16x16" if multi_pod else "16x16"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = POL.auto_policy(cfg, mesh)
    if policy_kw:
        policy = dataclasses.replace(policy, **policy_kw)
    tc = train_config_for(arch)
    key = jax.random.PRNGKey(0)

    t0 = time.time()
    lowered = _build_lowered(cfg, cell, mesh, policy, tc, key)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()

    # per-unit cost extrapolation from unrolled depth-1 / depth-2 programs
    c1 = _cost_vector(
        _build_lowered(_depth_cfg(cfg, 1, scan=False), cell, mesh, policy, tc, key).compile()
    )
    c2 = _cost_vector(
        _build_lowered(_depth_cfg(cfg, 2, scan=False), cell, mesh, policy, tc, key).compile()
    )
    U = _n_units(cfg)
    cost = {k: c1[k] + (U - 1) * (c2[k] - c1[k]) for k in c1}
    coll_by_kind = {k.split(":", 1)[1]: v for k, v in cost.items() if k.startswith("coll:")}
    rl = RL.Roofline(
        flops_per_chip=cost["flops"],
        bytes_per_chip=cost["bytes"],
        coll_bytes_per_chip=float(sum(coll_by_kind.values())),
        coll_by_kind=coll_by_kind,
    )

    params_shapes = jax.eval_shape(lambda: T.init_params(cfg, key))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params_shapes))
    n_active = RL.active_params(cfg, params_shapes)
    n_tokens = cell.global_batch * (cell.seq_len if cell.kind in ("train", "prefill") else 1)
    mf = RL.model_flops(cfg, n_tokens, n_params, n_active)  # 6*N*D
    if cell.kind != "train":
        mf /= 3.0  # forward-only: 2*N*D

    n_chips = mesh.size
    result = {
        "arch": arch,
        "cell": cell_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "n_params": int(n_params),
        "n_active_params": int(n_active),
        "fsdp": policy.fsdp,
        "dp_only": policy.dp_only,
        "variant": variant,
        "attn_impl": cfg.attn_impl,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes_per_device": int(
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
            ),
        },
        "roofline": rl.to_dict(),
        "model_flops_global": mf,
        "model_flops_per_chip": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / max(rl.flops_per_chip, 1.0),
    }
    return result


def save_result(res: dict, tag: str = ""):
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    name = f"{res['arch']}__{res['cell']}__{res.get('mesh', 'na')}{tag}.json"
    path = RESULTS_DIR / name
    path.write_text(json.dumps(res, indent=2))
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--cell", choices=[c.name for c in SHAPE_CELLS])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="beyond-paper optimized configs (SSPerf)")
    args = ap.parse_args()

    cells = (
        [(a, c.name) for a in ARCHS for c in SHAPE_CELLS]
        if args.all
        else [(args.arch, args.cell)]
    )
    for arch, cell in cells:
        if args.optimized:
            base = get_config(arch)
            c = optimized_cfg(base, shape_cell(cell))
            res = lower_cell(arch, cell, multi_pod=args.multi_pod, cfg=c,
                             policy_kw=optimized_policy_kw(base, shape_cell(cell)),
                             variant="optimized")
            res_tag = "__opt"
        else:
            res = lower_cell(arch, cell, multi_pod=args.multi_pod)
            res_tag = ""
        path = save_result(res, tag=res_tag)
        if "skipped" in res:
            print(f"SKIP {arch} x {cell}: {res['skipped']}")
            continue
        rl = res["roofline"]
        print(
            f"OK {arch} x {cell} [{res['mesh']}] compile={res['compile_s']}s "
            f"peak_mem={res['memory']['peak_bytes_per_device']/2**30:.2f}GiB "
            f"t_comp={rl['t_compute_s']:.4f}s t_mem={rl['t_memory_s']:.4f}s "
            f"t_coll={rl['t_collective_s']:.4f}s bottleneck={rl['bottleneck']}"
        )


if __name__ == "__main__":
    main()
