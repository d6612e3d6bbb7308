"""Hillclimb driver: lower a cell with a named variant and print the
roofline delta vs the recorded baseline.  Results land in
benchmarks/results/hillclimb/ and the narrative in EXPERIMENTS.md SSPerf.

  PYTHONPATH=src python -m benchmarks.hillclimb --target minicpm-prefill --variant chunked_attn
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

RESULTS = pathlib.Path(__file__).resolve().parent / "results" / "hillclimb"

# variant name -> (cfg overrides, policy overrides)
LM_VARIANTS = {
    "baseline": ({}, {}),
    "chunked_attn": ({"attn_impl": "chunked", "attn_chunk": 1024}, {}),
    "chunked_attn_512": ({"attn_impl": "chunked", "attn_chunk": 512}, {}),
    "chunked_attn_2048": ({"attn_impl": "chunked", "attn_chunk": 2048}, {}),
    "last_logits": ({"prefill_last_only": True}, {}),
    "chunked+last_logits": (
        {"attn_impl": "chunked", "attn_chunk": 1024, "prefill_last_only": True}, {}),
    "dp_only": ({}, {"dp_only": True, "fsdp": False}),
    "chunked+dp_only": (
        {"attn_impl": "chunked", "attn_chunk": 1024},
        {"dp_only": True, "fsdp": False}),
    "chunked+dp_only+last": (
        {"attn_impl": "chunked", "attn_chunk": 1024, "prefill_last_only": True},
        {"dp_only": True, "fsdp": False}),
    "fsdp_off": ({}, {"fsdp": False}),
    "dp_only+fsdp": ({}, {"dp_only": True, "fsdp": True}),
    "chunked+dp_only+fsdp": (
        {"attn_impl": "chunked", "attn_chunk": 1024},
        {"dp_only": True, "fsdp": True}),
    "seq_shard": ({"attn_seq_shard": True}, {}),
    "chunked+seq_shard": (
        {"attn_impl": "chunked", "attn_chunk": 1024, "attn_seq_shard": True}, {}),
    "chunked+last+seq_shard": (
        {"attn_impl": "chunked", "attn_chunk": 1024, "prefill_last_only": True,
         "attn_seq_shard": True}, {}),
    "chunked2k+last+seq_shard": (
        {"attn_impl": "chunked", "attn_chunk": 2048, "prefill_last_only": True,
         "attn_seq_shard": True}, {}),
    "chunked4k+last+seq_shard": (
        {"attn_impl": "chunked", "attn_chunk": 4096, "prefill_last_only": True,
         "attn_seq_shard": True}, {}),
    "chunked8k+last+seq_shard": (
        {"attn_impl": "chunked", "attn_chunk": 8192, "prefill_last_only": True,
         "attn_seq_shard": True}, {}),
}

TARGETS = {
    "minicpm-prefill": ("minicpm-2b", "prefill_32k"),
    "minicpm-train": ("minicpm-2b", "train_4k"),
    "whisper-train": ("whisper-medium", "train_4k"),
    "smollm-train": ("smollm-135m", "train_4k"),
    "mamba2-train": ("mamba2-2.7b", "train_4k"),
    "grok-train": ("grok-1-314b", "train_4k"),
}

TC_VARIANTS = {"bf16_moments": {"moment_dtype": "bfloat16"}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", required=True)
    ap.add_argument("--variant", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    RESULTS.mkdir(parents=True, exist_ok=True)

    from repro.configs import get_config
    from repro.launch.dryrun import lower_cell

    arch, cell = TARGETS[args.target]
    parts = args.variant.split("&")
    cfg_kw, pol_kw = LM_VARIANTS[parts[0]]
    cfg = dataclasses.replace(get_config(arch), **cfg_kw)
    if len(parts) > 1:
        import repro.launch.dryrun as DR

        tc_kw = TC_VARIANTS[parts[1]]
        orig = DR.train_config_for
        DR.train_config_for = lambda a: dataclasses.replace(orig(a), **tc_kw)
    res = lower_cell(arch, cell, multi_pod=args.multi_pod, cfg=cfg,
                     policy_kw=pol_kw, variant=args.variant)

    out = RESULTS / f"{args.target}__{args.variant}.json"
    out.write_text(json.dumps(res, indent=2))
    rl = res["roofline"]
    print(
        f"{args.target} / {args.variant}: "
        f"t_comp={rl['t_compute_s']:.4f} t_mem={rl['t_memory_s']:.4f} "
        f"t_coll={rl['t_collective_s']:.4f} bottleneck={rl['bottleneck']} "
        f"frac={rl['roofline_fraction']:.4f} "
        f"peak={res['memory']['peak_bytes_per_device']/2**30:.1f}GiB"
    )


if __name__ == "__main__":
    main()
