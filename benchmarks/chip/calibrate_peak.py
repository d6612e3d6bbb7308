"""Measure the chip's float32 vector peak once, for ``peaks.json``.

    python benchmarks/chip/calibrate_peak.py

No float32 vector peak of the TPU v5e is published (its 197 TFLOP/s is
the bf16 matrix unit's).  The kNN and lookup kernels are float32 vector
work, so their roofline shares are taken against this measured rate.

The kernel keeps ``ROWS`` x 128 float32 values in vector registers and
applies ``x = x * a + b`` to each of them ``ITERS`` times: two operations
per element per pass, no memory traffic inside the loop, and ``ROWS / 8``
independent vector registers so that the vector units are never starved
by one dependency chain.  Operations are counted as one per multiply and
one per add, the same rule the kernels' counts use.  The rate is the
best of ``REPEATS`` timed calls, each long enough (about a second) that
the host clock's error is negligible.  The number printed is recorded in
``peaks.json`` by hand, with the command and date as its source; no
benchmark run measures it again.
"""
from __future__ import annotations

import functools
import json
import sys
import time

ROWS = 256  # 32 vector registers of (8, 128)
UNROLL = 16
ITERS = 50_000_000
REPEATS = 5


def _kernel(x_ref, o_ref, *, iters: int):
    import jax
    import jax.numpy as jnp

    a = jnp.float32(0.9999999)
    b = jnp.float32(1e-7)

    def body(_, xs):
        for _ in range(UNROLL):
            xs = tuple(x * a + b for x in xs)
        return xs

    xs = tuple(x_ref[pl_slice(i)] for i in range(ROWS // 8))
    xs = jax.lax.fori_loop(0, iters // UNROLL, body, xs)
    for i, x in enumerate(xs):
        o_ref[pl_slice(i)] = x


def pl_slice(i: int):
    return slice(8 * i, 8 * i + 8), slice(None)


def measure() -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"calibrate_peak: JAX finds no TPU ({dev.platform})")
    fn = jax.jit(pl.pallas_call(
        functools.partial(_kernel, iters=ITERS),
        out_shape=jax.ShapeDtypeStruct((ROWS, 128), jnp.float32),
    ))
    x = jnp.ones((ROWS, 128), jnp.float32)
    fn(x).block_until_ready()
    ops = 2.0 * ROWS * 128 * (ITERS // UNROLL) * UNROLL
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return {"device_kind": dev.device_kind, "ops": ops, "seconds": best,
            "f32_vector_ops_per_s": ops / best}


if __name__ == "__main__":
    print(json.dumps(measure()))
    sys.exit(0)
