"""Pallas TPU kernel: flash attention forward (online softmax), GQA-aware.

The TPU-target resolution of the SSPerf HC1/HC2 finding that XLA:CPU (and
to a lesser degree XLA:TPU) materializes the softmax chain: here the
(block_q, block_k) logit tile, its exp, and the PV partial products all
live in VMEM; HBM sees only Q/K/V reads and one O write.

Grid: (batch*q_heads, num_q_blocks, num_k_blocks) — the kv axis is the
innermost (sequential on TPU), so the online-softmax state (m, l, acc)
persists in VMEM scratch across kv steps of one (head, q-block).
Causal blocks entirely above the diagonal are skipped with pl.when.
MXU alignment: block_q/block_k multiples of 128, d_head padded by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, causal: bool, block_q: int, block_k: int, nk: int, sm_scale: float,
):
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # kv block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    k_start = j * block_k
    # skip fully-masked blocks (strictly above the causal diagonal)
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(k_start <= q_start + block_q - 1 if causal else True)
    def _body():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # (bq, dh)
        k = k_ref[0].astype(jnp.float32)  # (bk, dh)
        v = v_ref[0].astype(jnp.float32)
        s = q @ k.T  # (bq, bk)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _emit():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def flash_attn_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    block_q: int = 128,
    block_k: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    """q: (BH, Sq, dh); k/v: (BK, Sk, dh) with BH = B*H, BK = B*K.
    Head grouping (GQA) is encoded in the k/v index maps: q head h reads
    kv head h // rep.  Shapes must be pre-padded to block multiples.
    """
    BH, Sq, dh = q.shape
    BK, Sk, _ = k.shape
    rep = BH // BK
    nq = Sq // block_q
    nk = Sk // block_k
    sm_scale = 1.0 / (dh ** 0.5)

    kernel = functools.partial(
        _flash_kernel, causal=causal, block_q=block_q, block_k=block_k,
        nk=nk, sm_scale=sm_scale,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda h, i, j: (h // rep, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda h, i, j: (h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
