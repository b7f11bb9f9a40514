"""Pallas TPU flash attention (causal / sliding-window / softcap).

Tiling: grid (batch*heads, q_blocks, kv_blocks) with the kv dimension
sequential ("arbitrary") so the online-softmax running state lives in VMEM
scratch across kv steps.  Block shapes are explicit BlockSpecs: q/o tiles
(1, block_q, d_head), k/v tiles (1, block_k, d_head); the MXU sees
(block_q x d_head) @ (d_head x block_k) and (block_q x block_k) @
(block_k x d_head) matmuls — block sizes default to 128/256, multiples of
the 128-lane register tiling.

HBM->VMEM traffic per (q-block, kv-block): block_q*dh + 2*block_k*dh of
bf16 — the full O(Sq*Sk) score matrix never exists, which is the point
(FlashAttention, adapted to the TPU memory hierarchy: VMEM scratch plays
the role of SRAM, sequential kv grid of the SM loop).

``repro.models.layers.mha`` is the jnp fallback; ``kernels.ref`` wraps it
as the oracle for interpret-mode tests.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  block_q: int, block_k: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
    # guard: rows with every key masked keep p == 0 (not exp(0))
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    alpha = jnp.exp(m_old - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot(
        p.astype(v_ref.dtype).astype(jnp.float32),
        v_ref[0].astype(jnp.float32))
    m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q, k, v: (BH, S, dh) with heads pre-merged into the batch dim
    (ops.py handles the GQA expansion).  Returns (BH, Sq, dh)."""
    bh, sq, dh = q.shape
    sk = k.shape[1]
    scale = (1.0 / math.sqrt(dh)) if scale is None else scale
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lengths ({sq},{sk}) must divide blocks "
                         f"({block_q},{block_k})")
    n_q, n_kv = sq // block_q, sk // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, n_kv=n_kv)

    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, dh), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
