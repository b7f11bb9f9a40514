"""Pallas TPU kernel for the RWKV-6 WKV recurrence.

Per (batch, head):   S_t = diag(w_t) S_{t-1} + k_t^T v_t
                     y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

State S is (dh x dh) and lives in VMEM scratch across the sequential time
grid; each grid step streams a (block_t, dh) tile of r/k/v/w and performs
block_t rank-1 updates.  dh = 64 keeps S at 16 KiB fp32 — far under VMEM.
The time loop is VPU-bound (outer products), matching the memory-bound
roofline of the op; the chunkwise-matmul variant in models.rwkv6 is the
MXU-friendly form used for full-sequence training, with this kernel as the
exact sequential semantics (and the decode path).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 8       # time steps per aligned load (one f32 sublane tile)


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, s_scr,
                 *, block_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    u = u_ref[0].astype(jnp.float32)                  # (dh, 1)

    def chunk(c, s):
        # Mosaic loads rows only at sublane-aligned offsets: take CHUNK rows
        # at once and walk them with static indices; the column views of r,
        # k and w come from one transpose per chunk
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        r = r_ref[0, rows, :].astype(jnp.float32).T   # (dh, CHUNK)
        k = k_ref[0, rows, :].astype(jnp.float32).T
        w = jnp.exp(lw_ref[0, rows, :].astype(jnp.float32)).T
        v = v_ref[0, rows, :].astype(jnp.float32)     # (CHUNK, dh)
        ys = []
        for t in range(CHUNK):
            kv = k[:, t:t + 1] * v[t:t + 1, :]        # (dh, dh) rank-1
            ys.append(jnp.sum((s + u * kv) * r[:, t:t + 1], axis=0,
                              keepdims=True))
            s = w[:, t:t + 1] * s + kv
        y_ref[0, rows, :] = jnp.concatenate(ys, axis=0).astype(y_ref.dtype)
        return s

    s_scr[...] = jax.lax.fori_loop(0, block_t // CHUNK, chunk, s_scr[...])


def wkv6_kernel(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
                u: jax.Array, s0: Optional[jax.Array] = None, *,
                block_t: int = 64, interpret: bool = False):
    """r, k, v, logw: (BH, T, dh) (batch*heads merged); u: (BH, dh) per-head
    bonus (pre-broadcast); s0: (BH, dh, dh).  Returns y: (BH, T, dh)."""
    BH, T, dh = r.shape
    if s0 is None:
        s0 = jnp.zeros((BH, dh, dh), jnp.float32)
    block_t = min(block_t, T)
    if T % block_t or block_t % CHUNK:
        raise ValueError(f"T={T} must divide block_t={block_t}, itself a "
                         f"multiple of {CHUNK}")
    grid = (BH, 1, T // block_t)

    kernel = functools.partial(_wkv6_kernel, block_t=block_t)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, dh), lambda b, _, ti: (b, ti, 0)),
            pl.BlockSpec((1, block_t, dh), lambda b, _, ti: (b, ti, 0)),
            pl.BlockSpec((1, block_t, dh), lambda b, _, ti: (b, ti, 0)),
            pl.BlockSpec((1, block_t, dh), lambda b, _, ti: (b, ti, 0)),
            pl.BlockSpec((1, dh, 1), lambda b, _, ti: (b, 0, 0)),
            pl.BlockSpec((1, dh, dh), lambda b, _, ti: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t, dh), lambda b, _, ti: (b, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, dh), r.dtype),
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u.reshape(BH, dh, 1), s0)
