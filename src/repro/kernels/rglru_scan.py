"""Pallas TPU kernel for the RG-LRU linear recurrence (Griffin).

    h_t = a_t * h_{t-1} + b_t        (per channel; a, b precomputed gates)

Bandwidth-bound elementwise scan: the associative-scan XLA fallback runs
log(S) full passes over HBM; this kernel streams the sequence once, keeping
the carry in VMEM scratch.  Tiling: grid (batch_tiles, width_tiles,
time_blocks), time sequential; each step loads (block_b, block_t, block_w)
tiles of a and b, loops block_t steps in registers, writes h tiles back.

HBM traffic = 2 reads + 1 write of (B, S, W) fp32 — the roofline floor for
this op; the XLA assoc-scan does ~2*log2(S) x that.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 8       # time steps per aligned load (one f32 sublane tile)


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, carry, *, block_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        carry[...] = h0_ref[...]

    def chunk(c, h):                      # h: (bb, bw)
        # Mosaic indexes the time (sublane) axis only at aligned offsets:
        # load CHUNK steps at once and walk them with static indices
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        a = a_ref[:, rows, :]             # (bb, CHUNK, bw)
        b = b_ref[:, rows, :]
        hs = []
        for t in range(CHUNK):
            h = a[:, t, :] * h + b[:, t, :]
            hs.append(h)
        o_ref[:, rows, :] = jnp.stack(hs, axis=1).astype(o_ref.dtype)
        return h

    carry[...] = jax.lax.fori_loop(0, block_t // CHUNK, chunk, carry[...])


def rglru_scan_kernel(a: jax.Array, b: jax.Array,
                      h0: Optional[jax.Array] = None, *,
                      block_b: int = 8, block_t: int = 128,
                      block_w: int = 512, interpret: bool = False
                      ) -> jax.Array:
    """a, b: (B, S, W) fp32 decay/input; h0: (B, W) initial state.
    Returns h: (B, S, W)."""
    B, S, W = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    block_b = min(block_b, B)
    block_t = min(block_t, S)
    block_w = min(block_w, W)
    if B % block_b or S % block_t or W % block_w or block_t % CHUNK:
        raise ValueError(f"dims {(B, S, W)} must divide blocks "
                         f"{(block_b, block_t, block_w)}, and block_t be a "
                         f"multiple of {CHUNK}")
    grid = (B // block_b, W // block_w, S // block_t)

    kernel = functools.partial(_rglru_kernel, block_t=block_t)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_t, block_w),
                         lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((block_b, block_t, block_w),
                         lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((block_b, block_w), lambda bi, wi, ti: (bi, wi)),
        ],
        out_specs=pl.BlockSpec((block_b, block_t, block_w),
                               lambda bi, wi, ti: (bi, ti, wi)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
