"""Pallas TPU kernels for the RWKV-6 WKV recurrence, chunkwise parallel,
forward and backward.

Per (batch, head), with the (dh x dh) state S:

    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

The sequence is cut into chunks of ``CHUNK`` steps, the form of
``models.rwkv6.wkv6_chunked``.  With P the inclusive cumulative log-decay
inside a chunk and P_C its total:

    y  = mask(q~ k~^T) v + (r . k u) v + q~ S       q~ = r exp(P_{t-1})
    S' = diag(exp(P_C)) S + kd^T v                  k~ = k exp(-P_t)
                                                    kd = k exp(P_C - P_t)

so the intra-chunk products run on the MXU and only the state crosses
chunks, in VMEM scratch along the sequential grid axis.  |P| stays under
88 (f32's exp range) because ``models.rwkv6._decay`` clips the log-decay
at -exp(0.2) and a chunk is 64 steps.

Layout: the model's (B, T, H * dh), no transposes.  A grid step takes
``LANES`` lanes, G = LANES / dh heads side by side, and keeps their states
transposed on a block diagonal, Z = diag(S_1^T, ..., S_G^T) (LANES x
LANES, f32), so that one product serves all G heads.  States cross the
kernel boundary compacted to (dh, LANES): Z's G diagonal blocks side by
side.  The forward writes the state entering every chunk; the backward
reads them and walks the chunks in reverse with the state's cotangent in
VMEM.  The cumulative sums (products with a triangle of ones) and the
bonus's sums over a head are exact in f32; every other product takes f32
operands at the default precision, as the jnp form's einsums do.
``models.rwkv6.wkv6_sequential`` is the oracle.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64      # steps per chunk: exp(+-P) stays in f32 range (see above)
LANES = 128     # lanes a grid step takes: G = LANES // dh heads
BLOCK_CHUNKS = 8  # chunks per grid step, at most
f32 = jnp.float32


def fits(T: int, H: int, dh: int) -> bool:
    """Whether (T, H, dh) tiles the kernels: whole chunks, heads that fill
    the lanes."""
    return T % CHUNK == 0 and LANES % dh == 0 and (H * dh) % LANES == 0


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=f32)


def _nn(a, b):                        # a @ b
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):                        # a @ b^T
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):                        # a^T @ b
    return _dot(a, b, ((0,), (0,)))


class _Consts:
    """Masks of one grid step, built from iotas."""

    def __init__(self, dh: int):
        C, L = CHUNK, LANES
        t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
        s = lax.broadcasted_iota(jnp.int32, (C, C), 1)
        self.incl = (s <= t).astype(jnp.bfloat16)   # cumsum: incl @ x
        self.rev = (s >= t).astype(jnp.bfloat16)    # reversed: rev @ x
        self.strict = s < t                   # intra-chunk causal mask
        a = lax.broadcasted_iota(jnp.int32, (L, L), 0) // dh
        b = lax.broadcasted_iota(jnp.int32, (L, L), 1) // dh
        self.same_head = a == b               # the block diagonal
        lane = lax.broadcasted_iota(jnp.int32, (1, L), 1) // dh
        self.lane_heads = [(lane == g).astype(f32) for g in range(L // dh)]
        self.dh = dh

    def head_sum(self, x):
        """Each head's sum over its lanes, on its lanes."""
        return sum(jnp.sum(x * m, axis=1, keepdims=True) * m
                   for m in self.lane_heads)

    def expand(self, zc):
        """(dh, LANES) compact state -> (LANES, LANES) block diagonal."""
        return jnp.concatenate([zc * m for m in self.lane_heads], axis=0)

    def compact(self, z):
        dh = self.dh
        return sum(z[g * dh:(g + 1) * dh] for g in range(LANES // dh))


def _tri(tri, x):
    """tri @ x for a triangle of ones, exact in f32: x as the sum of three
    bf16 parts, each product exact, accumulated in f32."""
    out = 0.0
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        out = out + _nn(tri, part)
        x = x - part.astype(f32)
    return out


def _chunk_terms(cs, r, k, lw):
    """Decay factors of one chunk: q~, k~, kd, exp(P_{t-1}), exp(-P_t),
    exp(P_C - P_t), and the total's exp(P_C), a row (1, LANES)."""
    cum = _tri(cs.incl, lw)
    cp = cum - lw
    tot = cum[CHUNK - 1:CHUNK]
    e_q, e_k, e_kd = jnp.exp(cp), jnp.exp(-cum), jnp.exp(tot - cum)
    return r * e_q, k * e_k, k * e_kd, e_q, e_k, e_kd, jnp.exp(tot)


def _fwd_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                y_ref, st_ref, sT_ref, z_scr, *, dh: int, n_sub: int):
    ti = pl.program_id(2)
    cs = _Consts(dh)

    @pl.when(ti == 0)
    def _init():
        z_scr[...] = cs.expand(s0_ref[0])

    u = u_ref[...]

    def chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        r = r_ref[0, rows, :].astype(f32)
        k = k_ref[0, rows, :].astype(f32)
        v = v_ref[0, rows, :].astype(f32)
        lw = lw_ref[0, rows, :].astype(f32)
        z = z_scr[...]
        st_ref[0, c] = cs.compact(z)
        q, kt, kd, _, _, _, e_tot = _chunk_terms(cs, r, k, lw)
        bonus = cs.head_sum(r * k * u)
        y = bonus * v + _nt(q, z)
        for m in cs.lane_heads:
            a = jnp.where(cs.strict, _nt(q * m, kt), 0.0)
            y = y + _nn(a, v * m)
        y_ref[0, rows, :] = y.astype(y_ref.dtype)
        z_scr[...] = z * e_tot + jnp.where(cs.same_head, _tn(v, kd), 0.0)
        return carry

    lax.fori_loop(0, n_sub, chunk, 0)

    @pl.when(ti == pl.num_programs(2) - 1)
    def _final():
        sT_ref[0] = cs.compact(z_scr[...])


def _bwd_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, st_ref, dy_ref, dsT_ref,
                dr_ref, dk_ref, dv_ref, dlw_ref, du_ref, ds0_ref, dz_scr,
                *, dh: int, n_sub: int):
    ti = pl.program_id(2)
    cs = _Consts(dh)

    @pl.when(ti == 0)
    def _init():
        dz_scr[...] = cs.expand(dsT_ref[0])
        du_ref[...] = jnp.zeros_like(du_ref)

    u = u_ref[...]

    def chunk(j, carry):
        c = n_sub - 1 - j
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        r = r_ref[0, rows, :].astype(f32)
        k = k_ref[0, rows, :].astype(f32)
        v = v_ref[0, rows, :].astype(f32)
        lw = lw_ref[0, rows, :].astype(f32)
        dy = dy_ref[0, rows, :].astype(f32)
        z = cs.expand(st_ref[0, c])
        dz = dz_scr[...]
        q, kt, kd, e_q, e_k, e_kd, e_tot = _chunk_terms(cs, r, k, lw)
        bonus = cs.head_sum(r * k * u)
        dq = _nn(dy, z)
        dv = bonus * dy + _nt(kd, dz)
        dkd = _nn(v, dz)
        dkt = jnp.zeros_like(kt)
        for m in cs.lane_heads:
            a = jnp.where(cs.strict, _nt(q * m, kt), 0.0)
            da = jnp.where(cs.strict, _nt(dy * m, v), 0.0)
            dq = dq + _nn(da, kt * m)
            dkt = dkt + _tn(da, q * m)
            dv = dv + _tn(a, dy * m)
        dbonus = cs.head_sum(dy * v)
        dr_ref[0, rows, :] = (dq * e_q + dbonus * k * u).astype(dr_ref.dtype)
        dk_ref[0, rows, :] = (dkt * e_k + dkd * e_kd
                              + dbonus * r * u).astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)
        du_ref[0] += jnp.sum(dbonus * r * k, axis=0, keepdims=True)
        # the log-decay: q~ reads P_{t-1}, k~ and kd read P_t, kd and the
        # state's decay read P_C
        dcp = dq * q
        dcum = dcp - dkt * kt - dkd * kd
        dtot = (jnp.sum(dkd * kd, axis=0, keepdims=True)
                + jnp.sum(z * dz, axis=0, keepdims=True) * e_tot)
        dlw = _tri(cs.rev, dcum) - dcp + dtot
        dlw_ref[0, rows, :] = dlw.astype(dlw_ref.dtype)
        dz_scr[...] = dz * e_tot + jnp.where(cs.same_head, _tn(dy, q), 0.0)
        return carry

    lax.fori_loop(0, n_sub, chunk, 0)

    @pl.when(ti == pl.num_programs(2) - 1)
    def _final():
        ds0_ref[0] = cs.compact(dz_scr[...])


def _block_chunks(T: int) -> int:
    n = T // CHUNK
    return max(d for d in range(1, min(n, BLOCK_CHUNKS) + 1) if n % d == 0)


def wkv6_fwd(r, k, v, lw, u, s0, *, dh: int, interpret: bool = False):
    """r, k, v, lw: (B, T, H * dh); u: (1, H * dh) f32; s0: (B, dh, H * dh)
    compact transposed states.  Returns y (B, T, H * dh) in r's dtype, the
    compact state entering each chunk (B, T / CHUNK, dh, H * dh) and the
    final one (B, dh, H * dh), f32."""
    B, T, D = r.shape
    nb = _block_chunks(T)
    bt, n = nb * CHUNK, T // CHUNK
    grid = (B, D // LANES, T // bt)
    seq = pl.BlockSpec((1, bt, LANES), lambda b, h, t: (b, t, h))
    state = pl.BlockSpec((1, dh, LANES), lambda b, h, t: (b, 0, h))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dh=dh, n_sub=nb),
        grid=grid,
        in_specs=[seq, seq, seq, seq,
                  pl.BlockSpec((1, LANES), lambda b, h, t: (0, h)), state],
        out_specs=[seq,
                   pl.BlockSpec((1, nb, dh, LANES),
                                lambda b, h, t: (b, t, 0, h)),
                   state],
        out_shape=[jax.ShapeDtypeStruct((B, T, D), r.dtype),
                   jax.ShapeDtypeStruct((B, n, dh, D), f32),
                   jax.ShapeDtypeStruct((B, dh, D), f32)],
        scratch_shapes=[pltpu.VMEM((LANES, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="wkv6_fwd",
    )(r, k, v, lw, u, s0)


def wkv6_bwd(r, k, v, lw, u, states, dy, dsT, *, dh: int,
             interpret: bool = False):
    """The cotangents of ``wkv6_fwd``'s inputs from those of y and the final
    state: dr, dk, dv (r's dtype), dlw (f32), du per batch row
    (B, 1, H * dh) and ds0 (B, dh, H * dh)."""
    B, T, D = r.shape
    nb = _block_chunks(T)
    bt = nb * CHUNK
    nt = T // bt
    grid = (B, D // LANES, nt)
    seq = pl.BlockSpec((1, bt, LANES), lambda b, h, t: (b, nt - 1 - t, h))
    state = pl.BlockSpec((1, dh, LANES), lambda b, h, t: (b, 0, h))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dh=dh, n_sub=nb),
        grid=grid,
        in_specs=[seq, seq, seq, seq,
                  pl.BlockSpec((1, LANES), lambda b, h, t: (0, h)),
                  pl.BlockSpec((1, nb, dh, LANES),
                               lambda b, h, t: (b, nt - 1 - t, 0, h)),
                  seq, state],
        out_specs=[seq, seq, seq, seq,
                   pl.BlockSpec((1, 1, LANES), lambda b, h, t: (b, 0, h)),
                   state],
        out_shape=[jax.ShapeDtypeStruct((B, T, D), r.dtype),
                   jax.ShapeDtypeStruct((B, T, D), k.dtype),
                   jax.ShapeDtypeStruct((B, T, D), v.dtype),
                   jax.ShapeDtypeStruct((B, T, D), lw.dtype),
                   jax.ShapeDtypeStruct((B, 1, D), f32),
                   jax.ShapeDtypeStruct((B, dh, D), f32)],
        scratch_shapes=[pltpu.VMEM((LANES, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="wkv6_bwd",
    )(r, k, v, lw, u, states, dy, dsT)
