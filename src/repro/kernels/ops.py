"""Jit'd public wrappers around the Pallas kernels.

On a TPU runtime these lower to Mosaic; on CPU (this container) callers pass
``interpret=True`` (tests) or use the jnp fallbacks in ``repro.models``.
The wrappers own layout plumbing: head merging/expansion for GQA, dtype
promotion, state threading.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .rglru_scan import rglru_scan_kernel
from .wkv6 import CHUNK, LANES, fits, wkv6_bwd, wkv6_fwd


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale", "interpret"))
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int = 0, softcap: float = 0.0,
              scale: Optional[float] = None, interpret: bool = False
              ) -> jax.Array:
    """GQA flash attention.  q: (B, Sq, H, dh); k, v: (B, Sk, K, dh)."""
    B, Sq, H, dh = q.shape
    Sk, K = k.shape[1], k.shape[2]
    if H != K:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    qm = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    km = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, dh)
    vm = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, dh)
    o = flash_attention(qm, km, vm, causal=causal, window=window,
                        softcap=softcap, scale=scale, interpret=interpret)
    return o.reshape(B, H, Sq, dh).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rglru_scan(a: jax.Array, b: jax.Array, h0: Optional[jax.Array] = None,
               *, interpret: bool = False) -> jax.Array:
    """Linear recurrence h_t = a_t h_{t-1} + b_t.  a, b: (B, S, W)."""
    B, S, W = a.shape
    # pick block sizes that divide the dims (kernel requirement)
    def divisor(n, target):
        d = min(target, n)
        while n % d:
            d -= 1
        return d
    return rglru_scan_kernel(a, b, h0,
                             block_b=divisor(B, 8),
                             block_t=divisor(S, 128),
                             block_w=divisor(W, 512),
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
         u: jax.Array, s0: Optional[jax.Array] = None, *,
         interpret: bool = False):
    """RWKV6 recurrence, chunkwise on the MXU, differentiable.
    r/k/v/logw: (B, T, H, dh); u: (H, dh); s0: optional (B, H, dh, dh).
    Returns (y: (B, T, H, dh), s_final: (B, H, dh, dh) f32).  Needs
    ``wkv6.fits(T, H, dh)``.

    Heads stay merged as the model holds them, (B, T, H * dh); only the
    states change layout, to the kernels' compact transposed form."""
    B, T, H, dh = r.shape
    if not fits(T, H, dh):
        raise ValueError(f"T={T}, H={H}, dh={dh} do not tile the WKV "
                         f"kernels (T a multiple of {CHUNK}, H * dh of "
                         f"{LANES})")
    if s0 is None:
        s0 = jnp.zeros((B, H, dh, dh), jnp.float32)

    def flat(x):
        return x.reshape(B, T, H * dh)
    y, s_final = _wkv6(dh, interpret, flat(r), flat(k), flat(v),
                       flat(logw).astype(jnp.float32),
                       u.reshape(1, H * dh).astype(jnp.float32),
                       s0.astype(jnp.float32).transpose(0, 3, 1, 2)
                       .reshape(B, dh, H * dh))
    return (y.reshape(B, T, H, dh),
            s_final.reshape(B, dh, H, dh).transpose(0, 2, 3, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _wkv6(dh, interpret, r, k, v, lw, u, s0):
    y, _, s_final = wkv6_fwd(r, k, v, lw, u, s0, dh=dh, interpret=interpret)
    return y, s_final


def _wkv6_fwd(dh, interpret, r, k, v, lw, u, s0):
    y, states, s_final = wkv6_fwd(r, k, v, lw, u, s0, dh=dh,
                                  interpret=interpret)
    return (y, s_final), (r, k, v, lw, u, states)


def _wkv6_bwd(dh, interpret, res, cts):
    r, k, v, lw, u, states = res
    dy, ds_final = cts
    # the caller's scope does not reach the transposed computation
    with jax.named_scope("wkv"):
        dr, dk, dv, dlw, du, ds0 = wkv6_bwd(
            r, k, v, lw, u, states, dy.astype(r.dtype),
            ds_final.astype(jnp.float32), dh=dh, interpret=interpret)
        return dr, dk, dv, dlw, du.sum(axis=0), ds0


_wkv6.defvjp(_wkv6_fwd, _wkv6_bwd)
