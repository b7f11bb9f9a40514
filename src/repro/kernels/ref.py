"""Pure-jnp oracles for the Pallas kernels (the ground truth for tests); the
WKV kernels' is ``models.rwkv6.wkv6_sequential``."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        softcap: float = 0.0,
                        scale: Optional[float] = None) -> jax.Array:
    """Naive O(S^2) attention.  q/k/v: (BH, S, dh)."""
    import math
    bh, sq, dh = q.shape
    sk = k.shape[1]
    scale = (1.0 / math.sqrt(dh)) if scale is None else scale
    s = jnp.einsum("bqd,bsd->bqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqs,bsd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def rglru_scan_ref(a: jax.Array, b: jax.Array,
                   h0: Optional[jax.Array] = None) -> jax.Array:
    """h_t = a_t h_{t-1} + b_t, sequential scan.  a, b: (B, S, W)."""
    B, S, W = a.shape
    h = jnp.zeros((B, W), a.dtype) if h0 is None else h0

    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    _, hs = jax.lax.scan(step, h, (jnp.moveaxis(a, 1, 0), jnp.moveaxis(b, 1, 0)))
    return jnp.moveaxis(hs, 0, 1)

