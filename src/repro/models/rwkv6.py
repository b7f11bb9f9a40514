"""RWKV-6 "Finch" block (Peng et al., arXiv:2404.05892).

Time-mix with data-dependent decay:
    per head h, channel c:   S_t = diag(w_t) S_{t-1} + k_t^T v_t
                             y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)
with w_t = exp(-exp(w0 + lora_w(x~_t)))  (data-dependent decay),
token-shift data-dependent lerps for r/k/v/w/g, per-head groupnorm on y,
and a squared-ReLU channel-mix FFN.

The sequence form is *chunkwise parallel* (matmul-heavy for the MXU):
within a chunk the contribution is a masked (q~ k~^T) v matmul in log-decay
space; across chunks the (dh x dh) state propagates sequentially.  On a TPU,
where the shapes tile, it runs as the Pallas kernels of
``repro.kernels.wkv6`` (forward and backward); elsewhere as
``wkv6_chunked``, a ``lax.scan`` over chunks.  ``wkv6_sequential``, the
token-by-token recurrence, is the oracle of both and the decode path.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops, wkv6
from repro.runtime import batch_map

from .layers import ParamSpec, linear_spec, apply_linear

LORA_DIM = 32
MIXES = ("r", "k", "v", "w", "g")


def rwkv6_head_dim(cfg) -> int:
    return 64 if cfg.d_model % 64 == 0 else cfg.d_model // cfg.n_heads


def rwkv6_spec(cfg) -> Dict[str, Any]:
    d = cfg.d_model
    dh = rwkv6_head_dim(cfg)
    H = d // dh
    sc = 1.0 / math.sqrt(d)
    spec: Dict[str, Any] = {
        "mu": ParamSpec((len(MIXES), d), (None, "embed"), scale=0.5),
        "mix_lora_a": ParamSpec((d, len(MIXES) * LORA_DIM), ("embed", None), scale=sc),
        "mix_lora_b": ParamSpec((len(MIXES), LORA_DIM, d), (None, None, "embed"),
                                scale=0.01),
        "w0": ParamSpec((d,), ("embed",), init="zeros"),
        "w_lora_a": ParamSpec((d, LORA_DIM * 2), ("embed", None), scale=sc),
        "w_lora_b": ParamSpec((LORA_DIM * 2, d), (None, "embed"), scale=0.01),
        "u": ParamSpec((H, dh), (None, None), scale=0.5),
        "wr": linear_spec(d, d, ("embed", "q_proj")),
        "wk": linear_spec(d, d, ("embed", "q_proj")),
        "wv": linear_spec(d, d, ("embed", "q_proj")),
        "wg": linear_spec(d, d, ("embed", "q_proj")),
        "wo": linear_spec(d, d, ("q_proj", "embed")),
        "ln_scale": ParamSpec((d,), ("embed",), init="ones"),
        # channel mix
        "ck": linear_spec(d, cfg.d_ff, ("embed", "mlp")),
        "cv": linear_spec(cfg.d_ff, d, ("mlp", "embed")),
        "cr": linear_spec(d, d, ("embed", "q_proj")),
        "mu_ck": ParamSpec((d,), ("embed",), scale=0.5),
        "mu_cr": ParamSpec((d,), ("embed",), scale=0.5),
    }
    return spec


def _token_shift(x: jax.Array, prev: jax.Array | None) -> jax.Array:
    """Previous-token stream: shift right by one along S; position 0 takes
    ``prev`` (decode carry) or zeros."""
    first = jnp.zeros_like(x[:, :1]) if prev is None else prev[:, None].astype(x.dtype)
    return jnp.concatenate([first, x[:, :-1]], axis=1)


def _ddlerp(p, x: jax.Array, xx: jax.Array) -> Dict[str, jax.Array]:
    """Data-dependent token-shift mix for the five streams (RWKV6 ddlerp)."""
    base = x + (xx - x) * 0.5
    lora = jnp.einsum("bsd,dk->bsk", base, p["mix_lora_a"].astype(x.dtype))
    lora = jnp.tanh(lora.reshape(*x.shape[:2], len(MIXES), LORA_DIM))
    delta = jnp.einsum("bsmk,mkd->bsmd", lora, p["mix_lora_b"].astype(x.dtype))
    out = {}
    for m, name in enumerate(MIXES):
        mix = p["mu"][m].astype(x.dtype) + delta[:, :, m]
        out[name] = x + (xx - x) * mix
    return out


def _decay(p, xw: jax.Array) -> jax.Array:
    """log w_t (negative): -exp(w0 + lora(xw)); per channel, fp32."""
    a = jnp.tanh(jnp.einsum("bsd,dk->bsk", xw, p["w_lora_a"].astype(xw.dtype)))
    dd = jnp.einsum("bsk,kd->bsd", a, p["w_lora_b"].astype(xw.dtype))
    # upper clip 0.2 bounds per-step log-decay at -exp(0.2) ~ -1.22 so the
    # chunkwise factored form exp(+-cum) stays inside fp32 range with
    # chunk=64 (|cum| <= 64 * 1.22 ~ 78 < 88).  §Perf iteration 2 for the
    # rwkv prefill cell: chunk 32 -> 64 halves sequential-scan trips.
    return -jnp.exp(jnp.clip(p["w0"].astype(jnp.float32) + dd.astype(jnp.float32),
                             -8.0, 0.2))


def wkv6_chunked(r, k, v, logw, u, state=None, chunk: int = 64):
    """Chunkwise-parallel WKV6.

    r,k,v: (B,T,H,dh); logw: (B,T,H,dh) (log decay, <0); u: (H,dh).
    state: optional (B,H,dh,dh) initial state.  Returns (y, final_state).
    """
    B, T, H, dh = r.shape
    n = -(-T // chunk)
    pad = n * chunk - T
    if pad:
        z = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v, logw = z(r), z(k), z(v), z(logw)
    f32 = jnp.float32
    # keep inputs in their storage dtype; each chunk casts to f32 inside the
    # (checkpointed) scan body so only one chunk's f32 working set is live —
    # precomputing q_tilde/k_tilde for all chunks costs ~10 full-sequence f32
    # tensors and dominated train-step HBM (see EXPERIMENTS.md §Perf).
    stream_dt = jnp.bfloat16 if r.dtype != jnp.float64 else r.dtype
    rs = jnp.moveaxis(r.reshape(B, n, chunk, H, dh), 1, 0).astype(stream_dt)
    ks = jnp.moveaxis(k.reshape(B, n, chunk, H, dh), 1, 0).astype(stream_dt)
    vs = jnp.moveaxis(v.reshape(B, n, chunk, H, dh), 1, 0).astype(stream_dt)
    lw = jnp.moveaxis(logw.reshape(B, n, chunk, H, dh), 1, 0).astype(jnp.float32)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)
    uf = u.astype(f32)
    s0 = jnp.zeros((B, H, dh, dh), f32) if state is None else state.astype(f32)

    @jax.checkpoint
    def body(s, inp):
        r_c, k_c, v_c, lw_c = [a.astype(f32) for a in inp]   # (B,chunk,H,dh)
        cum = jnp.cumsum(lw_c, axis=1)                 # inclusive logdecay P_t
        cum_prev = cum - lw_c                          # P_{t-1}
        total = cum[:, -1]                             # chunk total decay
        q_tilde = r_c * jnp.exp(cum_prev)
        k_tilde = k_c * jnp.exp(-cum)
        # intra-chunk: scores_ts = sum_c r_t k_s exp(P_{t-1} - P_s)  (s < t)
        scores = jnp.einsum("bthd,bshd->bhts", q_tilde, k_tilde)
        scores = jnp.where(mask[None, None], scores, 0.0)
        y = jnp.einsum("bhts,bshd->bthd", scores, v_c)
        bonus = jnp.einsum("bthd,hd->bth", r_c * k_c, uf)
        y = y + bonus[..., None] * v_c
        # inter-chunk: state contribution and update
        y = y + jnp.einsum("bthd,bhde->bthe", q_tilde, s)
        k_dec = k_c * jnp.exp(total[:, None] - cum)
        s_new = s * jnp.exp(total)[..., None] + jnp.einsum(
            "bthd,bthe->bhde", k_dec, v_c)
        return s_new, y.astype(r.dtype)

    s_final, ys = lax.scan(body, s0, (rs, ks, vs, lw))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, n * chunk, H, dh)[:, :T]
    return y, s_final


def wkv6_sequential(r, k, v, logw, u, state=None):
    """Token-by-token reference recurrence (oracle for the chunked form and
    the Pallas kernel).  Same signature as ``wkv6_chunked``."""
    B, T, H, dh = r.shape
    f32 = jnp.float32
    s0 = jnp.zeros((B, H, dh, dh), f32) if state is None else state.astype(f32)

    def body(s, inp):
        r_t, k_t, v_t, lw_t = inp                     # (B,H,dh)
        kv = jnp.einsum("bhd,bhe->bhde", k_t, v_t)
        y_t = jnp.einsum("bhd,bhde->bhe", r_t, s + u.astype(f32)[None, :, :, None] * kv)
        s_new = jnp.exp(lw_t)[..., None] * s + kv
        return s_new, y_t

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (r, k, v, logw))
    s_final, ys = lax.scan(body, s0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype), s_final


def wkv6_kernel_fits(S: int, H: int, dh: int) -> bool:
    """Whether the sequence form runs as the Pallas kernels: on a TPU, with
    shapes that tile them."""
    return jax.default_backend() == "tpu" and wkv6.fits(S, H, dh)


def _group_norm(x: jax.Array, scale: jax.Array, H: int, eps: float = 64e-5) -> jax.Array:
    """Per-head groupnorm on (B, T, d) with d = H * dh (RWKV6 ln_x)."""
    B, T, d = x.shape
    xh = x.reshape(B, T, H, d // H).astype(jnp.float32)
    mu = jnp.mean(xh, axis=-1, keepdims=True)
    var = jnp.var(xh, axis=-1, keepdims=True)
    y = (xh - mu) * lax.rsqrt(var + eps)
    return (y.reshape(B, T, d) * scale.astype(jnp.float32)).astype(x.dtype)


def apply_time_mix(p, x: jax.Array, cfg, state=None, return_state: bool = False,
                   use_chunked: bool = True):
    """RWKV6 attention-free time-mix.  x: (B, S, d).

    state (decode): {"shift": (B, d), "wkv": (B, H, dh, dh)}.
    """
    B, S, d = x.shape
    dh = rwkv6_head_dim(cfg)
    H = d // dh
    prev = state["shift"] if state is not None else None
    xx = _token_shift(x, prev)
    mixed = _ddlerp(p, x, xx)
    r = apply_linear(p["wr"], mixed["r"]).reshape(B, S, H, dh)
    k = apply_linear(p["wk"], mixed["k"]).reshape(B, S, H, dh)
    v = apply_linear(p["wv"], mixed["v"]).reshape(B, S, H, dh)
    g = apply_linear(p["wg"], mixed["g"])
    logw = _decay(p, mixed["w"]).reshape(B, S, H, dh)
    s0 = state["wkv"] if state is not None else None
    with jax.named_scope("wkv"):
        if use_chunked and wkv6_kernel_fits(S, H, dh):
            if s0 is None:
                s0 = jnp.zeros((B, H, dh, dh), jnp.float32)
            y, s_final = batch_map(ops.wkv6, r, k, v, logw, p["u"], s0,
                                   replicated=(4,))
        else:
            fn = wkv6_chunked if (use_chunked and S > 1) else wkv6_sequential
            y, s_final = fn(r, k, v, logw, p["u"], s0)
    y = _group_norm(y.reshape(B, S, d), p["ln_scale"], H)
    out = apply_linear(p["wo"], y * jax.nn.silu(g))
    if return_state:
        return out, {"shift": x[:, -1].astype(jnp.float32), "wkv": s_final}
    return out


def apply_channel_mix(p, x: jax.Array, cfg, state=None, return_state: bool = False):
    """RWKV6 channel-mix (squared-ReLU FFN with receptance gate)."""
    prev = state["shift"] if state is not None else None
    xx = _token_shift(x, prev)
    xk = x + (xx - x) * p["mu_ck"].astype(x.dtype)
    xr = x + (xx - x) * p["mu_cr"].astype(x.dtype)
    kk = jax.nn.relu(apply_linear(p["ck"], xk))
    vv = apply_linear(p["cv"], kk * kk)
    out = jax.nn.sigmoid(apply_linear(p["cr"], xr)) * vv
    if return_state:
        return out, {"shift": x[:, -1].astype(jnp.float32)}
    return out


def init_rwkv6_state(cfg, batch: int) -> Dict[str, jax.Array]:
    d = cfg.d_model
    dh = rwkv6_head_dim(cfg)
    H = d // dh
    return {
        "tm_shift": jnp.zeros((batch, d), jnp.float32),
        "wkv": jnp.zeros((batch, H, dh, dh), jnp.float32),
        "cm_shift": jnp.zeros((batch, d), jnp.float32),
    }
