"""Model API: param specs, init, loss, prefill, decode.

Covers decoder-only LMs (dense/MoE/hybrid/SSM), the VLM stub (pixtral:
patch embeddings replace the first ``n_patches`` token positions) and the
enc-dec audio stub (whisper: precomputed frame embeddings feed the encoder).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig
from .layers import (apply_embed, apply_linear, apply_logits, apply_norm,
                     build_params, embed_spec, linear_spec, logits_spec,
                     norm_spec, sinusoidal)
from .transformer import (cache_shapes, group_meta, init_cache, run_stack,
                          run_stack_decode, run_stack_prefill,
                          stack_group_spec)

LOSS_CHUNK = 512  # sequence-chunked cross-entropy (bounds logits memory)


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    spec: Dict[str, Any] = {
        "embed": embed_spec(cfg),
        "final_norm": norm_spec(cfg.d_model, cfg.norm),
        "logits": logits_spec(cfg),
        "groups": tuple(stack_group_spec(cfg, unit, n, cross=cfg.is_encdec)
                        for unit, n in group_meta(cfg)),
    }
    if cfg.is_encdec:
        # encoder: plain full-attention blocks, one group
        enc_cfg = cfg
        spec["enc_groups"] = (stack_group_spec(enc_cfg, ("global",),
                                               cfg.encoder_layers),)
        spec["enc_norm"] = norm_spec(cfg.d_model, cfg.norm)
        spec["frame_proj"] = linear_spec(cfg.d_model, cfg.d_model,
                                         ("embed", "embed2"))
    if cfg.frontend == "vision_stub":
        spec["patch_proj"] = linear_spec(cfg.d_model, cfg.d_model,
                                         ("embed", "embed2"))
    if cfg.param_dtype != "float32":
        import dataclasses as _dc
        spec = jax.tree_util.tree_map(
            lambda ps: _dc.replace(ps, dtype=cfg.param_dtype), spec,
            is_leaf=lambda x: hasattr(x, "init"))
    return spec


def init_params(cfg: ModelConfig, seed: int = 0):
    return build_params(param_specs(cfg), jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# Inputs
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, batch: int, seq: int,
                mode: str = "train") -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    i32 = jnp.int32
    cdt = jnp.dtype(cfg.compute_dtype)
    if mode in ("train", "prefill"):
        out = {"tokens": jax.ShapeDtypeStruct((batch, seq), i32)}
        if mode == "train":
            out["labels"] = jax.ShapeDtypeStruct((batch, seq), i32)
        if cfg.frontend == "vision_stub":
            out["patches"] = jax.ShapeDtypeStruct(
                (batch, cfg.n_patches, cfg.d_model), cdt)
        if cfg.is_encdec:
            out["frames"] = jax.ShapeDtypeStruct(
                (batch, cfg.encoder_seq, cfg.d_model), cdt)
        return out
    if mode == "decode":
        return {"tokens": jax.ShapeDtypeStruct((batch, 1), i32),
                "pos": jax.ShapeDtypeStruct((), i32),
                "cache": cache_shapes(cfg, batch, seq)}
    raise ValueError(mode)


def _embed_inputs(params, cfg: ModelConfig, tokens: jax.Array,
                  patches: Optional[jax.Array] = None,
                  pos_offset: int = 0) -> jax.Array:
    with jax.named_scope("embed"):
        x = apply_embed(params["embed"], tokens, cfg)
        if cfg.frontend == "vision_stub" and patches is not None:
            pe = apply_linear(params["patch_proj"], patches.astype(x.dtype))
            x = jnp.concatenate([pe, x[:, cfg.n_patches:]], axis=1)
        if not cfg.use_rope:
            S = tokens.shape[1]
            x = x + sinusoidal(S, cfg.d_model,
                               pos_offset).astype(x.dtype)[None]
        return x


def _encode(params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    x = apply_linear(params["frame_proj"], frames)
    x = x + sinusoidal(frames.shape[1], cfg.d_model).astype(x.dtype)[None]
    pos = jnp.arange(frames.shape[1])
    x = run_stack(params["enc_groups"], x, cfg, pos, causal=False)
    return apply_norm(params["enc_norm"], x, cfg.norm)


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens: jax.Array,
            patches: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None,
            remat: bool = True) -> jax.Array:
    """Returns final hidden states (B, S, d) — logits are computed chunked
    inside the loss to bound memory."""
    x = _embed_inputs(params, cfg, tokens, patches)
    enc = _encode(params, cfg, frames) if cfg.is_encdec else None
    pos = jnp.arange(tokens.shape[1])
    # the stack's own ops (the layer loop, stacking each layer's gradients)
    # are named by the region that holds the blocks
    with jax.named_scope("layers"):
        x = run_stack(params["groups"], x, cfg, pos, encoder_out=enc,
                      remat=remat)
    return apply_norm(params["final_norm"], x, cfg.norm)


def chunked_loss(params, cfg: ModelConfig, hidden: jax.Array,
                 labels: jax.Array) -> jax.Array:
    """Cross-entropy with sequence-chunked logits (never materializes the
    full (B, S, V) tensor; each chunk is rematerialized in the backward)."""
    from repro.runtime import constrain
    B, S, d = hidden.shape
    n = max(S // min(LOSS_CHUNK, S), 1)
    hs = hidden.reshape(B, n, S // n, d).transpose(1, 0, 2, 3)
    hs = constrain(hs, None, "batch")
    ls = constrain(labels.reshape(B, n, S // n).transpose(1, 0, 2),
                   None, "batch")

    @jax.checkpoint
    def chunk_nll(h, l):
        logits = apply_logits(params["logits"], params["embed"], h, cfg)
        logits = constrain(logits, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    def body(acc, inp):
        h, l = inp
        return acc + chunk_nll(h, l), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (hs, ls))
    return total / (B * S)


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            remat: bool = True) -> jax.Array:
    hidden = forward(params, cfg, batch["tokens"],
                     patches=batch.get("patches"),
                     frames=batch.get("frames"), remat=remat)
    with jax.named_scope("loss"):
        return chunked_loss(params, cfg, hidden, batch["labels"])


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, tokens: jax.Array, s_buf: int,
            patches: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None):
    """Forward pass that returns (last-position logits, decode cache)."""
    x = _embed_inputs(params, cfg, tokens, patches)
    enc = _encode(params, cfg, frames) if cfg.is_encdec else None
    pos = jnp.arange(tokens.shape[1])
    x, cache = run_stack_prefill(params["groups"], x, cfg, pos, s_buf,
                                 encoder_out=enc)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = apply_logits(params["logits"], params["embed"], x[:, -1:], cfg)
    return logits, cache


def decode_step(params, cfg: ModelConfig, tokens: jax.Array, pos: jax.Array,
                cache) -> Tuple[jax.Array, Any]:
    """One-token decode: tokens (B, 1), pos scalar -> (logits (B,1,V), cache)."""
    x = apply_embed(params["embed"], tokens, cfg)
    if not cfg.use_rope:
        x = x + _sin_at(pos, cfg.d_model).astype(x.dtype)[None, None]
    x, cache = run_stack_decode(params["groups"], cache, x, cfg, pos)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = apply_logits(params["logits"], params["embed"], x, cfg)
    return logits, cache


def _sin_at(pos: jax.Array, d: int) -> jax.Array:
    import math as _m
    half = d // 2
    freqs = jnp.exp(-_m.log(10_000.0)
                    * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = pos.astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
