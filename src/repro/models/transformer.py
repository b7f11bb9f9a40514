"""Pattern-based transformer stack with scan-over-superblocks + remat.

Layers are grouped into repeating *units* (``cfg.block_pattern``), each unit's
parameters stacked along a leading ``layers`` axis and iterated with
``lax.scan`` (keeps HLO size O(1) in depth); leftover layers form a second,
shorter group.  ``jax.checkpoint`` around the scan body gives per-superblock
rematerialization.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.runtime import constrain

from .config import ModelConfig
from .layers import (apply_linear, apply_mlp, apply_norm, attention_block,
                     attention_decode, linear_spec, mlp_spec, norm_spec,
                     attention_spec, stack_spec_tree)
from .moe import apply_moe, moe_spec
from .rglru import apply_rglru, init_rglru_state, rglru_decode, rglru_spec
from .rwkv6 import (apply_channel_mix, apply_time_mix, init_rwkv6_state,
                    rwkv6_head_dim, rwkv6_spec)

REMAT_POLICY = jax.checkpoint_policies.nothing_saveable


# ---------------------------------------------------------------------------
# Group layout
# ---------------------------------------------------------------------------

def group_meta(cfg: ModelConfig) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
    """((unit kinds, n_repeats), ...) covering cfg.n_layers in order."""
    unit = cfg.block_pattern
    n_full, leftover = divmod(cfg.n_layers, len(unit))
    groups: List[Tuple[Tuple[str, ...], int]] = []
    if n_full:
        groups.append((unit, n_full))
    if leftover:
        groups.append((unit[:leftover], 1))
    return tuple(groups)


def _attn_kind(kind: str) -> bool:
    return kind in ("global", "local", "moe_global", "moe_local")


def block_spec(cfg: ModelConfig, kind: str, cross: bool = False) -> Dict[str, Any]:
    spec: Dict[str, Any] = {"ln1": norm_spec(cfg.d_model, cfg.norm),
                            "ln2": norm_spec(cfg.d_model, cfg.norm)}
    if cfg.post_norm:
        spec["post1"] = norm_spec(cfg.d_model, cfg.norm)
        spec["post2"] = norm_spec(cfg.d_model, cfg.norm)
    if _attn_kind(kind):
        spec["attn"] = attention_spec(cfg)
        if kind.startswith("moe"):
            spec["moe"] = moe_spec(cfg)
        else:
            spec["mlp"] = mlp_spec(cfg)
        if cross:
            spec["cross"] = attention_spec(cfg)
            spec["ln_cross"] = norm_spec(cfg.d_model, cfg.norm)
    elif kind == "rec":
        spec["rec"] = rglru_spec(cfg)
        spec["mlp"] = mlp_spec(cfg)
    elif kind == "rwkv":
        spec["tm"] = rwkv6_spec(cfg)
    else:  # pragma: no cover
        raise ValueError(kind)
    return spec


def stack_group_spec(cfg: ModelConfig, unit: Sequence[str], n: int,
                     cross: bool = False) -> Dict[str, Any]:
    return {f"pos{i}": stack_spec_tree(block_spec(cfg, kind, cross), n)
            for i, kind in enumerate(unit)}


# ---------------------------------------------------------------------------
# Forward blocks (training / prefill)
# ---------------------------------------------------------------------------

def _maybe_post(p, h, cfg, name):
    return apply_norm(p[name], h, cfg.norm) if cfg.post_norm else h


def block_forward(kind: str, p, x: jax.Array, cfg: ModelConfig,
                  positions: jax.Array,
                  encoder_out: Optional[jax.Array] = None,
                  causal: bool = True,
                  collect_cache: Optional[int] = None):
    """Returns (x, cache_dict_or_None).  ``collect_cache``: target KV buffer
    length (prefill) — None during training.  The mixer and the FFN run
    under the named scopes ``mix`` and ``ffn`` (the step's region-tree
    names), which reach the compiled ops' ``op_name``."""
    cache: Dict[str, jax.Array] = {}
    window = cfg.window if kind.endswith("local") or kind == "local" else 0
    if _attn_kind(kind):
        with jax.named_scope("mix"):
            h_in = apply_norm(p["ln1"], x, cfg.norm)
            if collect_cache is None:
                h = attention_block(p["attn"], h_in, cfg, positions=positions,
                                    window=window, causal=causal)
            else:
                h, kv = _attention_with_cache(p["attn"], h_in, cfg, positions,
                                              window, collect_cache)
                cache.update(kv)
            x = x + _maybe_post(p, h, cfg, "post1")
            if "cross" in p:
                hc = attention_block(p["cross"],
                                     apply_norm(p["ln_cross"], x, cfg.norm),
                                     cfg, positions=positions,
                                     encoder_out=encoder_out)
                x = x + hc
                if collect_cache is not None:
                    B, Se = encoder_out.shape[0], encoder_out.shape[1]
                    K, dh = cfg.n_kv_heads, cfg.d_head
                    cache["cross_k"] = apply_linear(
                        p["cross"]["wk"], encoder_out).reshape(B, Se, K, dh)
                    cache["cross_v"] = apply_linear(
                        p["cross"]["wv"], encoder_out).reshape(B, Se, K, dh)
        with jax.named_scope("ffn"):
            h2_in = apply_norm(p["ln2"], x, cfg.norm)
            if kind.startswith("moe"):
                h2 = apply_moe(p["moe"], h2_in, cfg)
            else:
                h2 = apply_mlp(p["mlp"], h2_in, cfg)
            x = x + _maybe_post(p, h2, cfg, "post2")
    elif kind == "rec":
        with jax.named_scope("mix"):
            h_in = apply_norm(p["ln1"], x, cfg.norm)
            if collect_cache is None:
                h = apply_rglru(p["rec"], h_in, cfg)
            else:
                h, st = apply_rglru(p["rec"], h_in, cfg, return_state=True)
                cache.update(st)
            x = x + h
        with jax.named_scope("ffn"):
            x = x + apply_mlp(p["mlp"], apply_norm(p["ln2"], x, cfg.norm),
                              cfg)
    elif kind == "rwkv":
        with jax.named_scope("mix"):
            h_in = apply_norm(p["ln1"], x, cfg.norm)
            if collect_cache is None:
                h = apply_time_mix(p["tm"], h_in, cfg)
            else:
                h, st = apply_time_mix(p["tm"], h_in, cfg, return_state=True)
                cache["tm_shift"], cache["wkv"] = st["shift"], st["wkv"]
            x = x + h
        with jax.named_scope("ffn"):
            c_in = apply_norm(p["ln2"], x, cfg.norm)
            if collect_cache is None:
                h2 = apply_channel_mix(p["tm"], c_in, cfg)
            else:
                h2, st2 = apply_channel_mix(p["tm"], c_in, cfg,
                                            return_state=True)
                cache["cm_shift"] = st2["shift"]
            x = x + h2
    return x, (cache or None)


def _attention_with_cache(p, x, cfg, positions, window, s_buf):
    """Prefill attention that also emits the KV cache buffer."""
    from .layers import mha, rope as rope_fn  # local import to avoid cycle
    B, S, _ = x.shape
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = apply_linear(p["wq"], x).reshape(B, S, H, dh)
    k = apply_linear(p["wk"], x).reshape(B, S, K, dh)
    v = apply_linear(p["wv"], x).reshape(B, S, K, dh)
    if cfg.use_rope:
        q = rope_fn(q, positions, cfg.rope_theta)
        k = rope_fn(k, positions, cfg.rope_theta)
    out = mha(q, k, v, causal=True, window=window, softcap=cfg.attn_softcap,
              scale=cfg.query_scale, pad_heads=cfg.pad_heads)
    y = apply_linear(p["wo"], out.reshape(B, S, H * dh))
    if window and window < s_buf:
        # ring buffer holding the last `window` positions at slot p % window
        W = window
        idx = (S - W + jnp.arange(W)) % W
        kc = jnp.zeros((B, W, K, dh), k.dtype).at[:, idx].set(k[:, S - W:])
        vc = jnp.zeros((B, W, K, dh), v.dtype).at[:, idx].set(v[:, S - W:])
    else:
        pad = s_buf - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return y, {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# Decode blocks (single token)
# ---------------------------------------------------------------------------

def block_decode(kind: str, p, x: jax.Array, cache, cfg: ModelConfig,
                 pos: jax.Array,
                 encoder_cache: Optional[Dict[str, jax.Array]] = None):
    window = cfg.window if kind.endswith("local") or kind == "local" else 0
    new_cache = dict(cache)
    if _attn_kind(kind):
        h_in = apply_norm(p["ln1"], x, cfg.norm)
        h, kv = attention_decode(p["attn"], h_in,
                                 {"k": cache["k"], "v": cache["v"]}, cfg,
                                 pos=pos, window=window)
        new_cache["k"], new_cache["v"] = kv["k"], kv["v"]
        x = x + _maybe_post(p, h, cfg, "post1")
        if "cross" in p:
            from .layers import mha_decode
            B = x.shape[0]
            H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
            hc_in = apply_norm(p["ln_cross"], x, cfg.norm)
            q = apply_linear(p["cross"]["wq"], hc_in).reshape(B, 1, H, dh)
            enc_len = jnp.asarray(cache["cross_k"].shape[1], jnp.int32)
            out = mha_decode(q, cache["cross_k"], cache["cross_v"],
                             k_len=enc_len, scale=cfg.query_scale)
            x = x + apply_linear(p["cross"]["wo"], out.reshape(B, 1, H * dh))
        h2_in = apply_norm(p["ln2"], x, cfg.norm)
        if kind.startswith("moe"):
            h2 = apply_moe(p["moe"], h2_in, cfg)
        else:
            h2 = apply_mlp(p["mlp"], h2_in, cfg)
        x = x + _maybe_post(p, h2, cfg, "post2")
    elif kind == "rec":
        h_in = apply_norm(p["ln1"], x, cfg.norm)
        h, st = rglru_decode(p["rec"], h_in, cfg,
                             {"h": cache["h"], "conv": cache["conv"]})
        new_cache["h"], new_cache["conv"] = st["h"], st["conv"]
        x = x + h
        x = x + apply_mlp(p["mlp"], apply_norm(p["ln2"], x, cfg.norm), cfg)
    elif kind == "rwkv":
        h_in = apply_norm(p["ln1"], x, cfg.norm)
        h, st = apply_time_mix(p["tm"], h_in, cfg,
                               state={"shift": cache["tm_shift"],
                                      "wkv": cache["wkv"]},
                               return_state=True, use_chunked=False)
        new_cache["tm_shift"], new_cache["wkv"] = st["shift"], st["wkv"]
        x = x + h
        c_in = apply_norm(p["ln2"], x, cfg.norm)
        h2, st2 = apply_channel_mix(p["tm"], c_in, cfg,
                                    state={"shift": cache["cm_shift"]},
                                    return_state=True)
        new_cache["cm_shift"] = st2["shift"]
        x = x + h2
    return x, new_cache


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

def layer_cache_shape(cfg: ModelConfig, kind: str, batch: int, s_buf: int,
                      cross: bool = False) -> Dict[str, Any]:
    """ShapeDtype specs for one layer's decode cache."""
    K, dh = cfg.n_kv_heads, cfg.d_head
    cdt = jnp.dtype(cfg.compute_dtype)
    window = cfg.window if kind.endswith("local") or kind == "local" else 0
    out: Dict[str, Any] = {}
    if _attn_kind(kind):
        W = min(window, s_buf) if window else s_buf
        out["k"] = jax.ShapeDtypeStruct((batch, W, K, dh), cdt)
        out["v"] = jax.ShapeDtypeStruct((batch, W, K, dh), cdt)
        if cross:
            out["cross_k"] = jax.ShapeDtypeStruct((batch, cfg.encoder_seq, K, dh), cdt)
            out["cross_v"] = jax.ShapeDtypeStruct((batch, cfg.encoder_seq, K, dh), cdt)
    elif kind == "rec":
        rw = cfg.rnn_width or cfg.d_model
        out["h"] = jax.ShapeDtypeStruct((batch, rw), jnp.float32)
        out["conv"] = jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, rw), jnp.float32)
    elif kind == "rwkv":
        d = cfg.d_model
        dh6 = rwkv6_head_dim(cfg)
        out["tm_shift"] = jax.ShapeDtypeStruct((batch, d), jnp.float32)
        out["wkv"] = jax.ShapeDtypeStruct((batch, d // dh6, dh6, dh6), jnp.float32)
        out["cm_shift"] = jax.ShapeDtypeStruct((batch, d), jnp.float32)
    return out


def cache_shapes(cfg: ModelConfig, batch: int, s_buf: int) -> Dict[str, Any]:
    """Full decode-cache spec tree (grouped/stacked to match scan layout)."""
    cross = cfg.is_encdec
    groups = []
    for unit, n in group_meta(cfg):
        g = {}
        for i, kind in enumerate(unit):
            per = layer_cache_shape(cfg, kind, batch, s_buf, cross)
            g[f"pos{i}"] = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((n, *s.shape), s.dtype), per)
        groups.append(g)
    return {"groups": tuple(groups)}


def init_cache(cfg: ModelConfig, batch: int, s_buf: int):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  cache_shapes(cfg, batch, s_buf))


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------

def _split_factor(n: int) -> int:
    """Largest divisor of n that is <= sqrt(n) (two-level remat split)."""
    best = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            best = d
        d += 1
    return best


def remat_split(n: int, carry_bytes: int, weight_bytes: int
                ) -> Tuple[int, int]:
    """(n_outer, n_inner) of a scanned group of ``n`` layers under remat:
    ``(n, 1)`` scans once, each layer checkpointed; ``n_inner > 1`` is the
    two-level split, ``n_outer`` checkpointed super-layers of ``n_inner``
    layers each, and ``n - n_outer * n_inner`` tail layers scanned once.

    Two-level stashes ``n_outer + n_inner`` residual-stream carries for the
    backward instead of ``n``, but recomputes each super-layer's inner
    forward once more: one full extra forward of the group, and under FSDP
    one more gather of its weights.  It pays only where the carries are a
    large share of the step's memory, so it is taken where the ``n`` carries
    (``carry_bytes`` each) outweigh one compute-dtype copy of the group's
    weights (``weight_bytes``), the bytes the extra forward brings in again.
    Both sides shrink alike under FSDP and data parallelism (weights split
    over ``data``, carries over ``batch``), so global shapes decide it.
    Groups under 9 layers always scan once."""
    if n < 9 or n * carry_bytes <= weight_bytes:
        return n, 1
    n_inner = _split_factor(n)
    if n_inner == 1:
        # prime depth (e.g. gemma2's 23 units): split off a tail so the
        # main run still gets the sqrt-remat treatment
        n_inner = _split_factor(n - 1)
    return n // n_inner, n_inner


def _group_weight_bytes(gp, cfg: ModelConfig) -> int:
    """One compute-dtype copy of a group's weights, in bytes."""
    return (sum(a.size for a in jax.tree_util.tree_leaves(gp))
            * jnp.dtype(cfg.compute_dtype).itemsize)


def remat_label(params_groups, cfg: ModelConfig, batch: int, seq: int) -> str:
    """The split ``run_stack`` compiles for each group, as ``n_outer x
    n_inner`` (``+tail`` where a tail is left), groups joined by ``,``: for
    example ``32x1`` or ``8x4``.  Takes the parameters or their shapes."""
    carry = batch * seq * cfg.d_model * jnp.dtype(cfg.compute_dtype).itemsize
    out = []
    for gp, (_, n) in zip(params_groups, group_meta(cfg)):
        o, i = remat_split(n, carry, _group_weight_bytes(gp, cfg))
        tail = n - o * i
        out.append(f"{o}x{i}" + (f"+{tail}" if tail else ""))
    return ",".join(out)


def run_stack(params_groups, x: jax.Array, cfg: ModelConfig,
              positions: jax.Array, encoder_out: Optional[jax.Array] = None,
              causal: bool = True, remat: bool = True) -> jax.Array:
    """Training/prefill-without-cache forward through all groups.

    Under remat each layer is checkpointed, and ``remat_split`` chooses per
    group between one scan and a two-level scan: an outer checkpointed scan
    over n_outer super-iterations, each an inner scan of n_inner layers.
    Two-level stashes n_outer + n_inner residual-stream carries instead of
    n (sqrt(N) activation memory, the classic recursive-checkpoint trade;
    e.g. qwen-110B at train_4k: 80 carries -> 18) at the price of a third
    forward, so it is taken only where the carries outweigh the group's
    weights; rwkv6-3b at 4 x 1024 scans its 32 layers once."""
    for g, (unit, n) in enumerate(group_meta(cfg)):
        gp = params_groups[g]

        def body(carry, layer_p, unit=unit):
            h = carry
            for i, kind in enumerate(unit):
                h, _ = block_forward(kind, layer_p[f"pos{i}"], h, cfg,
                                     positions, encoder_out, causal)
                h = constrain(h, "batch")
            return h, None

        n_outer, n_inner = 1, 1
        if remat:
            body = jax.checkpoint(body, policy=REMAT_POLICY)
            n_outer, n_inner = remat_split(
                n, x.size * x.dtype.itemsize, _group_weight_bytes(gp, cfg))
        if n_inner > 1:
            n_main = n_outer * n_inner

            def slice_main(a):
                return a[:n_main].reshape(n_outer, n_inner, *a.shape[1:])

            gp2 = jax.tree_util.tree_map(slice_main, gp)

            def outer(carry, pslice, body=body):
                h, _ = lax.scan(body, carry, pslice)
                return h, None

            outer = jax.checkpoint(outer, policy=REMAT_POLICY)
            x, _ = lax.scan(outer, x, gp2)
            if n_main < n:
                tail = jax.tree_util.tree_map(lambda a: a[n_main:], gp)
                x, _ = lax.scan(body, x, tail)
        else:
            x, _ = lax.scan(body, x, gp)
    return x


def run_stack_prefill(params_groups, x: jax.Array, cfg: ModelConfig,
                      positions: jax.Array, s_buf: int,
                      encoder_out: Optional[jax.Array] = None):
    """Prefill forward that also returns the grouped decode cache."""
    groups_cache = []
    for g, (unit, n) in enumerate(group_meta(cfg)):
        gp = params_groups[g]

        def body(carry, layer_p, unit=unit):
            h = carry
            caches = {}
            for i, kind in enumerate(unit):
                h, c = block_forward(kind, layer_p[f"pos{i}"], h, cfg,
                                     positions, encoder_out,
                                     collect_cache=s_buf)
                caches[f"pos{i}"] = c or {}
            return h, caches

        x, caches = lax.scan(body, x, gp)
        groups_cache.append(caches)
    return x, {"groups": tuple(groups_cache)}


def run_stack_decode(params_groups, cache, x: jax.Array, cfg: ModelConfig,
                     pos: jax.Array):
    """Single-token decode through all groups, returning the updated cache."""
    new_groups = []
    for g, (unit, n) in enumerate(group_meta(cfg)):
        gp = params_groups[g]
        gc = cache["groups"][g]

        def body(carry, inp, unit=unit):
            h = carry
            layer_p, layer_c = inp
            new_c = {}
            for i, kind in enumerate(unit):
                h, c = block_decode(kind, layer_p[f"pos{i}"], h,
                                    layer_c[f"pos{i}"], cfg, pos)
                new_c[f"pos{i}"] = c
            return h, new_c

        x, new_c = lax.scan(body, x, (gp, gc))
        new_groups.append(new_c)
    return x, {"groups": tuple(new_groups)}
