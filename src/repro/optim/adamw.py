"""AdamW from scratch (no optax dependency) with global-norm clipping.

Moments inherit the parameters' sharding (FSDP over the ``embed`` logical
axis per launch/sharding.py), which is exactly ZeRO-1: optimizer state is
partitioned, updates run shard-local, and GSPMD inserts the all-gathers the
forward needs.  ``moment_dtype="bfloat16"`` halves optimizer HBM at ~0 quality
cost for the first moment (kept fp32 for the second by default).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    moment_dtype: str = "float32"       # bf16 option: gradient-state compression
    warmup_steps: int = 100
    decay_steps: int = 10_000
    min_lr_ratio: float = 0.1


def schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    """Linear warmup + cosine decay to min_lr_ratio."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.decay_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos)


def init(params, cfg: AdamWConfig) -> Dict[str, Any]:
    mdt = jnp.dtype(cfg.moment_dtype)
    zeros = lambda p: jnp.zeros(p.shape, mdt)
    out = {"m": jax.tree_util.tree_map(zeros, params),
           "v": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                       params),
           "step": jnp.zeros((), jnp.int32)}
    leaves = jax.tree_util.tree_leaves(params)
    if leaves and any(l.dtype != jnp.float32 for l in leaves):
        # mixed precision: bf16 working weights (halves FSDP all-gather and
        # gradient all-reduce bytes) + fp32 master copy in the (ZeRO-1
        # sharded) optimizer state — EXPERIMENTS.md §Perf, mixtral hillclimb
        out["master"] = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32), params)
    return out


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def update(grads, opt_state, params, cfg: AdamWConfig
           ) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
    """One AdamW step. Returns (new_params, new_opt_state, metrics).  Its
    ops run under the named scope ``optimizer``."""
    with jax.named_scope("optimizer"):
        return _update(grads, opt_state, params, cfg)


def _update(grads, opt_state, params, cfg: AdamWConfig):
    step = opt_state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12))
    lr = schedule(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v, master):
        g = g.astype(jnp.float32) * scale
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * g * g
        mhat = m_new / bc1
        vhat = v_new / bc2
        base = p.astype(jnp.float32) if master is None else master
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * base
        p_new = base - lr * delta
        return (p_new.astype(p.dtype), m_new.astype(m.dtype), v_new,
                None if master is None else p_new)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(opt_state["m"])
    flat_v = treedef.flatten_up_to(opt_state["v"])
    has_master = "master" in opt_state
    flat_ma = (treedef.flatten_up_to(opt_state["master"]) if has_master
               else [None] * len(flat_p))
    out = [upd(p, g, m, v, ma) for p, g, m, v, ma
           in zip(flat_p, flat_g, flat_m, flat_v, flat_ma)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    new_state = {"m": new_m, "v": new_v, "step": step}
    if has_master:
        new_state["master"] = jax.tree_util.tree_unflatten(
            treedef, [o[3] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, new_state, metrics
