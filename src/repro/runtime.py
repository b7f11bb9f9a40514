"""Trace-time sharding context.

Model code is mesh-agnostic; step builders activate a (mesh, rules) context
while tracing, and ``constrain(x, *logical_axes)`` becomes a
``with_sharding_constraint`` resolving logical axes through
``repro.launch.sharding``.  Outside a context (unit tests, single-device
smoke runs) it is a no-op.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Tuple

import jax

_CTX: contextvars.ContextVar = contextvars.ContextVar("repro_sharding_ctx",
                                                      default=None)


@contextlib.contextmanager
def sharding_context(mesh, rules: Optional[dict] = None):
    token = _CTX.set((mesh, rules))
    try:
        yield
    finally:
        _CTX.reset(token)


def active() -> bool:
    return _CTX.get() is not None


def constrain(x: jax.Array, *logical_axes) -> jax.Array:
    """Pin ``x``'s sharding by logical axis names (None = replicated dim).
    Trailing dims may be omitted (treated as None)."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    from jax.sharding import NamedSharding
    from repro.launch.sharding import resolve_spec
    axes = tuple(logical_axes) + (None,) * (x.ndim - len(logical_axes))
    spec = resolve_spec(x.shape, axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_map(fn, *args, replicated: Tuple[int, ...] = ()):
    """``fn(*args)``, per shard of the batch where the context's mesh has
    more than one device: under ``shard_map`` over the mesh axes the
    ``batch`` logical axis resolves to, so that GSPMD neither gathers nor
    replicates the operands of a call it cannot partition (a Pallas
    kernel).  Arguments at the positions in ``replicated`` go whole to
    every shard; the others, and every output, are split on dim 0."""
    ctx = _CTX.get()
    if ctx is None or ctx[0].size == 1:
        return fn(*args)
    mesh, rules = ctx
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import resolve_spec
    spec = resolve_spec(args[0].shape[:1], ("batch",), mesh, rules)
    if not spec:
        return fn(*args)
    in_specs = tuple(P() if i in replicated else spec
                     for i in range(len(args)))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=spec,
                         check_vma=False)(*args)
