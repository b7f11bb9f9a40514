"""The layer stack's remat split (``transformer.remat_split``): one scan where
the stashed carries weigh less than the group's weights, the two-level sqrt
split where they weigh more, and the same loss and gradients either way."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (SHAPES, TRAIN_MICROBATCHES, get_config,
                           reduced_config)
from repro.launch.steps import state_specs
from repro.models import init_params, loss_fn, transformer
from repro.models.model import forward
from repro.optim import adamw

DEEP = 12     # layers of the tiny stack: past the 9 a two-level split needs


def _deep_cfg():
    return dataclasses.replace(reduced_config("rwkv6-3b"), n_layers=DEEP)


def _layer_scans(jaxpr):
    """Lengths of the scans at the top of the forward, each with the
    lengths of the scans directly inside it."""
    def inner(jx):
        for e in jx.eqns:
            if e.primitive.name == "scan":
                yield e
            else:
                for v in e.params.values():
                    for j in v if isinstance(v, (list, tuple)) else (v,):
                        j = getattr(j, "jaxpr", j)
                        if hasattr(j, "eqns"):
                            yield from inner(j)
    return [(e.params["length"], sorted(
        s.params["length"] for s in inner(e.params["jaxpr"].jaxpr)))
        for e in inner(jaxpr)]


@pytest.mark.parametrize("seq, scans", [
    # 12 carries of 2 x 8 x 64 bf16 weigh far less than the stack's weights
    (8, [(DEEP, [1])]),
    # at 2 x 1024 they outweigh them: 4 super-layers of 3 layers, each
    # layer's WKV a scan over 16 chunks
    (1024, [(4, [3])]),
])
def test_deep_stack_splits_by_carries_against_weights(seq, scans):
    cfg = _deep_cfg()
    params = jax.eval_shape(lambda: init_params(cfg, 0))
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: forward(p, cfg, t))(params, tokens)
    assert _layer_scans(jaxpr.jaxpr) == scans


def _split(arch, batch, seq):
    cfg = get_config(arch)
    shapes, _ = state_specs(cfg, adamw.AdamWConfig())
    (gp,) = shapes["params"]["groups"]
    (_, n), = transformer.group_meta(cfg)
    carry = batch * seq * cfg.d_model * jnp.dtype(cfg.compute_dtype).itemsize
    weights = transformer._group_weight_bytes(gp, cfg)
    return (transformer.remat_split(n, carry, weights),
            transformer.remat_label(shapes["params"]["groups"], cfg, batch,
                                    seq))


def test_qwen_110b_train_4k_keeps_80_carries_to_18():
    """One of ``train_4k``'s microbatches, 64 rows of 4096: its 80 carries
    (344 GB) outweigh the stack's bf16 weights (217 GB)."""
    shape = SHAPES["train_4k"]
    rows = shape.global_batch // TRAIN_MICROBATCHES[("qwen1.5-110b",
                                                     "train_4k")]
    assert rows == 64
    (n_outer, n_inner), label = _split("qwen1.5-110b", rows, shape.seq_len)
    assert (n_outer, n_inner, label) == (10, 8, "10x8")
    assert n_outer + n_inner == 18


@pytest.mark.parametrize("arch, batch, seq, split, label", [
    # the rwkv6-3b-fsdp4 cell: 0.67 GB of carries against 5.53 GB of weights
    ("rwkv6-3b", 4, 1024, (32, 1), "32x1"),
    # four rows of 32k: 21.5 GB of carries
    ("rwkv6-3b", 4, 32768, (8, 4), "8x4"),
    # 23 units of two layers, a prime depth: one unit is left as a tail
    ("gemma2-27b", 64, 4096, (11, 2), "11x2+1"),
])
def test_whole_model_split(arch, batch, seq, split, label):
    assert _split(arch, batch, seq) == (split, label)


@pytest.mark.parametrize("n, split", [
    (4, (4, 1)),       # too shallow to split, however heavy the carries
    (32, (8, 4)),
    (23, (11, 2)),     # prime depth: a tail of one layer is left over
    (80, (10, 8)),
])
def test_split_of_heavy_carries(n, split):
    assert transformer.remat_split(n, carry_bytes=10, weight_bytes=1) == split


@pytest.fixture(scope="module")
def single_level():
    cfg = _deep_cfg()
    params = init_params(cfg, 0)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    assert transformer.remat_split(DEEP, 2 * 8 * cfg.d_model * 2,
                                   transformer._group_weight_bytes(
                                       params["groups"][0], cfg)) == (DEEP, 1)
    step = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, cfg, batch)))
    return cfg, params, batch, step(params)


@pytest.mark.parametrize("split", [(4, 3), (3, 4), (5, 2)])
def test_two_level_matches_single_level(single_level, split, monkeypatch):
    """Same loss and gradients from a two-level split (``(5, 2)`` leaves a
    tail of two layers) as from the single scan the tiny stack takes."""
    cfg, params, batch, (loss, grads) = single_level
    monkeypatch.setattr(transformer, "remat_split", lambda n, c, w: split)
    loss2, grads2 = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, cfg, batch)))(params)
    np.testing.assert_allclose(float(loss2), float(loss), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads2),
                    jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
