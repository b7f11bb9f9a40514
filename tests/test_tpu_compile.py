"""Compiles for a described TPU v5e, no chip attached: the Pallas kernels at
real widths, the smoke configuration's full train step (rwkv6-3b at its
published width, 4 layers, batch 4 x seq 1024, as ``chip_smoke.py`` runs
it), and the whole model's step, 32 layers FSDP over four chips, as the
benchmark's ``rwkv6-3b-fsdp4.clean`` cell runs it.  These catch what
interpret mode cannot: tiling and alignment faults, VMEM limits, and a step
that does not fit the chip's memory."""
import contextlib
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.launch import steps as steps_lib
from repro.launch.hlo_analysis import Analyzer
from repro.models.model import input_specs
from repro.optim import adamw
from repro.perfdbg.attributes import device_peaks

SMOKE_LAYERS, SMOKE_BATCH, SMOKE_SEQ = 4, 4, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_dh128_seq1024(one_chip):
    q = jax.ShapeDtypeStruct((1, 1024, 8, 128), jnp.bfloat16,
                             sharding=one_chip)
    _compile(lambda q, k, v: ops.attention(q, k, v), q, q, q)


def _wkv6_shapes(one_chip):
    """rwkv6-3b's heads at the smoke step's batch and sequence, as the
    time-mix hands them over: r, k, v in bf16, the log-decay in f32."""
    x = jax.ShapeDtypeStruct((SMOKE_BATCH, SMOKE_SEQ, 40, 64), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=one_chip)
    u = jax.ShapeDtypeStruct((40, 64), jnp.float32, sharding=one_chip)
    return x, x, x, w, u


def test_wkv6_rwkv6_3b_heads_t1024(one_chip):
    compiled = _compile(lambda r, k, v, w, u: ops.wkv6(r, k, v, w, u),
                        *_wkv6_shapes(one_chip))
    assert "wkv6_fwd" in compiled.as_text()


def test_wkv6_backward_rwkv6_3b_heads_t1024(one_chip):
    def loss(r, k, v, w, u):
        y, s = ops.wkv6(r, k, v, w, u)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(s)
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                        *_wkv6_shapes(one_chip))
    assert "wkv6_bwd" in compiled.as_text()


def test_rglru_scan_width_4096(one_chip):
    a = jax.ShapeDtypeStruct((2, 1024, 4096), jnp.float32, sharding=one_chip)
    _compile(lambda a, b: ops.rglru_scan(a, b), a, a)


def _smoke_step(devices, layers=SMOKE_LAYERS):
    cfg = dataclasses.replace(get_config("rwkv6-3b"), n_layers=layers)
    opt = adamw.AdamWConfig(lr=3e-4, warmup_steps=5, decay_steps=10)
    mesh = Mesh(np.asarray(devices).reshape(len(devices), 1),
                ("data", "model"))
    bshapes = input_specs(cfg, SMOKE_BATCH, SMOKE_SEQ, "train")
    # the model asks the backend, which is this CPU, to choose its TPU path
    with mesh, pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        jitted, (st_shapes, _, _) = steps_lib.jit_train_step(
            cfg, opt, mesh, bshapes)
        return cfg, jitted.lower(st_shapes, bshapes).compile()


@pytest.fixture(scope="module")
def smoke_one_chip(topo):
    return _smoke_step(topo.devices[:1])


def test_smoke_train_step_fits_one_chip(smoke_one_chip, topo):
    cfg, compiled = smoke_one_chip
    ma = compiled.memory_analysis()
    # donated state aliases its outputs: arguments plus temporaries is the
    # step's footprint on the device
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < device_peaks(topo.devices[0].device_kind).hbm_bytes
    # the cost provider reads TPU HLO: layouts parsed, dots-as-convolutions
    # counted (about 1.07 x 6*N*T with the rematerialized forward)
    flops = Analyzer(compiled.as_text()).stats().flops
    model = 6.0 * cfg.active_params() * SMOKE_BATCH * SMOKE_SEQ
    assert 0.9 < flops / model < 1.5


def test_smoke_train_step_runs_the_wkv_kernels(smoke_one_chip):
    """The WKV kernels sit under the time-mix's scope in the forward, in
    the layer's recomputed forward and in the transposed computation."""
    _, compiled = smoke_one_chip
    calls = {m.group(1): m.group(2) for m in re.finditer(
        r"%(wkv6_(?:fwd|bwd)\.\d+) = .*custom_call_target=\"tpu_custom_call"
        r'.*op_name="([^"]*)"', compiled.as_text())}
    paths = sorted(calls.values())
    assert all("/mix/wkv/" in p for p in paths), paths
    assert any("wkv6_fwd" in p and "/jvp(layers)/" in p for p in paths)
    assert any("wkv6_fwd" in p and "transpose(jvp(layers))" in p
               for p in paths)
    assert any("wkv6_bwd" in p and "transpose(jvp(layers))" in p
               for p in paths)


def _kernel_calls(compiled, kernel):
    """How many custom calls of ``kernel`` the compiled step makes."""
    return len(re.findall(rf"^\s*%{kernel}\.\d+ = ", compiled.as_text(),
                          re.M))


def test_smoke_train_step_recomputes_the_forward_once(smoke_one_chip):
    """Four layers scan once: the forward, its one recomputation in the
    backward, and the backward kernel."""
    _, compiled = smoke_one_chip
    assert _kernel_calls(compiled, "wkv6_fwd") == 2
    assert _kernel_calls(compiled, "wkv6_bwd") == 1


def test_smoke_train_step_fsdp_four_chips(topo):
    """FSDP over (4, 1) at one row per chip: the TPU compiler refuses this
    step unless ``jit_train_step`` keeps async collective fusion out of
    while loops."""
    _, compiled = _smoke_step(topo.devices[:4])
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 4e9
    assert "all-gather" in compiled.as_text()
    # per chip, on its own rows of the batch
    assert re.search(r"wkv6_bwd\.\d+ = \(bf16\[1,1024,2560\]",
                     compiled.as_text())


# one v5e chip's memory_stats()["bytes_limit"] (a chip run), and the room
# the whole model's step leaves under it for the rest of the process: the
# benchmark's probes of the state, the next batch, the runtime
V5E_BYTES_LIMIT = 16_909_334_528
HEADROOM = 1e9


@pytest.fixture(scope="module")
def whole_four_chips(topo):
    """rwkv6-3b at its published 32 layers, FSDP over four chips, as the
    ``rwkv6-3b-fsdp4.clean`` cell runs it."""
    return _smoke_step(topo.devices[:4],
                       layers=get_config("rwkv6-3b").n_layers)


def test_whole_model_fsdp_four_chips_fits_with_headroom(whole_four_chips):
    cfg, compiled = whole_four_chips
    assert cfg.n_layers == 32
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < V5E_BYTES_LIMIT - HEADROOM, used


def test_whole_model_fsdp_four_chips_shards_the_work(whole_four_chips):
    """Each chip runs the WKV kernels on its own row of the batch, and the
    cost provider sees the step's collectives."""
    _, compiled = whole_four_chips
    text = compiled.as_text()
    for kernel in ("wkv6_fwd", "wkv6_bwd"):
        shapes = re.findall(rf"%{kernel}\.\d+ = \((\w+\[[\d,]*\])", text)
        assert shapes and set(shapes) == {"bf16[1,1024,2560]"}, shapes
    assert Analyzer(text).stats().total_collective_bytes > 0


def test_whole_model_fsdp_four_chips_recomputes_the_forward_once(
        whole_four_chips):
    """The 32 layers' carries (0.67 GB at 4 x 1024) weigh less than their
    weights in bf16 (5.53 GB), so the stack scans once: no two-level split,
    whose super-layers would run each layer's forward a third time."""
    _, compiled = whole_four_chips
    assert _kernel_calls(compiled, "wkv6_fwd") == 2
    assert _kernel_calls(compiled, "wkv6_bwd") == 1


def _program(hlo_text):
    """The module's computations with the metadata left out (op_name,
    source lines and the stack-frame tables) and instructions renumbered
    in order of appearance."""
    text = re.sub(r", metadata=\{[^}]*\}", "", hlo_text)
    lines = [ln for ln in text.splitlines()
             if ln.startswith((" ", "%", "ENTRY", "ROOT", "}", "HloModule"))]
    names = {}
    return [re.sub(r"%[\w.\-]+",
                   lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                   ln) for ln in lines]


def test_named_scopes_change_only_the_step_metadata(smoke_one_chip, topo,
                                                    monkeypatch):
    """The scopes reach the chip's compiled step as op_name metadata and
    change nothing else in it."""
    _, scoped = smoke_one_chip
    assert "/mix/wkv/" in scoped.as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, plain = _smoke_step(topo.devices[:1])
    assert "/mix/wkv/" not in plain.as_text()
    assert _program(scoped.as_text()) == _program(plain.as_text())
