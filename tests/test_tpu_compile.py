"""Compiles for a described TPU v5e, no chip attached: the Pallas kernels at
real widths and the smoke configuration's full train step (rwkv6-3b at its
published width, 4 layers, batch 4 x seq 1024, as ``chip_smoke.py`` runs
it).  These catch what interpret mode cannot: tiling and alignment faults,
VMEM limits, and a step that does not fit the chip's memory."""
import dataclasses
import os

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


def test_wkv6_rwkv6_3b_heads_t1024(one_chip):
    x = jax.ShapeDtypeStruct((1, 1024, 40, 64), jnp.bfloat16,
                             sharding=one_chip)
    u = jax.ShapeDtypeStruct((40, 64), jnp.float32, sharding=one_chip)
    _compile(lambda r, k, v, w, u: ops.wkv6(r, k, v, w, u), x, x, x, x, u)


def test_rglru_scan_width_4096(one_chip):
    a = jax.ShapeDtypeStruct((2, 1024, 4096), jnp.float32, sharding=one_chip)
    _compile(lambda a, b: ops.rglru_scan(a, b), a, a)


def _smoke_step(devices):
    cfg = dataclasses.replace(get_config("rwkv6-3b"), n_layers=SMOKE_LAYERS)
    opt = adamw.AdamWConfig(lr=3e-4, warmup_steps=5, decay_steps=10)
    mesh = Mesh(np.asarray(devices).reshape(len(devices), 1),
                ("data", "model"))
    bshapes = input_specs(cfg, SMOKE_BATCH, SMOKE_SEQ, "train")
    with mesh:
        jitted, (st_shapes, _, _) = steps_lib.jit_train_step(
            cfg, opt, mesh, bshapes)
        return cfg, jitted.lower(st_shapes, bshapes).compile()


def test_smoke_train_step_fits_one_chip(topo):
    cfg, compiled = _smoke_step(topo.devices[:1])
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


def test_smoke_train_step_fsdp_four_chips(topo):
    """FSDP over (4, 1) at one row per chip: the TPU compiler refuses this
    step unless ``jit_train_step`` keeps async collective fusion out of
    while loops."""
    _, compiled = _smoke_step(topo.devices[:4])
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 4e9
    assert "all-gather" in compiled.as_text()
