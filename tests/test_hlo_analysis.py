"""Validate the trip-aware HLO cost analyzer against known programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import Analyzer, analyze, parse_module


def test_scan_matmul_trip_aware_flops():
    """A 10-iteration scan of (M,M)@(M,M): cost must count 10 bodies."""
    M = 256
    trips = 10

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=trips)
        return y

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((M, M), jnp.float32),
        jax.ShapeDtypeStruct((M, M), jnp.float32)).compile()
    stats = analyze(compiled.as_text())
    expected = 2 * M ** 3 * trips
    assert stats["flops"] == pytest.approx(expected, rel=0.2), \
        f"got {stats['flops']:.3e}, want ~{expected:.3e}"
    # builtin cost_analysis undercounts by ~trips (regression canary: if XLA
    # ever fixes this, the roofline layer should switch back)
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    builtin = float(dict(ca).get("flops", 0.0))
    assert builtin < expected / 2


def test_plain_matmul_flops_and_bytes():
    M, N, K = 128, 192, 64

    def f(a, b):
        return a @ b

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.float32)).compile()
    stats = analyze(compiled.as_text())
    assert stats["flops"] == pytest.approx(2 * M * N * K, rel=0.1)
    io_bytes = 4 * (M * K + K * N + M * N)
    assert stats["bytes"] == pytest.approx(io_bytes, rel=0.5)


def test_parse_module_finds_entry():
    def f(x):
        return jnp.sin(x) * 2

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((32,), jnp.float32)).compile()
    hlo = compiled.as_text()
    comps = parse_module(hlo)
    assert comps, "no computations parsed"
    a = Analyzer(hlo)
    assert a.entry in comps


def test_collective_bytes_spmd():
    """psum over 4 host devices must show up as all-reduce bytes x shape."""
    import os
    if jax.device_count() < 2:
        pytest.skip("needs >1 device (run under dryrun env)")


def test_stats_by_computation_public_api():
    """Per-computation map: covers every parsed computation, entry equals
    stats(), and as_dict carries the pre-summed total_collective_bytes."""
    M, trips = 64, 5

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=trips)
        return y

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((M, M), jnp.float32),
        jax.ShapeDtypeStruct((M, M), jnp.float32)).compile()
    a = Analyzer(compiled.as_text())
    by_comp = a.stats_by_computation()
    assert set(by_comp) == set(a.comps)
    assert by_comp[a.entry].flops == a.stats().flops
    d = by_comp[a.entry].as_dict()
    assert d["total_collective_bytes"] == sum(d["collective_bytes"].values())
    # the body is counted once in its own entry, trips times in the entry's
    body_flops = max(s.flops for n, s in by_comp.items() if n != a.entry)
    assert a.stats().flops == pytest.approx(trips * body_flops, rel=0.35)


def test_nested_scan_multiplies():
    M = 64
    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ w), None
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((M, M), jnp.float32),
        jax.ShapeDtypeStruct((M, M), jnp.float32)).compile()
    stats = analyze(compiled.as_text())
    expected = 2 * M ** 3 * 4 * 5
    assert stats["flops"] == pytest.approx(expected, rel=0.25)


# TPU HLO as the TPU compiler prints it: tiled layouts whose parens look
# like calls, and dot_generals rewritten as convolutions
TPU_HLO = """HloModule jit_f, is_scheduled=true

%fused_computation (param_0: bf16[4,512,256], param_1: bf16[256,1024,1]) -> bf16[4,512,1024] {
  %param_0 = bf16[4,512,256]{2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1 = bf16[256,1024,1]{1,0,2:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.1 = bf16[4,512,1024]{2,1,0:T(8,128)(2,1)} convolution(%param_0, %param_1), window={size=1}, dim_labels=0bf_io0->0bf
}

ENTRY %main.9 (a: bf16[4,512,256], b: bf16[256,1024,1]) -> bf16[4,512,1024] {
  %a = bf16[4,512,256]{2,1,0:T(8,128)(2,1)} parameter(0)
  %b = bf16[256,1024,1]{1,0,2:T(8,128)(2,1)} parameter(1)
  %step = s32[]{:T(128)} constant(0)
  ROOT %fusion.1 = bf16[4,512,1024]{2,1,0:T(8,128)(2,1)} fusion(%a, %b), kind=kOutput, calls=%fused_computation
}
"""


def test_tpu_layouts_and_dot_as_convolution():
    a = Analyzer(TPU_HLO)
    assert a.entry == "main.9"
    assert [op.opcode for op in a.comps["main.9"].ops] == [
        "parameter", "parameter", "constant", "fusion"]
    st = a.stats()
    assert st.flops == 2 * 4 * 512 * 1024 * 256
    assert st.bytes == 2 * (4 * 512 * 256 + 256 * 1024 + 4 * 512 * 1024)


@pytest.mark.parametrize("pad,lhs_dil,stride", [
    ((0, 0), 1, 1), ((2, 1), 1, 1), ((1, 1), 2, 1), ((3, 0), 3, 2)])
def test_convolution_counts_only_valid_taps(pad, lhs_dil, stride):
    """Padding and lhs-dilation holes cost no multiply-adds: the count is
    the (output position, tap) pairs that land on a real input element."""
    B, N, C, O, W = 2, 9, 8, 16, 4
    def f(x, k):
        return jax.lax.conv_general_dilated(
            x, k, window_strides=(stride,), padding=(pad,),
            lhs_dilation=(lhs_dil,), dimension_numbers=("NWC", "WIO", "NWC"))
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((B, N, C), jnp.float32),
        jax.ShapeDtypeStruct((W, C, O), jnp.float32)).compile()
    # brute force over the dilated, padded input
    n_dil = (N - 1) * lhs_dil + 1
    m = (n_dil + sum(pad) - W) // stride + 1
    pairs = sum(1 for o in range(m) for t in range(W)
                if 0 <= o * stride + t - pad[0] < n_dil
                and (o * stride + t - pad[0]) % lhs_dil == 0)
    assert analyze(compiled.as_text())["flops"] == pytest.approx(
        2 * B * O * C * pairs, rel=0.05)
