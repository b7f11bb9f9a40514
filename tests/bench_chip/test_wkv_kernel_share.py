"""The reader of ``step.wkv_kernel_share`` on small recorded-style traces:
the known share where the WKV kernels run under the ``wkv`` scope, 0
where the scope holds none (the jnp scan of the recorded span trace),
nothing without a trace or without the scope; no chip needed."""
import importlib.util
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "..", "..", "benchmarks", "chip")
sys.path.insert(0, BENCH)

import spans  # noqa: E402
import yardstick  # noqa: E402


def load_reader():
    spec = importlib.util.spec_from_file_location(
        "metric_step_wkv_kernel_share",
        os.path.join(BENCH, "metrics", "step.wkv_kernel_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def recorded(name, window=(0, 1000), steps=2):
    with open(os.path.join(HERE, "data", name)) as f:
        d = json.load(f)
    return types.SimpleNamespace(
        trace=yardstick.Trace.from_json(d), trace_window=window,
        traced_steps=steps, program_trace=spans.ProgramTrace.from_json(d))


def test_the_kernels_share_of_the_scope():
    """Under wkv: the two forwards and the backward (200 + 180 + 400, the
    second forward.17 clipped at the window's end to 10), a fusion and a
    reduce of the scope (50 is not: fusion.3 is mix's), and a copy that
    carries the kernel's path but is not its instruction; the loop is a
    container, and fusion.9 a kernel outside the scope."""
    kernels = 200 + 180 + 400 + 10
    scope = kernels + 20 + 10
    assert load_reader().read(recorded("trace_wkv_kernels.json")) == \
        pytest.approx(100.0 * kernels / scope, rel=1e-12)


def test_a_scope_with_no_kernel_reads_zero():
    assert load_reader().read(recorded("trace_spans.json")) == 0.0


def test_without_a_trace_or_the_scope_it_reads_nothing():
    read = load_reader().read
    none = types.SimpleNamespace(trace=None, trace_window=(0, 0),
                                 traced_steps=0)
    assert read(none) is None
    run = recorded("trace_wkv_kernels.json")
    run.program_trace = spans.ProgramTrace.from_json({"op_scopes": {
        "wkv6_fwd.17": "jit(train_step)/wkv6_fwd/pallas_call"}})
    assert read(run) is None


@pytest.mark.parametrize("name,path,kernel", [
    ("wkv6_bwd.10", "a/wkv/jit(wkv6)/wkv/wkv6_bwd/pallas_call", True),
    ("wkv6_bwd", "wkv6_bwd/pallas_call", True),
    ("copy.18", "a/wkv/wkv6_bwd/pallas_call", False),
    ("wkv6_fwd.3", "a/wkv/wkv6_bwd/pallas_call", False),
    ("wkv6_fwd.3", "a/wkv/wkv6_fwd/pallas_call/convert", False),
])
def test_a_kernel_is_the_instruction_named_for_its_pallas_call(name, path,
                                                               kernel):
    assert load_reader().is_kernel(name, path) is kernel
