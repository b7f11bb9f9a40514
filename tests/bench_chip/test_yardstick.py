"""The benchmark's trace reduction, peaks table and quantiles, on a small
recorded trace; no chip needed."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "benchmarks", "chip"))

import yardstick  # noqa: E402

WINDOW = (0, 1000)


@pytest.fixture
def trace():
    with open(os.path.join(HERE, "data", "trace_two_chips.json")) as f:
        return yardstick.Trace.from_json(json.load(f))


def test_busy_is_the_union_of_operations_in_the_window(trace):
    chip0, chip1 = (yardstick.chip_times(ops, WINDOW)
                    for _, ops in sorted(trace.device_ops.items()))
    # chip 0: [0, 450) + [500, 700) + [950, 1000), the last op clipped;
    # chip 1: [100, 200) + a loop over [550, 950)
    assert chip0.busy_ns == 700
    assert chip1.busy_ns == 500


def test_exposed_collective_time_is_what_no_compute_covers(trace):
    # chip 1's reduce-scatter lies inside a loop's span: still exposed
    chip0, chip1 = (yardstick.chip_times(ops, WINDOW)
                    for _, ops in sorted(trace.device_ops.items()))
    assert (chip0.collective_ns, chip0.exposed_collective_ns) == (200, 150)
    assert (chip1.collective_ns, chip1.exposed_collective_ns) == (300, 300)


def test_top_ops_merge_numbered_instances_and_average_over_chips(trace):
    top = dict(yardstick.top_ops(trace, WINDOW))
    assert list(top) == ["fusion", "reduce-scatter", "all-gather",
                         "convolution"]
    assert top["fusion"] == pytest.approx(550 / 2 / 1e9)


def test_idle_gaps_are_named_by_the_innermost_host_span(trace):
    gaps = yardstick.idle_gaps(trace, WINDOW)
    assert gaps == [("host: flush_window", pytest.approx(250e-9)),
                    ("host: PjitFunction(train_step)", pytest.approx(50e-9))]
    # the span that marks the window names no gap
    trace.host_spans = [sp for sp in trace.host_spans
                        if sp[0] != "flush_window"]
    assert yardstick.idle_gaps(trace, WINDOW, marker="bench_window")[0] == \
        ("host: no span", pytest.approx(250e-9))


def test_interval_arithmetic():
    assert yardstick.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert yardstick.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert yardstick.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]


def test_a_chip_missing_from_the_peaks_table_is_an_error():
    assert yardstick.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        yardstick.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("q,want", [(0.0, 1.0), (0.5, 2.5), (0.9, 3.7),
                                    (1.0, 4.0)])
def test_quantile_interpolates_order_statistics(q, want):
    assert yardstick.quantile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_model_flops_are_six_per_parameter_and_token():
    assert yardstick.model_flops(513_146_880, 4096) == \
        pytest.approx(6 * 513_146_880 * 4096)


@pytest.fixture
def v5e_step():
    with open(os.path.join(HERE, "data", "trace_v5e_step.json")) as f:
        events = json.load(f)["events"]
    ops = [(yardstick.op_name(n), s, d) for n, s, d in events]
    return yardstick.Trace({"/device:TPU:0": ops}, [])


def test_operations_are_named_by_their_instruction():
    text = ("%fusion.806 = (bf16[4,512]{1,0:T(4,128)(2,1)S(1)}, f32[4,512,65536]"
            "{2,1,0:T(8,128)}) fusion(bf16[2560,65536] %all-gather.3)")
    assert yardstick.op_name(text) == "fusion.806"
    assert not yardstick.COLLECTIVE.search(yardstick.op_name(text))
    assert yardstick.COLLECTIVE.search("all-gather-start.2")


def test_a_recorded_v5e_step_reduces_to_its_leaf_operations(v5e_step):
    ops = v5e_step.device_ops["/device:TPU:0"]
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    ct = yardstick.chip_times(ops, (lo, hi))
    # nested operations (a loop and its body) count once
    assert ct.busy_ns <= hi - lo < sum(d for _, _, d in ops)
    assert ct.busy_ns > 0.5 * (hi - lo)
    assert ct.collective_ns == 0
    top = yardstick.top_ops(v5e_step, (lo, hi))
    assert len(top) == 10
    assert not any(yardstick.CONTAINER.match(name) for name, _ in top)
    assert any(n.startswith("while.") for n, _, _ in ops)
