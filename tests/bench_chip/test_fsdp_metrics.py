"""The readers of the ``fsdp.*`` metrics on a small recorded-style trace of
two chips (``data/trace_collectives.json``): each reader's known value,
the asynchronous collectives counted from start to done, and nothing
without a trace or, for the rate, without the step span's bytes; no chip
needed."""
import importlib.util
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "..", "..", "benchmarks", "chip")
sys.path.insert(0, BENCH)

import collectives  # noqa: E402
import spans  # noqa: E402
import yardstick  # noqa: E402

# chip 0: collective [150, 350) + [400, 650) + [900, 1000) = 550, exposed
# 150 + (10 + 50) + 100 = 310; chip 1: collective 200 + 100 = 300, exposed
# 100; averaged over the chips and the 2 traced steps, in ms
COLLECTIVE_MS = (550 + 300) / 2 / 2 / 1e6
EXPOSED_MS = (310 + 100) / 2 / 2 / 1e6
KNOWN = {"fsdp.collective_ms": COLLECTIVE_MS,
         "fsdp.exposed_collective_ms": EXPOSED_MS,
         "fsdp.collective_gb_per_s": 425000 / (COLLECTIVE_MS / 1e3) / 1e9}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def recorded(d=None):
    if d is None:
        with open(os.path.join(HERE, "data", "trace_collectives.json")) as f:
            d = json.load(f)
    return types.SimpleNamespace(
        trace=yardstick.Trace.from_json(d), trace_window=(0, 1000),
        traced_steps=2, program_trace=spans.ProgramTrace.from_json(d))


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_each_reader_reads_its_known_value(name):
    assert reader(name)(recorded()) == pytest.approx(KNOWN[name],
                                                     rel=1e-12)


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_without_a_trace_a_reader_reads_nothing(name):
    run = types.SimpleNamespace(trace=None, trace_window=(0, 0),
                                traced_steps=0)
    assert reader(name)(run) is None


def test_the_rate_needs_the_step_spans_bytes():
    """A program whose step span carries no bytes (one from before the
    counter) still reads its collective time, and no rate."""
    run = recorded()
    for thread in run.program_trace.thread_spans.values():
        for _, _, _, args in thread:
            args.pop("collective_bytes", None)
    assert reader("fsdp.collective_ms")(run) == pytest.approx(COLLECTIVE_MS)
    assert reader("fsdp.collective_gb_per_s")(run) is None


def test_an_asynchronous_collective_counts_from_start_to_done():
    """Chip 0 as ``yardstick.chip_times`` sees it leaves the asynchronous
    pair out of the collectives (its start and done read as compute);
    here the pair is in flight over [400, 650), under fusion.5 but at its
    two ends."""
    ops = recorded().trace.device_ops["/device:TPU:0"]
    plain = yardstick.chip_times(ops, (0, 1000))
    assert plain.collective_ns == 200 + 100
    got = collectives.chip_times(ops, (0, 1000))
    assert (got.collective_ns, got.exposed_collective_ns) == (550, 310)
    assert collectives.async_spans(
        recorded().trace.device_ops["/device:TPU:1"]) == []
