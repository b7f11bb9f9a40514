"""The readers of the program's spans and scopes (``spans.py`` and the
metrics that use it) on a small recorded-style trace, the search for a
run's trace file, and the existing readings of the two recorded traces,
which these additions leave as they were; no chip needed."""
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

NEW = {"collection.ms_per_step": 240 / 2e6,
       "analysis.queue_ms_p50": 25 / 1e6,
       "analysis.window_ms_p50": 200 / 1e6,
       "analysis.idle_overlap_ms_per_step": 250 / 2e6,
       "step.wkv_ms": 300 / 2 / 2e6,
       "step.optimizer_ms": 200 / 2 / 2e6}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def a_run(trace, window, steps, program_trace=None, **kw):
    run = types.SimpleNamespace(trace=trace, trace_window=window,
                                trace_window_s=(window[1] - window[0]) / 1e9,
                                traced_steps=steps, **kw)
    if program_trace is not None:
        run.program_trace = program_trace
    return run


@pytest.fixture
def recorded():
    d = load("trace_spans.json")
    return a_run(yardstick.Trace.from_json(d), (0, 1000), 2,
                 spans.ProgramTrace.from_json(d))


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_reader_reads_its_known_value(recorded, name):
    assert reader(name)(recorded) == pytest.approx(NEW[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(NEW))
def test_without_a_trace_or_the_program_spans_a_reader_reads_nothing(name):
    assert reader(name)(a_run(None, (0, 0), 0)) is None
    d = load("trace_two_chips.json")     # a trace of a program without them
    run = a_run(yardstick.Trace.from_json(d), (0, 1000), 2,
                spans.ProgramTrace.from_json({"op_scopes": {
                    "fusion.1": "jit(train_step)/add"}}))
    assert reader(name)(run) is None


def test_a_scope_is_a_whole_component_of_the_path(recorded):
    assert spans.scope_ms(recorded, "mix") == pytest.approx(
        (100 + 100 + 100 + 50) / 2 / 2e6)   # fusion.6 is wkv's alone
    assert spans.scope_ms(recorded, "wkvx") == pytest.approx(20 / 2 / 2e6)
    assert spans.scope_ms(recorded, "ffn") is None


def test_from_json_defaults_to_no_spans_and_no_scopes():
    pt = spans.ProgramTrace.from_json({})
    assert (pt.thread_spans, pt.op_scopes, pt.scope_source) == ({}, {}, "")


XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 5000 duration_ps: 2000
             stats { metadata_id: 1 int64_value: 4 } } }
  lines { id: 2 name: "python" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 300000
             stats { metadata_id: 1 int64_value: 4 } } }
  event_metadata { key: 1 value { id: 1 name: "MARK" } }
  event_metadata { key: 2 value { id: 2 name: "analysis.window" } }
  event_metadata { key: 3 value { id: 3 name: "perfdbg.flush" } }
  stat_metadata { key: 1 value { id: 1 name: "submission" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 200000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop"
    stats { metadata_id: 1 str_value: "jit(train_step)/mix/wkv/mul:" } } }
  event_metadata { key: 2 value { id: 2
    name: "%fusion.2 = f32[2]{0} fusion(f32[2]{0} %q), kind=kLoop"
    stats { metadata_id: 1 ref_value: 3 } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "jit(train_step)/optimizer/add" } }
}
"""


def write_xspace(root, sub, marker_start_ns):
    from jax.profiler import ProfileData
    text = XSPACE.replace("MARK", "bench_window").replace(
        "timestamp_ns: 1000", f"timestamp_ns: {marker_start_ns}", 1)
    path = root / sub / "trace" / "plugins" / "profile" / "1"
    path.mkdir(parents=True)
    (path / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_the_run_finds_its_own_trace_file(tmp_path, monkeypatch):
    """Of the trace files under the temporary directory, the run's is the
    one whose marker span is its window; the device planes' metadata give
    the op_name paths, inline or by reference."""
    import tempfile
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    write_xspace(tmp_path, "bench_other", 5000)
    write_xspace(tmp_path, "bench_mine", 1000)
    ops = {"/device:TPU:0": [("fusion.1", 1000, 100), ("fusion.2", 1200, 100)]}
    run = a_run(yardstick.Trace(ops, []), (1000, 2000), 1)
    pt = spans.for_run(run)
    assert pt is run.program_trace is spans.for_run(run)
    assert pt.op_scopes == {"fusion.1": "jit(train_step)/mix/wkv/mul",
                            "fusion.2": "jit(train_step)/optimizer/add"}
    assert pt.scope_source == "metadata stat tf_op"
    assert [(s, d, a) for _, s, d, a in pt.spans("analysis.window")] == \
        [(1100, 300, {"submission": 4})]
    assert len(pt.thread_spans) == 2
    assert reader("analysis.queue_ms_p50")(run) == pytest.approx(93 / 1e6)
    assert reader("step.wkv_ms")(run) == pytest.approx(100 / 1e6)
    # no file of this window: nothing to read
    assert spans.for_run(a_run(yardstick.Trace(ops, []), (0, 10), 1)) is None


# ---------------------------------------------------------------------------
# The readings that were there: unchanged
# ---------------------------------------------------------------------------

def test_the_two_chip_trace_reads_as_before():
    d = load("trace_two_chips.json")
    trace = yardstick.Trace.from_json(d)
    window = (0, 1000)
    cts = [yardstick.chip_times(ops, window)
           for _, ops in sorted(trace.device_ops.items())]
    assert [(c.busy_ns, c.collective_ns, c.exposed_collective_ns)
            for c in cts] == [(700, 200, 150), (500, 300, 300)]
    assert yardstick.top_ops(trace, window) == pytest.approx(
        [("fusion", 2.75e-07), ("reduce-scatter", 1.5e-07),
         ("all-gather", 1e-07), ("convolution", 5e-08)])
    assert yardstick.idle_gaps(trace, window, marker="bench_window") == \
        pytest.approx([("host: flush_window", 2.5e-07),
                       ("host: PjitFunction(train_step)", 5e-08)])
    for pt in (None, spans.ProgramTrace.from_json(d)):
        run = a_run(trace, window, 2, pt, chip_times=cts)
        assert reader("step.device_ms")(run) == pytest.approx(600 / 2e6)
        assert reader("device.idle_share")(run) == pytest.approx(50.0)


def test_the_recorded_v5e_step_reads_as_before():
    events = load("trace_v5e_step.json")["events"]
    ops = [(yardstick.op_name(n), s, d) for n, s, d in events]
    trace = yardstick.Trace({"/device:TPU:0": ops}, [])
    window = (42944230, 83835861)
    ct = yardstick.chip_times(ops, window)
    assert (ct.busy_ns, ct.collective_ns) == (40890890, 0)
    top = yardstick.top_ops(trace, window)
    assert top[:3] == pytest.approx([("fusion", 0.016212281),
                                     ("convolution_bitcast_fusion",
                                      0.007307435),
                                     ("convert", 0.004377111)])
    assert yardstick.idle_gaps(trace, window, n=1) == \
        pytest.approx([("host: no span", 6.44e-07)])
    run = a_run(trace, window, 1, chip_times=[ct])
    assert reader("step.device_ms")(run) == pytest.approx(40.89089)
    assert reader("device.idle_share")(run) == pytest.approx(
        100 * (1 - 40890890 / (83835861 - 42944230)))
