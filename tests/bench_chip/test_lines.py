"""Reading the driver's output: stamped lines, verdict lines, the plain
verdict reference against the journal the driver writes, and the metric
readers on a recorded run; no chip needed."""
import os
import sys
import threading

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "..", "..", "benchmarks", "chip")
sys.path.insert(0, BENCH)

import bench  # noqa: E402
import drive  # noqa: E402
import verdicts  # noqa: E402
import yardstick  # noqa: E402


class Sink:
    def __init__(self):
        self.text = []

    def write(self, s):
        self.text.append(s)

    def flush(self):
        pass


def test_line_clock_keeps_each_threads_lines_whole():
    clock = drive.LineClock(Sink())

    def writer(tag):
        for i in range(200):
            # print() writes the text and the newline in two calls
            clock.write(f"[{tag}] line {i}")
            clock.write("\n")
    threads = [threading.Thread(target=writer, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lines = [line for _, line in clock.lines]
    assert len(lines) == 400
    assert all(line.startswith(("[a] line ", "[b] line ")) for line in lines)
    times = [t for t, _ in clock.lines]
    assert times == sorted(times)
    assert clock.stamp(r"^\[b\] line 199$") is not None


def test_verdict_lines_parse_by_their_windows_last_step():
    with open(os.path.join(HERE, "data", "driver_pod256.txt")) as f:
        lines = [(float(i), line.rstrip("\n")) for i, line in enumerate(f)]
    w = bench.parse_windows(lines)
    assert sorted(w) == [2, 4, 6, 8]
    assert w[2]["stragglers"] == [255] and w[2]["diagnosis"] == "compute"
    assert w[6]["stragglers"] == [254, 255] and w[6]["severity"] == 2.9999
    assert w[8]["stragglers"] == [] and w[8]["diagnosis"] == "io"
    assert w[6]["t"] == 6.0


def _pod_snapshot(slow: float):
    from repro.core import RegionTree
    from repro.perfdbg import RegionRecorder
    tree = RegionTree("train")
    for name in ("data", "step", "checkpoint"):
        tree.add(name)
    rec = RegionRecorder(tree, n_ranks=256, schema="tpu")
    rng = np.random.default_rng(0)
    for r in range(256):
        s = slow if r == 255 else 1.0
        for rid, base in zip(tree.ids(), (2e-3, 0.2, 1e-5)):
            t = base * s * (1 + 1e-4 * rng.standard_normal())
            rec.add(r, rid, cpu_time=t, wall_time=t, cycles=t * 1e9,
                    instructions=1e6)
    return tree, rec.reset_window("steps 1-2")


def test_the_plain_decoder_reads_what_the_program_encodes(tmp_path):
    from repro.core.journal import WindowJournal
    from repro.perfdbg import WindowSnapshot
    tree, snap = _pod_snapshot(4.0)
    path = str(tmp_path / "w.journal")
    with WindowJournal(path) as j:
        j.append(0, snap.to_bytes(), label=snap.label)
        j.append(1, snap.to_bytes(checksum=True), label="steps 3-4")
    got = verdicts.read_journal(path)
    assert [lab for lab, _ in got] == ["steps 1-2", "steps 3-4"]
    want = WindowSnapshot.from_bytes(got[0][1]).measurements().cpu_time
    for _, blob in got:
        np.testing.assert_array_equal(verdicts.cpu_times(blob), want)


def test_the_plain_verdict_agrees_with_the_program_on_a_slow_rank():
    from repro.core import AnalysisSession
    tree, snap = _pod_snapshot(4.0)
    entry = AnalysisSession(tree).ingest_snapshot(snap, label=snap.label)
    v = entry.straggler_verdict()
    ref = verdicts.verdict(verdicts.cpu_times(snap.to_bytes()))
    assert list(v.stragglers) == ref["stragglers"] == [255]
    assert v.severity == pytest.approx(ref["severity"], rel=1e-9)


def test_a_pod_without_a_slow_rank_has_no_straggler():
    _, snap = _pod_snapshot(1.0)
    ref = verdicts.verdict(verdicts.cpu_times(snap.to_bytes()))
    assert ref["stragglers"] == [] and ref["severity"] < 1e-2


def _run(**kw):
    cell = bench.Cell("x", {}, {}, {}, None, [])
    base = dict(cell=cell, seed=1, chips=2, tokens_per_step=4096,
                flops_per_step=1e13, peak_flops=197e12, setup_s=30.0,
                window_s=2.0, window_steps=[0.2] * 9,
                verdict_ms=[10.0, 20.0, 30.0, 40.0])
    base.update(kw)
    return bench.Run(**base)


def _reader(name):
    return bench.load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                             "metric_" + name.replace(".", "_"))


def test_end_to_end_readers():
    run = _run()
    assert _reader("train_tokens_per_s").read(run) == pytest.approx(
        4096 * 9 / 2.0)
    assert _reader("step_ms_p95").read(run) == pytest.approx(200.0)
    assert _reader("verdict_ms_p90").read(run) == pytest.approx(37.0)
    assert _reader("verdict_ms_p90").read(_run(verdict_ms=[])) is None
    assert _reader("setup_s").read(run) == 30.0


def test_per_layer_readers_on_a_traced_run():
    chips = [yardstick.ChipTime(700_000_000, 200_000_000, 150_000_000),
             yardstick.ChipTime(400_000_000, 300_000_000, 300_000_000)]
    run = _run(chip_times=chips, traced_steps=5,
               trace_window=(0, 1_000_000_000))
    assert _reader("driver.host_ms_per_step").read(run) == pytest.approx(
        1e3 * (2.0 - 1.8) / 9)
    assert _reader("step.device_ms").read(run) == pytest.approx(110.0)
    assert _reader("step.mfu").read(run) == pytest.approx(
        100 * 1e13 * 9 / 2.0 / (2 * 197e12))
    assert _reader("device.idle_share").read(run) == pytest.approx(60.0)
    assert _reader("analysis.verdict_ms_p50").read(run) == pytest.approx(25.0)


def test_device_readers_return_nothing_without_a_trace():
    run = _run(chips=1)
    for name in ("step.device_ms", "device.idle_share"):
        assert _reader(name).read(run) is None
