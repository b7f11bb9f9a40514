"""Recorder (125*n*m contract), instrumenter, straggler policy, attributes."""
import time

import numpy as np
import pytest

from repro.core import RegionTree
from repro.perfdbg import (Instrumenter, PAPER_BYTES_PER_CELL, RegionRecorder,
                           detect, dominant_term, rebalance_weights,
                           region_attributes, roofline_terms)
from repro.perfdbg.instrument import build_step_tree


def small_tree(n=4):
    t = RegionTree()
    for i in range(1, n + 1):
        t.add(f"r{i}", rid=i)
    return t


class TestRecorder:
    def test_paper_byte_budget(self):
        """The paper's headline: <= 125 bytes per (region, process) cell."""
        t = small_tree(6)
        rec = RegionRecorder(t, n_ranks=16)
        assert rec.within_paper_budget()
        assert rec.packed_size() <= PAPER_BYTES_PER_CELL * 6 * 16
        # and the locate fields are ~1/3 of the record (paper: 33%)
        from repro.perfdbg.recorder import RECORD_DTYPE, LOCATE_FIELDS
        locate = sum(RECORD_DTYPE.fields[f][0].itemsize for f in LOCATE_FIELDS)
        assert locate / RECORD_DTYPE.itemsize == pytest.approx(1 / 3, abs=0.02)

    def test_packed_roundtrip(self):
        t = small_tree(3)
        rec = RegionRecorder(t, 2)
        rec.add(0, 1, cpu_time=1.5, wall_time=2.0, cycles=3e9,
                instructions=1e9, disk_io=42.0)
        blob = rec.packed()
        rec2 = RegionRecorder.from_packed(t, 2, blob)
        m1, m2 = rec.measurements(), rec2.measurements()
        np.testing.assert_array_equal(m1.cpu_time, m2.cpu_time)
        np.testing.assert_array_equal(rec.attributes()["disk_io"],
                                      rec2.attributes()["disk_io"])

    def test_accumulation(self):
        t = small_tree(2)
        rec = RegionRecorder(t, 1)
        rec.add(0, 1, cpu_time=1.0)
        rec.add(0, 1, cpu_time=2.0)
        assert rec.measurements().cpu_time[0, 0] == 3.0

    def test_analyze_smoke(self):
        t = small_tree(3)
        rec = RegionRecorder(t, 4)
        for r in range(4):
            for rid in (1, 2, 3):
                rec.add(r, rid, cpu_time=1.0 + (0.5 * rid if r == 3 else 0),
                        wall_time=1.0, cycles=2e9, instructions=1e9)
            rec.add_program_wall(r, 3.0)
        rep = rec.analyze()
        assert rep.external is not None and rep.internal is not None


class TestDurationWeightedRates:
    """WMEAN fields on multi-call regions: the recorded rate is the
    duration-weighted mean of the calls, however unequal their durations."""

    def test_unequal_durations_weight_by_wall_time(self):
        rec = RegionRecorder(small_tree(), 1)
        # three calls: 6s at 10% misses, 3s at 40%, 1s at 90%
        rec.add(0, 1, wall_time=6.0, l1_miss_rate=0.10)
        rec.add(0, 1, wall_time=3.0, l1_miss_rate=0.40)
        rec.add(0, 1, wall_time=1.0, l1_miss_rate=0.90)
        want = (0.10 * 6 + 0.40 * 3 + 0.90 * 1) / 10
        assert rec.attributes()["l1_miss_rate"][0, 0] == pytest.approx(want)
        # a long dominant call pins the mean near its own rate
        rec.add(0, 2, wall_time=99.0, l1_miss_rate=0.2)
        rec.add(0, 2, wall_time=1.0, l1_miss_rate=1.0)
        assert rec.attributes()["l1_miss_rate"][0, 1] == pytest.approx(0.208)

    def test_cpu_time_weight_fallback_then_unit(self):
        rec = RegionRecorder(small_tree(), 1)
        # no wall time recorded -> CPU time is the weight
        rec.add(0, 1, cpu_time=3.0, l2_miss_rate=0.1)
        rec.add(0, 1, cpu_time=1.0, l2_miss_rate=0.5)
        assert rec.attributes()["l2_miss_rate"][0, 0] == pytest.approx(0.2)
        # neither clock recorded -> every call weighs 1 (plain mean)
        rec.add(0, 2, l2_miss_rate=0.2)
        rec.add(0, 2, l2_miss_rate=0.6)
        assert rec.attributes()["l2_miss_rate"][0, 1] == pytest.approx(0.4)

    def test_constant_rate_is_exact_across_many_calls(self):
        rec = RegionRecorder(small_tree(), 1)
        for i in range(50):
            rec.add(0, 1, wall_time=0.1 * (1 + i % 7), l2_miss_rate=0.25)
        assert rec.attributes()["l2_miss_rate"][0, 0] == 0.25

    def test_weighted_mean_survives_wire_roundtrip(self):
        from repro.perfdbg import WindowSnapshot
        rec = RegionRecorder(small_tree(), 1)
        rec.add(0, 1, wall_time=9.0, l2_miss_rate=0.10)
        rec.add(0, 1, wall_time=1.0, l2_miss_rate=0.50)
        back = WindowSnapshot.from_bytes(rec.snapshot().to_bytes())
        assert back.attributes()["l2_miss_rate"][0, 0] == pytest.approx(0.14)


class TestCpuClockFallback:
    """CPU_CLOCK must fall back to perf_counter when the kernel's
    CLOCK_PROCESS_CPUTIME_ID is pinned or coarsely quantized (gVisor-style
    sandboxes tick it at ~10ms, collapsing short regions to zero)."""

    @pytest.fixture(autouse=True)
    def reset_clock_cache(self):
        from repro.perfdbg import instrument
        instrument._cpu_clock = None
        yield
        instrument._cpu_clock = None

    def test_quantized_clock_rejected(self, monkeypatch):
        from repro.perfdbg import instrument
        monkeypatch.setattr(time, "process_time", lambda: 0.0)
        assert not instrument._process_time_works(probe_s=0.005)
        assert instrument._cpu_clock is None
        instrument.CPU_CLOCK()
        assert instrument._cpu_clock is time.perf_counter

    def test_coarse_ticks_rejected(self, monkeypatch):
        """A clock that only advances in 10ms steps yields too few distinct
        values over the probe window."""
        from repro.perfdbg import instrument
        monkeypatch.setattr(
            time, "process_time",
            lambda: np.floor(time.perf_counter() * 100) / 100)
        assert not instrument._process_time_works(probe_s=0.005)

    def test_fine_clock_accepted(self, monkeypatch):
        from repro.perfdbg import instrument
        monkeypatch.setattr(time, "process_time", time.perf_counter)
        assert instrument._process_time_works(probe_s=0.005)
        instrument.CPU_CLOCK()
        assert instrument._cpu_clock is time.process_time

    def test_fallback_still_times_regions(self, monkeypatch):
        from repro.perfdbg import instrument
        monkeypatch.setattr(time, "process_time", lambda: 0.0)
        rec = RegionRecorder(small_tree(2), 1)
        ins = Instrumenter(rec, 0)
        with ins.region("r1", nominal_cpi=1.0):
            t_end = time.perf_counter() + 0.01
            while time.perf_counter() < t_end:
                pass
        m = rec.measurements()
        # perf_counter fallback: cpu_time tracks the busy wait instead of 0
        assert m.cpu_time[0, 0] >= 0.009
        assert m.instructions[0, 0] > 0


class TestInstrumenter:
    def test_region_timing(self):
        t = small_tree(2)
        rec = RegionRecorder(t, 1)
        ins = Instrumenter(rec, 0)
        with ins.region("r1", instructions=100):
            time.sleep(0.01)
        m = rec.measurements()
        assert m.wall_time[0, 0] >= 0.009
        assert m.instructions[0, 0] == 100

    def test_build_step_tree_granularity(self):
        t_layer = build_step_tree(["L0", "L1"], "layer")
        assert "L0" in [t_layer.name(r) for r in t_layer.ids()]
        t_op = build_step_tree(["L0"], "op")
        names = [t_op.name(r) for r in t_op.ids()]
        assert "L0.mix" in names and "L0.ffn" in names
        t_step = build_step_tree([], "step")
        assert len(t_step.ids()) == 6


class TestStraggler:
    def _report_with_straggler(self):
        t = small_tree(3)
        rec = RegionRecorder(t, 6)
        for r in range(6):
            slow = 3.0 if r == 5 else 1.0
            for rid in (1, 2, 3):
                rec.add(r, rid, cpu_time=slow, wall_time=slow,
                        cycles=slow * 2e9, instructions=1e9)
            rec.add_program_wall(r, slow * 3)
        return rec.analyze()

    def test_detects_slow_rank(self):
        v = detect(self._report_with_straggler())
        assert 5 in v.stragglers
        assert set(v.majority) == {0, 1, 2, 3, 4}
        assert v.action in ("rebalance", "alert")

    def test_no_stragglers_when_balanced(self):
        t = small_tree(2)
        rec = RegionRecorder(t, 4)
        for r in range(4):
            for rid in (1, 2):
                rec.add(r, rid, cpu_time=1.0, wall_time=1.0, cycles=2e9,
                        instructions=1e9)
        v = detect(rec.analyze())
        assert v.stragglers == ()

    def test_rebalance_weights(self):
        w = rebalance_weights(np.array([1.0, 1.0, 2.0]))
        assert w[2] < w[0]
        assert np.sum(w) == pytest.approx(3.0)


class TestAttributes:
    def test_peaks_keyed_by_device_kind(self):
        from repro.perfdbg.attributes import (DEVICE_PEAKS, HBM_BW,
                                              PEAK_FLOPS, device_peaks)
        v5e = device_peaks("TPU v5 lite")
        assert (v5e.flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                         16e9)
        assert (PEAK_FLOPS, HBM_BW) == (v5e.flops, v5e.hbm_bw)
        with pytest.raises(KeyError, match="TPU v4"):
            device_peaks("TPU v4")          # never another chip's numbers
        assert "TPU v4" not in DEVICE_PEAKS

    def test_roofline_terms_and_dominant(self):
        terms = roofline_terms(flops=197e12, bytes_hbm=819e9 * 2,
                               collective_bytes=0)
        assert terms["compute_s"] == pytest.approx(1.0)
        assert terms["memory_s"] == pytest.approx(2.0)
        assert dominant_term(terms) == "memory"

    def test_region_attributes_shapes(self):
        f = np.full((2, 3), 1e12)
        b = np.full((2, 3), 1e10)
        attrs = region_attributes(f, b, np.zeros((2, 3)), np.zeros((2, 3)))
        assert set(attrs) == {"l1_miss_rate", "l2_miss_rate", "disk_io",
                              "network_io", "instructions"}
        assert attrs["l2_miss_rate"].shape == (2, 3)
        # high intensity (100 flops/byte < ridge) => some memory-boundedness
        assert 0.0 <= attrs["l2_miss_rate"][0, 0] <= 1.0
