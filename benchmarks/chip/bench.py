"""On-chip benchmark of the instrumented training driver.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process on the chips JAX finds,
and prints one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced, ``breakdown``;
``checks`` comes last and holds each number compared with its limit.  With
``--trace 0`` the metrics are the cell's end-to-end ones, with ``--trace 1``
its per-layer ones.  Without a TPU, with fewer chips than the cell asks for,
or on a chip missing from ``peaks.json``, it exits non-zero and prints no
result.

A cell is ``workloads/<cell>.json`` (driver flags, chips) over
``configs/<config>.json`` (sizes, optimizer, limits) and the plain
reference ``configs/<architecture>.py``; each metric is read by
``metrics/<metric>.py``.  A run:

  1. calls the driver once for ``2 * analyze_every`` steps, which warms
     every program and sizes the timed call from its step time;
  2. calls it again for as many steps as fill ``--seconds``.  The window
     opens at the ``[step k]`` line after its second analysis window
     (k = 2 * analyze_every) and closes at its last ``[step n]`` line;
     set-up (``setup_s``) is process start to the window's opening.
     After steps 1 and 3 the state is probed for the first gradient and
     the parameters' change (check work, left out of ``setup_s``);
     traced, the profiler records
     ``trace_steps`` steps from the window's opening;
  3. checks the first three steps against the plain reference, once the
     driver's state is freed, and for a cell with ``expect`` every
     window's verdict against the plain reference of ``verdicts.py`` and
     the injected straggler.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import os
import re
import shutil
import statistics
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import drive  # noqa: E402
import yardstick  # noqa: E402

WINDOW_LINE = re.compile(
    r"^\[window (\d+)\] steps (\d+)-(\d+) .*\| diag (\w+) \| "
    r"(?:stragglers: \[([\d, ]*)\] \(S=([\d.]+)|no stragglers \(S=([\d.]+))")
REFERENCE_STEPS = 3
TRACE_MARK = "bench_window"    # the host span around the traced steps
STEP_GRAIN = 16   # the timed call's length is a whole number of this many
                  # steps, sized from the fastest warm-up step (the
                  # steadiest reading) rounded to two significant digits,
                  # so that its compiled step, whose learning-rate schedule
                  # spans the run, is the same from run to run and found in
                  # the cache again


def process_start() -> float:
    """This process's start on the ``perf_counter`` clock (Linux), or now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.perf_counter() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


class NoChip(RuntimeError):
    """The machine lacks what the cell needs; no result is printed."""


def load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict            # the cell's entry in BENCHMARK.json
    workload: Dict         # workloads/<cell>.json
    config: Dict           # configs/<config>.json
    ref: object            # configs/<architecture>.py
    metrics: List[Dict]    # the metric entries this cell reports


def load_cell(name: str, trace: bool, bench: Optional[Dict] = None,
              files: str = HERE) -> Cell:
    """The cell's entry, workload and configuration (from ``files``), its
    architecture's reference, and the metrics it reports."""
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json")
    workload = load_json(files, "workloads", f"{name}.json")
    config = load_json(files, "configs", f"{entry['config']}.json")
    ref = load_module(os.path.join(HERE, "configs",
                                   f"{config['architecture']}.py"),
                      f"ref_{config['architecture']}")
    listed = bench["per_layer"] if trace else bench["end_to_end"]
    metrics = [m for m in listed if name in m.get("workloads", [name])]
    return Cell(name, entry, workload, config, ref, metrics)


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their numbers here."""
    cell: Cell
    seed: int
    chips: int
    tokens_per_step: int
    flops_per_step: float          # model flops of one step, all chips
    peak_flops: float              # one chip's bf16 peak
    setup_s: float
    window_s: float                # host clock, window open to close
    window_steps: List[float]      # the driver's step_s of each timed step
    verdict_ms: List[float]        # last step's end to verdict, per window
    trace: Optional[yardstick.Trace] = None
    traced_steps: int = 0
    trace_window: tuple = (0, 0)   # ns on the trace's clock
    chip_times: List[yardstick.ChipTime] = dataclasses.field(
        default_factory=list)

    @property
    def trace_window_s(self) -> float:
        return (self.trace_window[1] - self.trace_window[0]) / 1e9


def read_metrics(cell: Cell, run: Run) -> Dict[str, Dict]:
    out = {}
    for m in cell.metrics:
        reader = load_module(os.path.join(HERE, "metrics", f"{m['name']}.py"),
                             "metric_" + re.sub(r"\W", "_", m["name"]))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# Comparisons
# ---------------------------------------------------------------------------

def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keys: List[str]) -> float:
    """Largest gap between two norms of one leaf, as a share of the
    reference's norm of that leaf or of the median leaf, the larger."""
    med = sorted(ref[k] for k in keys)[len(keys) // 2]
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def leaf_diffs(prog: Dict, ref: Dict, keys: List[str]) -> Dict[str, float]:
    """For each leaf, the norm of the program's difference from the
    reference over its sampled elements, as a share of the reference's
    norm there."""
    return {k: float(np.linalg.norm(prog[k] - ref[k])
                     / max(np.linalg.norm(ref[k]), 1e-30)) for k in keys}


def training_checks(prog: Dict, ref: Dict, limits: Dict) -> Dict[str, Dict]:
    """Loss of each compared step, first gradient and change after the last
    compared step, each a number beside its limit (None: read, not
    compared).  Leaves whose reference gradient is under a thousandth of
    the median leaf's move by rounding alone and are left out of all but
    ``grad_gap``.  ``*_gap`` compare norms (by the worst leaf), ``*_diff``
    the leaves element by element (by the median leaf)."""
    g = ref["grad"]["norms"]
    med = sorted(g.values())[len(g) // 2]
    moved = [k for k in g if g[k] >= 1e-3 * med]
    vals = {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                   ref["losses"])),
        "grad_gap": worst_leaf_gap(prog["grad"]["norms"], g, list(g)),
        "change_gap": worst_leaf_gap(prog["change"]["norms"],
                                     ref["change"]["norms"], moved),
        "grad_diff": statistics.median(leaf_diffs(
            prog["grad"]["samples"], ref["grad"]["samples"], moved).values()),
        "change_diff": statistics.median(leaf_diffs(
            prog["change"]["samples"], ref["change"]["samples"],
            moved).values()),
    }
    return {k: {"value": v, "limit": limits.get(k)} for k, v in vals.items()}


def judge(readings: Dict[str, Dict]):
    """The numbers compared, and whether each is within its limit.  A
    number without a limit is read, not compared (PERF.md says why)."""
    checks = {k: v for k, v in readings.items() if v["limit"] is not None}
    return checks, bool(checks) and all(v["value"] <= v["limit"]
                                        for v in checks.values())


def verdict_checks(windows: Dict[int, Dict], journal: str, expect: Dict,
                   timed: Callable[[int], bool]) -> Dict[str, Dict]:
    """Each timed window's printed verdict against the plain reference on
    the journaled window and against the injected fault: the windows whose
    stragglers, diagnosis or severity differ (severity beyond the half unit
    of the 4 decimals the driver prints)."""
    import verdicts
    mismatch = 0
    for label, blob in verdicts.read_journal(journal):
        last = int(label.rsplit("-", 1)[1])
        got = windows.get(last)
        if not timed(last) or got is None:
            continue                  # a missing verdict counts in `failed`
        want = verdicts.verdict(verdicts.cpu_times(blob))
        if (got["stragglers"] != want["stragglers"]
                or got["stragglers"] != expect["stragglers"]
                or got["diagnosis"] != expect["diagnosis"]
                or abs(got["severity"] - want["severity"]) > 0.5e-4):
            mismatch += 1
    return {"verdict_mismatch": {"value": mismatch,
                                 "limit": expect["verdict_mismatch_limit"]}}


def parse_windows(lines) -> Dict[int, Dict]:
    """Verdict lines by the last step of their window."""
    out = {}
    for t, line in lines:
        mt = WINDOW_LINE.match(line)
        if mt:
            strag = [int(x) for x in mt.group(5).split(",")] \
                if mt.group(5) else []
            out[int(mt.group(3))] = {
                "t": t, "first": int(mt.group(2)), "last": int(mt.group(3)),
                "diagnosis": mt.group(4), "stragglers": strag,
                "severity": float(mt.group(6) or mt.group(7))}
    return out


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------

def attach_probes(watch: drive.StepWatch, cell: Cell, seed: int) -> Dict:
    """Read the first gradient after step 1 (AdamW's first moment is
    (1 - b1) g) and each leaf's change after the last compared step
    (``leaf_readings``) into the returned dict, and the seconds the reading
    took (``probe_s``): check work, which set-up does not count."""
    import jax
    c, ref = cell.config, cell.ref
    key = ref.seed_key(seed)
    read = jax.jit(ref.leaf_readings)
    probes: Dict = {"probe_s": 0.0}

    def timed(fn):
        def probe(state, _):
            t = time.perf_counter()
            fn(state)
            probes["probe_s"] += time.perf_counter() - t
        return probe
    watch.after[1] = timed(lambda st: probes.update(grad=ref.to_host(
        read(st["opt"]["m"], key), 1 / (1 - c["optimizer"]["b1"]))))
    watch.after[REFERENCE_STEPS] = timed(lambda st: probes.update(
        change=ref.change_readings(c, key, st["params"])))
    return probes


def setup_jax(chips: int):
    """Compile cache at the checkout's fixed path (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), then the chip check."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    try:
        yardstick.peaks(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return devs[:chips]


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             bench: Optional[Dict] = None,
             files: str = HERE, peaks: Optional[Dict] = None,
             fault: Optional[Callable] = None,
             verdict_fault: Optional[Callable] = None,
             log=sys.stderr) -> Dict:
    """Run one cell; returns the result object (without printing it)."""
    t_start = process_start()
    cell = load_cell(name, trace, bench, files)
    chips = cell.entry["chips"]
    devs = setup_jax(chips)
    import jax
    peaks = peaks or yardstick.peaks(devs[0].device_kind)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import train

    c, wl = cell.config, cell.workload
    argv = c["driver_args"] + wl["driver_args"]
    args = train.build_parser().parse_args(argv)
    every = args.analyze_every
    open_step = 2 * every
    tokens = args.batch * args.seq
    clock = drive.LineClock(log)
    watch = drive.StepWatch()
    scratch = tempfile.mkdtemp(prefix="bench_")
    try:
        with drive.stamped_stdout(clock), \
                drive.hooks(c, seed, cell.ref, watch):
            # 1. warm-up and sizing
            sizing = train.run(argv + ["--steps", str(open_step)])
            per_step = float(f"{min(sizing.step_s):.2g}")
            del sizing
            gc.collect()
            grain = math.lcm(every, STEP_GRAIN)
            n_win = grain * max(1, int(seconds / per_step / grain))
            steps = open_step + n_win
            # 2. the timed call
            clock.reset()
            watch.calls = 0
            watch.ended.clear()
            probes = attach_probes(watch, cell, seed)
            trace_dir = os.path.join(scratch, "trace")
            n_traced = min(wl.get("trace_steps", 10), n_win)
            marks = {}
            if trace:
                def start(*_):
                    jax.profiler.start_trace(trace_dir)
                    marks["span"] = jax.profiler.TraceAnnotation(TRACE_MARK)
                    marks["span"].__enter__()

                def stop(*_):
                    marks.pop("span").__exit__(None, None, None)
                    jax.profiler.stop_trace()
                watch.before[open_step + 1] = start
                watch.before[open_step + 1 + n_traced] = stop
            watch.fault = fault
            journal = os.path.join(scratch, "windows.journal")
            extra = ["--journal", journal] if "expect" in wl else []
            if verdict_fault is not None:
                from repro.perfdbg import straggler
                detect = straggler.detect
                straggler.detect = lambda *a, **kw: verdict_fault(
                    detect(*a, **kw))
            try:
                res = train.run(argv + ["--steps", str(steps)] + extra)
            finally:
                if verdict_fault is not None:
                    straggler.detect = detect
                if "span" in marks:
                    stop()
        t_open = clock.stamp(rf"^\[step {open_step}\] ")
        phases = [("start", t_start)] + [
            (p, clock.stamp(rx)) for p, rx in (
                ("timed call", r"^\[train\] \S+: ~"),
                ("compiled", r"^\[train\] compiled"),
                ("costs", r"^\[costs\] \S+ step:"),
                ("first window", rf"^\[step {every}\] "))] + [
            ("window open", t_open)]
        print("set-up: " + ", ".join(f"{p} +{t - t_start:.2f}s"
                                     for p, t in phases if t)
              + f"; probes {probes['probe_s']:.2f}s", file=log)
        t_close = clock.stamp(rf"^\[step {steps}\] ")
        windows = parse_windows(clock.lines)
        timed = lambda last: open_step < last <= steps
        ends = range(open_step + every, steps + 1, every)
        verdict_ms = [1e3 * (windows[b]["t"] - watch.ended[b])
                      for b in ends if b in windows]
        mem = [(d.memory_stats() or {}).get("peak_bytes_in_use")
               for d in devs]
        window_losses = res.losses[open_step:steps]
        run = Run(cell=cell, seed=seed, chips=chips, tokens_per_step=tokens,
                  flops_per_step=yardstick.model_flops(c["flops_params"],
                                                       tokens),
                  peak_flops=peaks["bf16_flops"],
                  setup_s=t_open - t_start - probes["probe_s"],
                  window_s=t_close - t_open,
                  window_steps=res.step_s[open_step:steps],
                  verdict_ms=verdict_ms)
        prog = {"losses": res.losses[:REFERENCE_STEPS], **probes}
        del res
        gc.collect()
        result = {
            "correct": False,
            "attempted": n_win + len(ends),
            "failed": sum(not math.isfinite(x) for x in window_losses)
            + (n_win - len(window_losses)) + len(ends) - len(verdict_ms),
        }
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": max((b for b in mem if b),
                                           default=None)}
        if trace:
            tr = yardstick.Trace.from_dir(trace_dir, TRACE_MARK)
            span = [(s, s + d) for n, s, d in tr.host_spans
                    if n == TRACE_MARK]
            run.trace, run.traced_steps = tr, n_traced
            run.trace_window = span[0] if span else (0, 0)
            run.chip_times = [yardstick.chip_times(ops, run.trace_window)
                              for _, ops in sorted(tr.device_ops.items())]
            device["busy_s"] = (sum(ct.busy_ns for ct in run.chip_times)
                                / max(len(run.chip_times), 1) / 1e9)
            device["window_s"] = run.trace_window_s
        result["metrics"] = read_metrics(cell, run)
        result["device"] = device
        if trace:
            result["breakdown"] = {
                "device_ops": yardstick.top_ops(run.trace, run.trace_window),
                "idle_gaps": yardstick.idle_gaps(run.trace, run.trace_window,
                                                 marker=TRACE_MARK)}
        # 3. correctness, with the driver's state gone
        ref = cell.ref.train_readings(c, seed, args.batch, args.seq, steps,
                                      REFERENCE_STEPS, devices=devs)
        readings = training_checks(prog, ref, c["limits"])
        if "expect" in wl:
            readings.update(verdict_checks(windows, journal, wl["expect"],
                                           timed))
        for k, v in readings.items():
            print(f"reading {k}: {v['value']!r}", file=log)
        result["checks"], result["correct"] = judge(readings)
        return result
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    # before JAX starts: libtpu writes its logs under /tmp unless told
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
