"""The benchmark's own arithmetic: chip peaks, model flops, quantiles and the
reduction of a profiler trace to device busy time, collective time and idle
gaps.  Nothing here imports the system under test."""
from __future__ import annotations

import dataclasses
import glob
import json
import math
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"^(all-gather|reduce-scatter|all-reduce|all-to-all|"
                        r"collective-permute)")
# operations that only run the operations of their bodies, which the trace
# lists on their own
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")
          ) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; a kind missing from
    the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def model_flops(flops_params: int, tokens: int) -> float:
    """Training operations the model needs: 6 per parameter that multiplies
    each token (forward 2, backward 4), recomputation not counted."""
    return 6.0 * flops_params * tokens


def quantile(values: Sequence[float], q: float) -> float:
    """The q-quantile by linear interpolation between order statistics."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of no values")
    pos = (len(xs) - 1) * q
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------

Event = Tuple[str, int, int]          # name, start ns, duration ns


@dataclasses.dataclass
class Trace:
    """What a profiler trace holds that the metrics need: each chip's device
    operations and the host's main-thread spans, on one clock."""
    device_ops: Dict[str, List[Event]]
    host_spans: List[Event]

    @classmethod
    def from_xspace(cls, path: str, marker: str) -> "Trace":
        """Device operations by instruction name (the trace names them by
        their whole HLO text), and the spans of the host thread that
        recorded the span ``marker``."""
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        ops: Dict[str, List[Event]] = {}
        host: List[Event] = []
        for plane in pd.planes:
            for line in plane.lines:
                if plane.name.startswith("/device:") and line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (op_name(e.name), int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
                elif plane.name.startswith("/host:") and not host:
                    spans = [(e.name, int(e.start_ns), int(e.duration_ns))
                             for e in line.events]
                    if any(n == marker for n, _, _ in spans):
                        host = spans
        return cls(ops, host)

    @classmethod
    def from_dir(cls, log_dir: str, marker: str) -> "Trace":
        paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                              "*", "*.xplane.pb")))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_xspace(paths[-1], marker)

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in d["device_ops"].items()},
                   [tuple(e) for e in d["host_spans"]])


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals into disjoint ones, in order."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Tuple[int, int]], b: List[Tuple[int, int]]
             ) -> List[Tuple[int, int]]:
    """Parts of the disjoint sorted intervals ``a`` outside those of ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class ChipTime:
    busy_ns: int                 # union of all device operations
    collective_ns: int           # union of collective operations
    exposed_collective_ns: int   # collective time with no other op running


def clip(ops: List[Event], window: Tuple[int, int]) -> List[Event]:
    """The parts of the operations that fall inside [start, end)."""
    lo, hi = window
    return [(n, max(s, lo), min(s + d, hi) - max(s, lo))
            for n, s, d in ops if s < hi and s + d > lo]


def chip_times(ops: List[Event], window: Tuple[int, int]) -> ChipTime:
    """One chip's busy and collective time inside the window."""
    ops = clip(ops, window)
    busy = union((s, s + d) for _, s, d in ops)
    coll = union((s, s + d) for n, s, d in ops if COLLECTIVE.search(n))
    # a loop's own span covers the collectives in its body: it is no compute
    compute = union((s, s + d) for n, s, d in ops
                    if not COLLECTIVE.search(n) and not CONTAINER.match(n))
    return ChipTime(length(busy), length(coll), length(subtract(coll, compute)))


def top_ops(trace: Trace, window: Tuple[int, int], n: int = 10
            ) -> List[Tuple[str, float]]:
    """Operations that took the most device time in the window, in seconds
    averaged over chips; numbered instances of one operation (``fusion.12``)
    count as one."""
    tot: Dict[str, float] = {}
    for ops in trace.device_ops.values():
        for name, _, d in clip(ops, window):
            if CONTAINER.match(name):
                continue
            key = re.sub(r"\.\d+$", "", name)
            tot[key] = tot.get(key, 0.0) + d
    chips = max(len(trace.device_ops), 1)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / chips / 1e9) for k, v in ranked]


def idle_gaps(trace: Trace, window: Tuple[int, int], n: int = 10,
              marker: str = "") -> List[Tuple[str, float]]:
    """The longest stretches of the window in which the first chip ran
    nothing, each named by the host span that overlapped it most (not the
    ``marker`` span that makes the window)."""
    chip = sorted(trace.device_ops)[0] if trace.device_ops else None
    busy = union((s, s + d) for _, s, d in trace.device_ops.get(chip, []))
    gaps = subtract([window], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        # the most overlap wins; of equal overlaps the innermost (shortest)
        # span says the most
        best = max(((min(e, hs + hd) - max(s, hs), -hd, name)
                    for name, hs, hd in trace.host_spans if name != marker),
                   default=(0, 0, ""))
        label = f"host: {best[2]}" if best[0] > 0 else "host: no span"
        out.append((label, (e - s) / 1e9))
    return out
