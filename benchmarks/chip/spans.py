"""The program's own spans and named scopes in a traced run: what
``yardstick.Trace`` does not keep.

``yardstick.Trace`` holds each chip's device operations and the spans of the
thread that opened the window's marker.  The metrics of the collection and
analysis layers also need the spans of the analysis threads and their
arguments (the submission number that ties a window's ``perfdbg.flush`` on
the training thread to its ``analysis.window`` on a worker), and those of
the step program need each device operation's ``op_name`` path, which names
the ``jax.named_scope`` it was traced under.  ``ProgramTrace`` reads both
from the run's ``.xplane.pb``; ``for_run`` finds that file for the metric
readers.  Nothing here imports the system under test.

Where the ``op_name`` is: a device operation's event may carry it as a stat
(``tf_op``), its event metadata may (``tf_op``, or an HLO text with
``op_name="..."`` under ``long_name``), or the HLO text that names the event
may.  ``jax.profiler.ProfileData`` shows neither metadata stats nor
metadata ids, so the metadata are read from the file's protobuf wire format
here (``_device_metadata``); ``ProgramTrace.scope_source`` says which of the
three gave the paths.  A fusion carries the ``op_name`` of its root
operation.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import tempfile
from typing import Dict, Iterator, List, Optional, Tuple

import yardstick

# the spans the program opens (docs/performance.md) and the benchmark's marker
SPAN_NAMES = frozenset((
    "bench_window", "train", "region.data", "region.step",
    "region.checkpoint", "perfdbg.record", "perfdbg.flush",
    "analysis.window", "analysis.assemble", "analysis.external",
    "analysis.external_root_causes", "analysis.internal",
    "analysis.internal_root_causes", "analysis.diagnosis",
    "analysis.straggler"))
MARKER = "bench_window"
OP_NAME = re.compile(r'op_name="([^"]*)"')

Span = Tuple[str, int, int, Dict[str, object]]   # name, start ns, ns, args


@dataclasses.dataclass
class ProgramTrace:
    """Host spans of every thread (only ``SPAN_NAMES``), keyed by the
    thread's line in the trace, and each device operation's ``op_name``
    path by its instruction name (``yardstick.op_name``)."""
    thread_spans: Dict[str, List[Span]] = dataclasses.field(
        default_factory=dict)
    op_scopes: Dict[str, str] = dataclasses.field(default_factory=dict)
    scope_source: str = ""

    @classmethod
    def from_json(cls, d: Dict) -> "ProgramTrace":
        return cls({t: [(n, s, dur, dict(args)) for n, s, dur, args in v]
                    for t, v in d.get("thread_spans", {}).items()},
                   dict(d.get("op_scopes", {})), d.get("scope_source", ""))

    @classmethod
    def from_xspace(cls, path: str) -> "ProgramTrace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        threads: Dict[str, List[Span]] = {}
        scopes: Dict[str, str] = {}
        source = ""
        for plane in pd.planes:
            if plane.name.startswith("/host:"):
                for i, line in enumerate(plane.lines):
                    spans = [(e.name, int(e.start_ns), int(e.duration_ns),
                              dict(e.stats)) for e in line.events
                             if e.name in SPAN_NAMES]
                    if spans:
                        threads[f"{plane.name}#{i}:{line.name}"] = spans
            elif plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name != yardstick.OPS_LINE:
                        continue
                    for e in line.events:
                        name = yardstick.op_name(e.name)
                        if name in scopes:
                            continue
                        stats = dict(e.stats)
                        if isinstance(stats.get("tf_op"), str):
                            scopes[name] = stats["tf_op"].rstrip(":")
                            source = "event stat tf_op"
                        elif OP_NAME.search(e.name):
                            scopes[name] = OP_NAME.search(e.name).group(1)
                            source = "HLO text of the event name"
        if not scopes:
            with open(path, "rb") as f:
                for text, stats in _device_metadata(f.read()):
                    # tf_op is "<op_name>:<op_type>", the type empty
                    path_ = stats.get("tf_op", "").rstrip(":") or next(
                        (m.group(1) for v in stats.values()
                         for m in [OP_NAME.search(v)] if m), None)
                    if path_:
                        scopes.setdefault(yardstick.op_name(text), path_)
                        source = ("metadata stat tf_op" if "tf_op" in stats
                                  else "metadata stat with HLO text")
        return cls(threads, scopes, source)

    def spans(self, name: str) -> List[Span]:
        """Every span called ``name``, on any thread, by start."""
        return sorted((sp for v in self.thread_spans.values() for sp in v
                       if sp[0] == name), key=lambda sp: sp[1])


def for_run(run) -> Optional[ProgramTrace]:
    """The program trace of a traced run, read once and kept on the run.
    The harness removes its trace directory after the readers ran; until
    then it lies under ``<tmp>/bench_*/trace``.  Of the files there, the
    run's is the one whose marker span is the run's traced window."""
    if not hasattr(run, "program_trace"):
        run.program_trace = None
        if run.trace is not None:
            found = glob.glob(os.path.join(
                tempfile.gettempdir(), "bench_*", "trace", "plugins",
                "profile", "*", "*.xplane.pb"))
            for path in sorted(found, key=os.path.getmtime, reverse=True):
                pt = ProgramTrace.from_xspace(path)
                if any((s, s + d) == tuple(run.trace_window)
                       for _, s, d, _ in pt.spans(MARKER)):
                    run.program_trace = pt
                    break
    return run.program_trace


def scope_ms(run, scope: str) -> Optional[float]:
    """Device time per traced step of the leaf operations (containers left
    out, as in ``yardstick.top_ops``) whose ``op_name`` path has the
    component ``scope`` (``.../wkv/...`` or ``transpose(jvp(wkv))``),
    averaged over chips; None where no operation has it."""
    pt = for_run(run)
    if pt is None or not run.traced_steps:
        return None
    rx = re.compile(rf"(^|[/(]){re.escape(scope)}($|[/)])")
    total, seen = 0, False
    for ops in run.trace.device_ops.values():
        for name, _, d in yardstick.clip(ops, run.trace_window):
            path = pt.op_scopes.get(name)
            if path and rx.search(path) and \
                    not yardstick.CONTAINER.match(name):
                total += d
                seen = True
    if not seen:
        return None
    return total / len(run.trace.device_ops) / 1e6 / run.traced_steps


# ---------------------------------------------------------------------------
# The protobuf wire format, as far as the device planes' metadata need
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of the message in buf[lo:hi]: an int for a
    varint, a (start, end) pair for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"wire type {kind} in an XSpace")
        yield key >> 3, v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _device_metadata(buf: bytes) -> Iterator[Tuple[str, Dict[str, str]]]:
    """(name, its string stats by stat name) of every event metadata of the
    device planes of a serialized XSpace (XSpace.planes = 1; XPlane: name 2,
    event_metadata 4, stat_metadata 5; map entries: key 1, value 2;
    XEventMetadata: name 2, stats 5; XStat: metadata_id 1, str_value 5,
    ref_value 7; XStatMetadata: id 1, name 2)."""
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g in (4, 5):
                value = next((w for h, w in _fields(buf, *v) if h == 2), None)
                if value is None:
                    continue
                if g == 4:
                    events.append(value)
                else:
                    sm = dict(_fields(buf, *value))
                    if 1 in sm and 2 in sm:
                        stat_names[sm[1]] = _text(buf, sm[2])
        if not name.startswith("/device:"):
            continue
        for ev in events:
            text, stats = "", {}
            for h, w in _fields(buf, *ev):
                if h == 2:
                    text = _text(buf, w)
                elif h == 5:
                    st = dict(_fields(buf, *w))
                    key = stat_names.get(st.get(1))
                    if key is None:
                        continue
                    if 5 in st:
                        stats[key] = _text(buf, st[5])
                    elif 7 in st and st[7] in stat_names:
                        stats[key] = stat_names[st[7]]
            yield text, stats
