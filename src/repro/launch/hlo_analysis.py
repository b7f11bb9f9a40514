"""Trip-aware cost analysis of post-SPMD HLO text.

``Compiled.cost_analysis()`` counts while-loop bodies ONCE, which silently
drops ~n_layers x of the real cost for scan-over-layers programs (verified
against a probe in tests/test_hlo_analysis.py).  This module parses the
compiled per-device HLO text and aggregates, multiplying while bodies by
their trip counts:

  flops            dots (2*M*N*K), convolutions (valid window taps; the TPU
                   compiler emits most dots as convolutions), elementwise 1/el
  hbm bytes        operands+results of top-level (fusion-boundary) ops
  collective bytes per kind (all-reduce / all-gather / reduce-scatter /
                   all-to-all / collective-permute), result-shape proxy

The model is structural (no wall-clock): exactly what the §Roofline terms
need on a CPU-only container.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

_DT_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
             "f8e5m2": 1, "f8e4m3fn": 1, "s64": 8, "u64": 8, "s32": 4,
             "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
             "c64": 8, "c128": 16}

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[^=]*?\)?)\s*([\w\-]+)\(")

_SKIP_BYTES = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "after-all", "partition-id", "replica-id", "iota",
               "rng-bit-generator", "opt-barrier"}


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES[dt]
    return total


def _type_numel(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


@dataclasses.dataclass
class Op:
    name: str
    type_str: str
    opcode: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]
    types: Dict[str, str]           # op name -> result type string


@dataclasses.dataclass
class Stats:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: Optional[Dict[str, float]] = None
    collective_counts: Optional[Dict[str, float]] = None

    def __post_init__(self):
        if self.collective_bytes is None:
            self.collective_bytes = {k: 0.0 for k in COLLECTIVE_KINDS}
        if self.collective_counts is None:
            self.collective_counts = {k: 0.0 for k in COLLECTIVE_KINDS}

    def add(self, other: "Stats", mult: float = 1.0) -> None:
        self.flops += mult * other.flops
        self.bytes += mult * other.bytes
        for k in COLLECTIVE_KINDS:
            self.collective_bytes[k] += mult * other.collective_bytes[k]
            self.collective_counts[k] += mult * other.collective_counts[k]

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())

    def as_dict(self) -> Dict:
        return {"flops": self.flops, "bytes": self.bytes,
                "collective_bytes": dict(self.collective_bytes),
                "collective_counts": dict(self.collective_counts),
                "total_collective_bytes": self.total_collective_bytes}


_COMMENT_RE = re.compile(r"/\*.*?\*/")
# a shape's layout, e.g. ``{2,1,0:T(8,128)(2,1)S(1)}`` in TPU HLO: its tile
# parens would otherwise read as an opcode call
_LAYOUT_RE = re.compile(r"\]\{[^{}]*\}")


def parse_module(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in hlo.splitlines():
        # strip /*index=N*/ comments: large tuple types embed them and the
        # '=' inside breaks op matching (that silently hid every big while)
        line = _LAYOUT_RE.sub("]", _COMMENT_RE.sub("", raw)).rstrip()
        stripped = line.strip()
        if cur is None:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?.*\{\s*$",
                         line)
            if m and ("->" in line or line.startswith("ENTRY")
                      or line.lstrip().startswith("%")):
                cur = Computation(m.group(1), [], {})
            continue
        if stripped == "}" or stripped.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if m:
            name, type_str, opcode = m.groups()
            op = Op(name, type_str.strip(), opcode, stripped)
            cur.ops.append(op)
            cur.types[name] = type_str.strip()
        else:
            m2 = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?.*?\)?)\s*parameter\(",
                          line)
            if m2:
                cur.types[m2.group(1)] = m2.group(2).strip()
    return comps


def _operands(line: str) -> List[str]:
    """Operand names of an op line.  Handles both bare operands
    (``dot(%a, %b)``) and the typed form newer XLA prints
    (``dot(f32[128,64]{1,0} %a, ...)``) whose shape commas must not split.
    The operand list starts at the paren after the opcode — for tuple-typed
    results the first '(' in the line is the result type, not the call."""
    m = _OP_RE.match(line)
    inner = line[m.end():] if m else line.split("(", 1)[1]
    pdepth, bdepth = 1, 0      # parens; brackets+braces (shape/layout commas)
    buf, toks = "", []
    for ch in inner:
        if ch == "(":
            pdepth += 1
        elif ch == ")":
            pdepth -= 1
            if pdepth == 0:
                break
        elif ch in "[{":
            bdepth += 1
        elif ch in "]}":
            bdepth -= 1
        if ch == "," and pdepth == 1 and bdepth == 0:
            toks.append(buf)
            buf = ""
        else:
            buf += ch
    if buf.strip():
        toks.append(buf)
    out = []
    for tok in toks:
        m = re.search(r"%([\w.\-]+)\s*$", tok.strip())
        if m:
            out.append(m.group(1))
        elif re.match(r"^[\w.\-]+$", tok.strip()):
            out.append(tok.strip())
    return out


def _called(line: str, key: str) -> Optional[str]:
    m = re.search(key + r"=%?([\w.\-]+)", line)
    return m.group(1) if m else None


def _trip_count(cond: Computation) -> int:
    """Scan loops compare the counter against a constant; take the compare's
    constant operand (fall back to the largest s32 constant)."""
    consts: Dict[str, int] = {}
    for op in cond.ops:
        m = re.search(r"constant\((\d+)\)", op.line)
        if m and op.line.split("=")[1].strip().startswith("s32[]"):
            consts[op.name] = int(m.group(1))
    for op in cond.ops:
        if op.opcode == "compare":
            for o in _operands(op.line):
                if o in consts:
                    return consts[o]
    return max(consts.values(), default=1)


def _dot_flops(op: Op, comp: Computation) -> float:
    result_numel = _type_numel(op.type_str)
    ops = _operands(op.line)
    if not ops:
        return 0.0
    lhs_type = comp.types.get(ops[0], "")
    dims = []
    m = _SHAPE_RE.search(lhs_type)
    if m:
        dims = [int(d) for d in m.group(2).split(",") if d]
    mc = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.line)
    k = 1
    if mc and dims:
        for ix in mc.group(1).split(","):
            if ix and int(ix) < len(dims):
                k *= dims[int(ix)]
    return 2.0 * result_numel * k


def _dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    return [int(d) for d in m.group(2).split(",") if d] if m else []


def _window(line: str) -> Dict[str, List[List[int]]]:
    """``window={size=5x4 stride=4x1 pad=0_0x3_3 lhs_dilate=5x1}`` ->
    {attr: per-spatial-dim int lists} (pad entries are [lo, hi])."""
    m = re.search(r"window=\{([^}]*)\}", line)
    out: Dict[str, List[List[int]]] = {}
    for item in (m.group(1).split() if m else ()):
        key, _, val = item.partition("=")
        out[key] = [[int(x) for x in d.split("_")] for d in val.split("x")]
    return out


def _conv_flops(op: Op, comp: Computation) -> float:
    """2 x multiply-adds of a convolution, counting only the (output
    position, window tap) pairs that land on a real input element (padding
    and lhs-dilation holes excluded).  The TPU compiler rewrites most
    dot_generals as convolutions, so this is where a TPU step's matmul
    flops live."""
    m = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", op.line)
    ops = _operands(op.line)
    if not m or len(ops) < 2:
        return 2.0 * _type_numel(op.type_str)
    lhs_l, rhs_l, out_l = m.groups()
    lhs, rhs = _dims(comp.types.get(ops[0], "")), _dims(comp.types.get(ops[1], ""))
    out = _dims(op.type_str)
    if len(lhs) != len(lhs_l) or len(rhs) != len(rhs_l) or len(out) != len(out_l):
        return 2.0 * _type_numel(op.type_str)
    win = _window(op.line)
    macs = float(np.prod(out)) * rhs[rhs_l.index("i")]
    for j, (w,) in enumerate(win.get("size", [])):
        n, mo = lhs[lhs_l.index(str(j))], out[out_l.index(str(j))]
        s = win["stride"][j][0] if "stride" in win else 1
        lo = win["pad"][j][0] if "pad" in win else 0
        ld = win["lhs_dilate"][j][0] if "lhs_dilate" in win else 1
        rd = win["rhs_dilate"][j][0] if "rhs_dilate" in win else 1
        p = (np.arange(mo)[:, None] * s + np.arange(w)[None, :] * rd - lo)
        valid = (p >= 0) & (p <= (n - 1) * ld) & (p % ld == 0)
        macs *= valid.sum() / mo
    return 2.0 * macs


class Analyzer:
    def __init__(self, hlo: str):
        self.comps = parse_module(hlo)
        self.entry = self._find_entry(hlo)
        self._memo: Dict[Tuple[str, bool], Stats] = {}

    def _find_entry(self, hlo: str) -> str:
        m = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
        if m:
            return m.group(1)
        return next(iter(self.comps))

    def stats(self) -> Stats:
        return self._comp_stats(self.entry, top=True)

    def stats_by_computation(self) -> Dict[str, Stats]:
        """Per-computation trip-aware aggregates: each computation's own
        standalone cost (whiles inside it multiplied by their trip counts,
        fusion callees folded in), keyed by computation name.  The entry's
        value equals :meth:`stats`.  This is the public feed for cost
        providers (``perfdbg.costs.HloCosts``) that re-attribute named
        computations to code regions; note a callee's standalone stats are
        *not* disjoint from its caller's — attribution must pick disjoint
        computations (HloCosts documents this)."""
        return {name: self._comp_stats(name, top=True)
                for name in self.comps}

    def _comp_stats(self, name: str, top: bool) -> Stats:
        key = (name, top)
        if key in self._memo:
            return self._memo[key]
        comp = self.comps.get(name)
        st = Stats()
        if comp is None:
            self._memo[key] = st
            return st
        self._memo[key] = st  # break cycles defensively
        for op in comp.ops:
            oc = op.opcode
            if oc == "while":
                body = _called(op.line, "body")
                cond = _called(op.line, "condition")
                trips = _trip_count(self.comps[cond]) if cond in self.comps else 1
                if body:
                    st.add(self._comp_stats(body, top=True), mult=max(trips, 1))
                continue
            if oc in ("fusion", "call", "custom-call"):
                callee = _called(op.line, "calls") or _called(op.line, "to_apply")
                if callee:
                    sub = self._comp_stats(callee, top=False)
                    st.flops += sub.flops
                    for k in COLLECTIVE_KINDS:
                        st.collective_bytes[k] += sub.collective_bytes[k]
                        st.collective_counts[k] += sub.collective_counts[k]
                if top:
                    st.bytes += self._io_bytes(op, comp)
                continue
            if oc == "conditional":
                branches = re.findall(r"(?:true_computation|false_computation|branch_computations=\{)([^,}]+)",
                                      op.line)
                subs = [self._comp_stats(b.strip("%"), top=True)
                        for b in branches if b.strip("%") in self.comps]
                if subs:
                    best = max(subs, key=lambda s: s.flops)
                    st.add(best)
                continue
            base = oc.replace("-start", "")
            if base in COLLECTIVE_KINDS and not oc.endswith("-done"):
                b = _type_bytes(op.type_str)
                st.collective_bytes[base] += b
                st.collective_counts[base] += 1
                if top:
                    st.bytes += self._io_bytes(op, comp)
                continue
            if oc.endswith("-done"):
                continue
            if oc == "dot":
                st.flops += _dot_flops(op, comp)
                if top:
                    st.bytes += self._io_bytes(op, comp)
                continue
            if oc == "convolution":
                st.flops += _conv_flops(op, comp)
                if top:
                    st.bytes += self._io_bytes(op, comp)
                continue
            if oc in _SKIP_BYTES:        # no compute, no traffic
                continue
            # elementwise & everything else: 1 flop/elem
            st.flops += _type_numel(op.type_str)
            if top:
                st.bytes += self._io_bytes(op, comp)
        self._memo[key] = st
        return st

    def _io_bytes(self, op: Op, comp: Computation) -> float:
        total = float(_type_bytes(op.type_str))
        callee = None
        if op.opcode == "fusion":
            callee = self.comps.get(_called(op.line, "calls") or "")
        operands = _operands(op.line)
        sliced = self._sliced_params(callee) if callee else {}
        for i, o in enumerate(operands):
            t = comp.types.get(o)
            if not t:
                continue
            if i in sliced:
                # the fusion only dynamic-slices this operand: HBM reads the
                # slice, not the buffer (scan xs / in-place cache updates
                # were otherwise counted at full size every iteration)
                total += sliced[i]
            else:
                total += _type_bytes(t)
        return total

    def _sliced_params(self, callee: Computation) -> Dict[int, float]:
        """param position -> bytes actually read, for fusion params whose
        only consumers are dynamic-slice (read slice) or which serve as the
        in-place target of dynamic-update-slice (read+write the update)."""
        key = ("sliced", callee.name)
        if key in self._memo:                      # type: ignore[comparison-overlap]
            return self._memo[key]                 # type: ignore[return-value]
        params: Dict[str, int] = {}
        for o in callee.ops:
            if "parameter(" in o.line:
                m = re.search(r"parameter\((\d+)\)", o.line)
                if m:
                    params[o.name] = int(m.group(1))
        # also capture parameters recorded only in types (ROOT-less parse)
        for name, t in callee.types.items():
            if name not in params and name.startswith("param"):
                continue
        out: Dict[int, float] = {}
        for pname, pix in params.items():
            consumers = [o for o in callee.ops
                         if pname in _operands(o.line) and o.name != pname]
            if not consumers:
                continue
            total = 0.0
            ok = True
            for c in consumers:
                if c.opcode == "dynamic-slice":
                    total += _type_bytes(c.type_str)
                elif (c.opcode == "dynamic-update-slice"
                      and _operands(c.line)[0] == pname):
                    ops_c = _operands(c.line)
                    upd = callee.types.get(ops_c[1], "") if len(ops_c) > 1 else ""
                    total += 2.0 * _type_bytes(upd)
                else:
                    ok = False
                    break
            if ok and total > 0:
                out[pix] = total
        self._memo[key] = out                      # type: ignore[assignment]
        return out


def analyze(hlo: str) -> Dict:
    return Analyzer(hlo).stats().as_dict()


def top_bytes_contributors(hlo: str, top: int = 25) -> List[Tuple[str, float, float]]:
    """(op_name metadata tag, bytes x trips, flops x trips) for the heaviest
    HBM-traffic ops — the profile view the perf loop reads."""
    a = Analyzer(hlo)
    contrib: Dict[str, List[float]] = {}

    def visit(comp_name: str, mult: float, top_level: bool):
        comp = a.comps.get(comp_name)
        if comp is None:
            return
        for op in comp.ops:
            oc = op.opcode
            if oc == "while":
                body = _called(op.line, "body")
                cond = _called(op.line, "condition")
                trips = _trip_count(a.comps[cond]) if cond in a.comps else 1
                if body:
                    visit(body, mult * max(trips, 1), True)
                continue
            if oc in _SKIP_BYTES or oc.endswith("-done"):
                continue
            m = re.search(r'op_name="([^"]*)"', op.line)
            tag = m.group(1) if m else oc
            tag = re.sub(r"\[[^\]]*\]", "", tag)[:120]
            b = a._io_bytes(op, comp) * mult if top_level else 0.0
            f = 0.0
            if oc == "dot":
                f = _dot_flops(op, comp) * mult
            if b or f:
                cur = contrib.setdefault(tag, [0.0, 0.0])
                cur[0] += b
                cur[1] += f

    visit(a.entry, 1.0, True)
    rows = sorted(((k, v[0], v[1]) for k, v in contrib.items()),
                  key=lambda r: -r[1])
    return rows[:top]
