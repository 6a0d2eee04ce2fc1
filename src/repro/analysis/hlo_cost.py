"""Loop-aware cost analysis of optimized HLO text.

XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE — a
scanned 95-layer model reports ~1 layer of FLOPs, and collectives inside
the layer scan disappear from naive HLO greps. This analyzer re-walks the
optimized HLO *with loop multiplication*:

  * computations are parsed into op lists with result/operand shapes;
  * ``while`` ops multiply their body+condition totals by the trip count
    XLA annotates in ``backend_config={"known_trip_count":{"n":...}}``;
  * FLOPs come from ``dot``/``convolution`` ops (2 × result × contraction),
    recursing into fusions and called computations;
  * memory bytes are counted at FUSION BOUNDARIES (operands + result of
    top-level ops), which approximates real HBM traffic of fused chains —
    layout no-ops (tuple/bitcast/parameter/get-tuple-element/constant)
    are free;
  * collective bytes/counts are accumulated per kind with multipliers.

Shapes in optimized HLO are post-SPMD = per-device, so all totals are
per-device. The contracts and ``launch/cache_dryrun`` read it.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "token": 0, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([^\s]+)\s*\(.*\)\s*->")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_RE = re.compile(r"(?:calls|body|condition|branch_computations|to_apply)="
                      r"\{?%?([\w\.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OP_RE = re.compile(r"^\(?[a-z0-9\[\],\s\{\}/_\*]*?\)?\s*"
                    r"([a-z][a-z0-9\-]*)\(")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
FREE_OPS = {"tuple", "get-tuple-element", "parameter", "constant", "bitcast",
            "after-all", "copy-start", "copy-done", "partition-id",
            "replica-id", "iota", "reshape"}


def _shape_elems_bytes(text: str) -> tuple[int, int]:
    """Total (elements, bytes) over all shapes in a type string."""
    elems = total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        total += n * _DTYPE_BYTES[dt]
    return elems, total


def _bytes_by_dtype(text: str) -> dict[str, int]:
    """Bytes per dtype over all shapes in a type string — the s8-vs-f32
    split the quantized-residency contracts gate on (a quantized trace
    must move its table bytes as s8; an f32 rematerialization of the
    int8 table shows up here as f32 bytes it should not have)."""
    out: dict[str, int] = {}
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[dt] = out.get(dt, 0) + n * _DTYPE_BYTES[dt]
    return out


def _primary_dtype(text: str) -> str | None:
    for dt, _dims in _SHAPE_RE.findall(text):
        if dt in _DTYPE_BYTES:
            return dt
    return None


@dataclass
class Op:
    name: str
    kind: str
    result_type: str
    operands: list[str]
    line: str
    trip: int = 1
    calls: list[str] = field(default_factory=list)


@dataclass
class Totals:
    flops: float = 0.0
    bytes: float = 0.0
    bytes_by_dtype: dict = field(default_factory=dict)
    collective_bytes: dict = field(default_factory=dict)
    collective_counts: dict = field(default_factory=dict)

    def add(self, other: "Totals", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        for k, v in other.bytes_by_dtype.items():
            self.bytes_by_dtype[k] = self.bytes_by_dtype.get(k, 0) + v * mult
        for k, v in other.collective_bytes.items():
            self.collective_bytes[k] = self.collective_bytes.get(k, 0) + v * mult
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = self.collective_counts.get(k, 0) + v * mult

    def count_bytes(self, type_text: str) -> float:
        """Charge every shape in ``type_text`` to the total AND to its
        dtype bucket. Returns the bytes charged."""
        nbytes = 0.0
        for dt, v in _bytes_by_dtype(type_text).items():
            self.bytes_by_dtype[dt] = self.bytes_by_dtype.get(dt, 0) + v
            nbytes += v
        self.bytes += nbytes
        return nbytes

    def count_bytes_as(self, nbytes: float, dtype: str | None) -> None:
        """Charge pre-computed bytes to one dtype bucket (partially
        touched operands, where the byte count is not the full shape)."""
        self.bytes += nbytes
        key = dtype or "unknown"
        self.bytes_by_dtype[key] = self.bytes_by_dtype.get(key, 0) + nbytes

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())

    def to_dict(self) -> dict:
        return {"flops": self.flops, "bytes": self.bytes,
                "bytes_by_dtype": dict(self.bytes_by_dtype),
                "collective_bytes": dict(self.collective_bytes),
                "collective_counts": dict(self.collective_counts),
                "total_collective_bytes": self.total_collective_bytes}


def _parse_computations(hlo: str) -> dict[str, list[Op]]:
    comps: dict[str, list[Op]] = {}
    cur: list[Op] | None = None
    shapes: dict[str, str] = {}
    for raw in hlo.splitlines():
        line = raw.strip()
        if not line or line.startswith("//"):
            continue
        if line.endswith("{") and ("->" in line) and "(" in line:
            m = _COMP_HDR_RE.match(line)
            if m:
                name = m.group(1)
                cur = comps.setdefault(name, [])
                continue
        if line == "}" or line.startswith("}"):
            continue
        if cur is None:
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        # result type = everything before the op token
        om = re.search(r"\b([a-z][a-z0-9\-]*)\(", rhs)
        kind = om.group(1) if om else "unknown"
        result_type = rhs[:om.start()] if om else rhs
        operands = re.findall(r"%([\w\.\-]+)", rhs[om.end():] if om else "")
        trip = 1
        tm = _TRIP_RE.search(line)
        if tm:
            trip = int(tm.group(1))
        calls = _CALL_RE.findall(line)
        cur.append(Op(name=name, kind=kind, result_type=result_type,
                      operands=operands, line=line, trip=trip, calls=calls))
    return comps


def _dot_flops(op: Op, sym: dict[str, str]) -> float:
    _, _ = sym, op
    res_elems, _ = _shape_elems_bytes(op.result_type)
    cm = _CONTRACT_RE.search(op.line)
    contract = 1
    if cm and op.operands:
        lhs_type = sym.get(op.operands[0], "")
        dims = []
        for dt, dd in _SHAPE_RE.findall(lhs_type):
            dims = [int(x) for x in dd.split(",") if x]
            break
        for idx in cm.group(1).split(","):
            if idx and dims and int(idx) < len(dims):
                contract *= dims[int(idx)]
    return 2.0 * res_elems * contract


_PARAM_RE = re.compile(r"parameter\((\d+)\)")


def _sliced_param_bytes(op: Op, comps: dict) -> dict[int, int]:
    """Fusion params consumed via dynamic-slice / gather /
    dynamic-update-slice → bytes actually touched per execution."""
    touched: dict[int, int] = {}
    for callee in op.calls:
        ops = comps.get(callee)
        if ops is None:
            continue
        pidx = {}
        for iop in ops:
            pm = _PARAM_RE.search(iop.line)
            if pm and iop.kind == "parameter":
                pidx[iop.name] = int(pm.group(1))
        for iop in ops:
            if iop.kind in ("dynamic-slice", "gather"):
                src = iop.operands[0] if iop.operands else None
                if src in pidx:
                    _, rb = _shape_elems_bytes(iop.result_type)
                    i = pidx[src]
                    touched[i] = touched.get(i, 0) + rb
            elif iop.kind == "dynamic-update-slice":
                src = iop.operands[0] if iop.operands else None
                upd = iop.operands[1] if len(iop.operands) > 1 else None
                if src in pidx:
                    ub = _shape_elems_bytes(
                        _op_type(ops, upd))[1] if upd else 0
                    i = pidx[src]
                    # in-place RMW ≈ 2× the update bytes
                    touched[i] = touched.get(i, 0) + 2 * ub
    return touched


def _op_type(ops: list[Op], name: str | None) -> str:
    for o in ops:
        if o.name == name:
            return o.result_type
    return ""


def analyze(hlo: str, entry: str | None = None) -> Totals:
    comps = _parse_computations(hlo)
    if entry is None:
        m = re.search(r"ENTRY\s+%?([^\s]+)\s*\(", hlo)
        entry = m.group(1) if m else next(iter(comps))
    memo: dict[str, Totals] = {}

    def comp_totals(name: str, stack: tuple = ()) -> Totals:
        if name in memo:
            return memo[name]
        if name in stack or name not in comps:
            return Totals()
        t = Totals()
        sym = {op.name: op.result_type for op in comps[name]}
        for op in comps[name]:
            if op.kind in FREE_OPS:
                continue
            base = op.kind.replace("-start", "")
            if base in COLLECTIVES:
                rbytes = t.count_bytes(op.result_type)
                t.collective_bytes[base] = \
                    t.collective_bytes.get(base, 0) + rbytes
                t.collective_counts[base] = \
                    t.collective_counts.get(base, 0) + 1
                continue
            if op.kind.endswith("-done"):
                continue
            if op.kind == "while":
                body = Totals()
                for callee in op.calls:
                    body.add(comp_totals(callee, stack + (name,)))
                t.add(body, mult=op.trip)
                continue
            if op.kind in ("fusion", "call", "conditional", "custom-call",
                           "reduce", "sort", "scatter", "map",
                           "select-and-scatter"):
                # boundary bytes: result + operands, with sliced/gathered
                # operands charged at the bytes actually touched (a
                # dynamic-slice fusion inside a scan reads ONE slice per
                # iteration, not the whole stacked tensor).
                t.count_bytes(op.result_type)
                touched = _sliced_param_bytes(op, comps)
                for i, o in enumerate(op.operands):
                    otype = sym.get(o, "")
                    full = _shape_elems_bytes(otype)[1]
                    if i in touched and touched[i] < full:
                        t.count_bytes_as(touched[i], _primary_dtype(otype))
                    else:
                        t.count_bytes(otype)
                # recurse for dots hidden inside (flops only)
                for callee in op.calls:
                    inner = comp_totals(callee, stack + (name,))
                    t.flops += inner.flops
                    for k, v in inner.collective_bytes.items():
                        t.collective_bytes[k] = t.collective_bytes.get(k, 0) + v
                    for k, v in inner.collective_counts.items():
                        t.collective_counts[k] = t.collective_counts.get(k, 0) + v
                continue
            if op.kind in ("dot", "convolution"):
                t.flops += _dot_flops(op, sym)
                t.count_bytes(op.result_type)
                for o in op.operands:
                    t.count_bytes(sym.get(o, ""))
                continue
            # generic op: boundary bytes + 1 flop/elem for arithmetic
            t.count_bytes(op.result_type)
            for o in op.operands:
                t.count_bytes(sym.get(o, ""))
        memo[name] = t
        return t

    # Only memoize per computation — multipliers applied at call sites.
    return comp_totals(entry)


def analyze_compiled(compiled) -> dict:
    return analyze(compiled.as_text()).to_dict()
