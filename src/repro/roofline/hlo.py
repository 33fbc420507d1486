"""Roofline-term extraction from compiled (post-SPMD) HLO text.

XLA's ``compiled.cost_analysis()`` does NOT expand while-loop bodies, so
for scanned-layer programs it undercounts FLOPs/bytes by the trip count.
This module re-derives all three roofline inputs from the HLO text with
call-graph expansion:

  * dot/convolution FLOPs            (2 · prod(result) · prod(contraction))
  * HBM traffic at fusion boundaries (operands + results of real kernels)
  * collective bytes-on-wire         (ring-algorithm factors per op)

While-loop trip counts are recovered from ``known_trip_count`` when
present, else from the loop-condition constant.  Fusion computations are
walked for FLOPs but their *internal* ops contribute no HBM traffic —
only the fusion boundary does (that is what fusion means).
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# Ops whose operands+results count as HBM traffic.  Standalone elementwise
# ops (convert/add/tanh/...) are intentionally EXCLUDED: the CPU backend
# leaves them unfused (e.g. bf16→f32 converts around every dot), while the
# TPU target fuses them into neighbours — counting them would triple-count
# the same tensors.  Fusion boundaries + matmuls + data movement remain.
_TRAFFIC_OPS = _COLLECTIVES + (
    "fusion", "dot", "convolution", "copy", "dynamic-slice",
    "dynamic-update-slice", "scatter", "gather", "reduce", "transpose",
    "select-and-scatter", "sort", "concatenate", "reduce-window",
    "cholesky", "triangular-solve", "rng", "map", "custom-call",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# type is either a parenthesized tuple (may contain /*index=N*/ comments
# and, in TPU layouts, one level of parens such as {1,0:T(8,128)(2,1)}) or
# a single space-free token like bf16[8,16]{1,0}
_OP_LINE = re.compile(
    r"^(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(\((?:[^()]|\([^()]*\))*\)|\S+)\s+"
    r"([\w\-]+)\((.*)$")
_COMP_HEADER = re.compile(r"^(?:ENTRY )?%([\w\.\-]+)\s*\(")
_WHILE_RE = re.compile(
    r"condition=%?([\w\.\-]+).*?body=%?([\w\.\-]+)"
    r"|body=%?([\w\.\-]+).*?condition=%?([\w\.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count.{0,12}?[\'"]?n[\'"]?\s*[:=]\s*'
                      r'[\'"]?(\d+)')


def _shapes(type_str: str) -> List[Tuple[str, List[int]]]:
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        out.append((dt, dims))
    return out


def _type_bytes(type_str: str) -> int:
    return sum(_DTYPE_BYTES[dt] * math.prod(dims) if dims else
               _DTYPE_BYTES[dt] for dt, dims in _shapes(type_str))


def _group_size(line: str, n_devices: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return n_devices


def _wire_bytes(kind: str, bytes_result: int, g: int) -> float:
    if g <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (g - 1) / g * bytes_result
    if kind == "all-gather":
        return (g - 1) / g * bytes_result
    if kind == "reduce-scatter":
        return float((g - 1) * bytes_result)
    if kind == "all-to-all":
        return (g - 1) / g * bytes_result
    if kind == "collective-permute":
        return float(bytes_result)
    return 0.0


@dataclass
class Op:
    name: str
    kind: str
    type_str: str
    args: str
    line: str


@dataclass
class Computation:
    name: str
    ops: List[Op] = field(default_factory=list)
    types: Dict[str, str] = field(default_factory=dict)
    max_const: int = 0


def parse_computations(text: str) -> Tuple[Dict[str, Computation], str]:
    comps: Dict[str, Computation] = {}
    entry = ""
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        ls = raw.strip()
        m = _COMP_HEADER.match(ls)
        if m and ls.endswith("{") and "->" in ls:
            cur = Computation(m.group(1))
            comps[cur.name] = cur
            if ls.startswith("ENTRY"):
                entry = cur.name
            continue
        if cur is None or ls.startswith("}"):
            continue
        om = _OP_LINE.match(ls)
        if om:
            name, type_str, kind, args = om.groups()
            cur.ops.append(Op(name, kind, type_str, args, ls))
            cur.types[name] = type_str
            if kind == "constant":
                cm = re.match(r"^(\d+)\)", args)
                if cm:
                    cur.max_const = max(cur.max_const, int(cm.group(1)))
    return comps, entry


def _operand_names(args: str) -> List[str]:
    # operands appear before the first "), " — parse %names in the call parens
    depth, out, i = 1, [], 0
    buf = ""
    while i < len(args) and depth > 0:
        c = args[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        buf += c
        i += 1
    return re.findall(r"%([\w\.\-]+)", buf)


def _dot_flops(op: Op, comp: Computation) -> float:
    res = _shapes(op.type_str)
    if not res:
        return 0.0
    result_elems = math.prod(res[0][1]) if res[0][1] else 1
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.line)
    operands = _operand_names(op.args)
    if not m or not operands:
        return 2.0 * result_elems
    lhs_t = comp.types.get(operands[0])
    if lhs_t is None:
        return 2.0 * result_elems
    lhs_shapes = _shapes(lhs_t)
    if not lhs_shapes:
        return 2.0 * result_elems
    lhs_dims = lhs_shapes[0][1]
    contract = 1
    for d in (m.group(1).split(",") if m.group(1) else []):
        contract *= lhs_dims[int(d)]
    return 2.0 * result_elems * contract


def _conv_flops(op: Op, comp: Computation) -> float:
    res = _shapes(op.type_str)
    operands = _operand_names(op.args)
    if not res or len(operands) < 2:
        return 0.0
    out_elems = math.prod(res[0][1]) if res[0][1] else 1
    rhs_t = comp.types.get(operands[1])
    k_elems = math.prod(_shapes(rhs_t)[0][1]) if rhs_t and _shapes(rhs_t) else 1
    return 2.0 * out_elems * k_elems      # upper-bound-ish; convs are stubs


def _nonscalar_operand_bytes(op: Op, comp: Computation) -> List[int]:
    out = []
    for o in _operand_names(op.args):
        ot = comp.types.get(o)
        if ot:
            b = _type_bytes(ot)
            if b > 64:
                out.append(b)
    return out


_FOLLOW = {"bitcast", "convert", "copy", "reshape", "transpose"}


def _sliced_param_bytes(called: Computation) -> Dict[int, int]:
    """For a fusion's called computation: parameter index -> effective
    bytes, reduced to the slice size when the parameter is only consumed
    (transitively through bitcast/convert/... chains) by dynamic-slice /
    slice (read) or is the in-place target of a dynamic-update-slice
    (write counts the update size)."""
    param_name: Dict[str, int] = {}
    for o in called.ops:
        if o.kind == "parameter":
            m = re.match(r"^(\d+)\)", o.args)
            if m:
                param_name[o.name] = int(m.group(1))
    uses: Dict[str, List[Op]] = {}
    for o in called.ops:
        for nm in _operand_names(o.args):
            uses.setdefault(nm, []).append(o)

    def slice_bytes(name: str, depth: int = 0) -> Optional[int]:
        """Bytes actually read from `name`, or None if fully consumed."""
        if depth > 8:
            return None
        total = 0
        for u in uses.get(name, []):
            if u.kind in ("dynamic-slice", "slice"):
                total += _type_bytes(u.type_str)
            elif u.kind in _FOLLOW:
                sub = slice_bytes(u.name, depth + 1)
                if sub is None:
                    return None
                total += sub
            else:
                return None
        return total if uses.get(name) else None

    out: Dict[int, int] = {}
    for pname, idx in param_name.items():
        full = _type_bytes(called.types.get(pname, ""))
        ops_using = uses.get(pname, [])
        sb = slice_bytes(pname)
        if sb is not None:
            out[idx] = min(sb, full)
        elif (ops_using and len(ops_using) == 1
              and ops_using[0].kind == "dynamic-update-slice"
              and _operand_names(ops_using[0].args)[:1] == [pname]):
            upd = _operand_names(ops_using[0].args)
            ub = _type_bytes(called.types.get(upd[1], "")) if len(upd) > 1 else 0
            out[idx] = 2 * ub           # read-modify-write of the slice
        else:
            out[idx] = full
    return out


def _op_traffic(op: Op, comp: Computation,
                comps: Dict[str, "Computation"]) -> float:
    res_bytes = _type_bytes(op.type_str)
    if op.kind == "dynamic-slice":
        return 2.0 * res_bytes
    if op.kind == "dynamic-update-slice":
        nb = _nonscalar_operand_bytes(op, comp)
        upd = min(nb) if nb else res_bytes
        return 2.0 * upd
    if op.kind == "fusion":
        cm = _CALLS_RE.search(op.line)
        called = comps.get(cm.group(1)) if cm else None
        total = float(res_bytes)
        operands = _operand_names(op.args)
        sliced = _sliced_param_bytes(called) if called else {}
        for i, o in enumerate(operands):
            ot = comp.types.get(o)
            if not ot:
                continue
            total += sliced.get(i, _type_bytes(ot))
        # in-place DUS fusion: result buffer is not fully written
        if called and any(u.kind == "dynamic-update-slice"
                          for u in called.ops):
            total -= res_bytes
            nb = [v for v in sliced.values()]
            total += min(nb) if nb else 0
        return max(total, 0.0)
    total = float(res_bytes)
    for o in _operand_names(op.args):
        ot = comp.types.get(o)
        if ot:
            total += _type_bytes(ot)
    return total


# No-arithmetic op kinds: fusions composed only of these are data
# movement (loop-state copies) or dtype conversion (the CPU backend's
# bf16->f32 dot-upcast, which TPU performs natively inside the MXU) —
# they are accounted as copy_bytes, not HBM kernel traffic.
_PURE_MOVEMENT = {"parameter", "copy", "bitcast", "get-tuple-element",
                  "tuple", "constant", "reshape", "transpose", "broadcast",
                  "slice", "convert", "dynamic-slice"}


def _is_copy_fusion(op: Op, comps: Dict[str, "Computation"]) -> bool:
    """Fusions whose body is pure data movement (loop-state copies).  The
    CPU backend materializes these; TPU aliases loop-carried state in
    place — they are accounted separately from real HBM traffic."""
    if op.kind == "copy":
        return True
    if op.kind != "fusion":
        return False
    cm = _CALLS_RE.search(op.line)
    called = comps.get(cm.group(1)) if cm else None
    if called is None:
        return False
    return all(o.kind in _PURE_MOVEMENT for o in called.ops)


def _while_loop(op: Op, comps: Dict[str, Computation]
                ) -> Optional[Tuple[str, int]]:
    """(body computation, trip count) of a while op: the trip count from
    ``known_trip_count`` when present, else the loop-condition constant."""
    wm = _WHILE_RE.search(op.line)
    if not wm:
        return None
    cond = wm.group(1) or wm.group(4)
    tm = _TRIP_RE.search(op.line)
    trips = (int(tm.group(1)) if tm else
             max(comps.get(cond, Computation("")).max_const, 1))
    return wm.group(2) or wm.group(3), trips


@dataclass
class Totals:
    flops: float = 0.0
    traffic: float = 0.0
    copy_traffic: float = 0.0
    wire: Dict[str, float] = field(default_factory=dict)

    def add(self, other: "Totals", mult: float = 1.0):
        self.flops += other.flops * mult
        self.traffic += other.traffic * mult
        self.copy_traffic += other.copy_traffic * mult
        for k, v in other.wire.items():
            self.wire[k] = self.wire.get(k, 0.0) + v * mult


def analyze_text(text: str, n_devices: int) -> Dict[str, float]:
    """Loop-expanded per-chip totals: flops, HBM traffic bytes, collective
    wire bytes (by kind + total) and counts."""
    comps, entry = parse_computations(text)
    memo: Dict[Tuple[str, bool], Totals] = {}

    def walk(name: str, inside_fusion: bool, depth: int = 0) -> Totals:
        key = (name, inside_fusion)
        if depth > 24 or name not in comps:
            return Totals()
        if key in memo:
            return memo[key]
        comp = comps[name]
        t = Totals()
        for op in comp.ops:
            if op.kind == "dot":
                t.flops += _dot_flops(op, comp)
            elif op.kind == "convolution":
                t.flops += _conv_flops(op, comp)
            if op.kind.replace("-start", "") in _COLLECTIVES:
                kind = op.kind.replace("-start", "")
                b = _type_bytes(op.type_str)
                if op.kind.endswith("-start"):
                    b //= 2               # start tuples carry (operand, result)
                g = _group_size(op.line, n_devices)
                t.wire[kind] = t.wire.get(kind, 0.0) + _wire_bytes(kind, b, g)
                t.wire[f"{kind}_count"] = t.wire.get(f"{kind}_count", 0) + 1
            # traffic at kernel boundaries only (slice-aware: DS/DUS and
            # fusions that merely slice a big operand count the slice)
            if not inside_fusion and op.kind in _TRAFFIC_OPS:
                b = _op_traffic(op, comp, comps)
                if _is_copy_fusion(op, comps):
                    t.copy_traffic += b
                else:
                    t.traffic += b
            # descend
            if op.kind == "while":
                loop = _while_loop(op, comps)
                if loop:
                    body, trips = loop
                    t.add(walk(body, inside_fusion, depth + 1), trips)
            elif op.kind == "fusion":
                cm = _CALLS_RE.search(op.line)
                if cm:
                    t.add(walk(cm.group(1), True, depth + 1), 1.0)
            elif op.kind in ("call", "conditional", "async-start"):
                for cname in _CALLS_RE.findall(op.line):
                    t.add(walk(cname, inside_fusion, depth + 1), 1.0)
        memo[key] = t
        return t

    t = walk(entry, False)
    out = {"flops": t.flops, "traffic_bytes": t.traffic,
           "copy_bytes": t.copy_traffic}
    out.update(t.wire)
    out["total"] = sum(v for k, v in t.wire.items() if not k.endswith("_count"))
    return out


def collective_bytes(text: str, n_devices: int) -> Dict[str, float]:
    """Wire bytes per chip by collective kind (loop-expanded)."""
    res = analyze_text(text, n_devices)
    return {k: v for k, v in res.items()
            if k not in ("flops", "traffic_bytes")}


def loop_all_reduces(text: str, n_devices: int) -> List[Dict]:
    """Every while loop of a compiled program with the all-reduces its
    body issues per iteration: ``trips`` (the trip count),
    ``all_reduces`` (the count in the body) and ``group_sizes`` (the
    replica-group size of each).  A PT step that fuses its tracks once
    per block shows one loop with ``all_reduces == 1`` grouped over the
    track axis, and ``trips`` track blocks."""
    comps, _ = parse_computations(text)
    loops: Dict[str, Dict] = {}
    for comp in comps.values():
        for op in comp.ops:
            loop = _while_loop(op, comps) if op.kind == "while" else None
            if not loop:
                continue
            body, trips = loop
            ars = [o for o in comps.get(body, Computation("")).ops
                   if o.kind in ("all-reduce", "all-reduce-start")]
            loops[body] = {"trips": trips, "all_reduces": len(ars),
                           "group_sizes": [_group_size(o.line, n_devices)
                                           for o in ars]}
    return list(loops.values())
