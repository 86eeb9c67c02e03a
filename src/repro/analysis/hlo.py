"""HLO text analysis with while-loop trip-count awareness.

XLA's ``compiled.cost_analysis()`` counts a while-loop body **once**
(verified empirically — see DESIGN.md §6), which makes it useless for
scan-over-layers programs. This parser rebuilds the cost model from the
optimized HLO text:

  1. split the module into computations and their op lines;
  2. extract while-loop trip counts from the loop-condition compare
     constants;
  3. propagate execution multipliers through the call graph
     (body/condition/calls/to_apply/branches);
  4. count dot/convolution FLOPs, fusion-boundary HBM traffic, and
     collective wire bytes (ring-algorithm factors × replica-group size)
     per computation, scaled by its multiplier.

Validated against XLA's own cost analysis on unrolled (loop-free) modules
in tests/test_hlo_analysis.py.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
# a shape's layout, e.g. `{1,0:T(8,128)S(1)}` on TPU: its tiles read as
# `word(` to `_OP_RE`, so it is dropped before an op line is matched
_LAYOUT_RE = re.compile(r"(?<=\])\{[^{}]*\}")
# `%name = <type> opcode(...)` — the type may be a tuple; the opcode is the
# first `word(` token (tuple-opening parens are preceded by whitespace).
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s*([\w\-]+)\((.*)$")
_COMP_NAME_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[\\"]*:\s*\{[\\"]*n[\\"]*:[\\"]*(\d+)')

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# ops that don't touch HBM as fusion boundaries
_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "after-all", "partition-id", "replica-id", "iota"}


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _shape_dims(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None, ()
    dtype, dims = m.groups()
    dims = tuple(int(d) for d in dims.split(",") if d) if dims else ()
    return dtype, dims


@dataclass
class Op:
    name: str
    type_str: str
    opcode: str
    rest: str          # operand list + attributes (raw)


@dataclass
class HLOAnalysis:
    flops: float = 0.0
    bytes_hbm: float = 0.0
    collective_bytes: float = 0.0
    collective_breakdown: dict = field(default_factory=dict)
    while_trip_counts: dict = field(default_factory=dict)
    n_collectives: int = 0
    num_partitions: int = 1
    flops_by_multiplier: dict = field(default_factory=dict)


def _parse_computations(text: str):
    comps: dict[str, list[Op]] = {}
    entry = None
    current = None
    for line in text.splitlines():
        stripped = _LAYOUT_RE.sub("", _COMMENT_RE.sub("", line)).strip()
        if not stripped:
            continue
        # computation headers end with "{", contain "->", and are not ops
        if stripped.endswith("{") and "->" in stripped and " = " not in stripped:
            m = _COMP_NAME_RE.match(stripped)
            if m:
                current = m.group(1)
                comps[current] = []
                if stripped.startswith("ENTRY"):
                    entry = current
            continue
        if stripped.startswith("}"):
            current = None
            continue
        if current is None:
            continue
        om = _OP_RE.match(stripped)
        if om:
            name, type_str, opcode, rest = om.groups()
            comps[current].append(Op(name, type_str.strip(), opcode, rest))
    return comps, entry


def _callees(op: Op):
    """(attr, computation) references made by this op."""
    out = []
    for attr in ("body", "condition", "to_apply", "calls"):
        m = re.search(attr + r"=%?([\w.\-]+)", op.rest)
        if m:
            out.append((attr, m.group(1)))
    m = re.search(r"branch_computations=\{([^}]*)\}", op.rest)
    if m:
        for c in m.group(1).split(","):
            out.append(("branch", c.strip().lstrip("%")))
    return out


def _trip_count(cond_ops: list[Op]) -> int:
    """Trip count from the condition computation: the compare constant."""
    consts = {}
    for op in cond_ops:
        if op.opcode == "constant":
            m = re.match(r"\(?(-?\d+)\)?", op.rest)
            if m and op.type_str.strip().startswith(("s32", "s64", "u32", "u64")):
                consts[op.name] = int(m.group(1))
    best = 0
    for op in cond_ops:
        if op.opcode == "compare":
            for operand in re.findall(r"%([\w.\-]+)", op.rest):
                if operand in consts:
                    best = max(best, consts[operand])
    return max(best, 1)


def _group_size(op: Op, num_partitions: int) -> int:
    """Participant count per replica group."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]", op.rest)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,\s]*)\}", op.rest)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip()]
        return max(len(ids), 1)
    m = re.search(r"source_target_pairs=", op.rest)
    if m:
        return 2  # permute: pairwise
    return num_partitions


def _operand_names(op: Op):
    """Operand %names appearing before the first attribute comma group."""
    # operands are inside the leading parenthesized list before '), attr=...'
    depth = 0
    end = len(op.rest)
    for i, ch in enumerate(op.rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            if depth == 0:
                end = i
                break
            depth -= 1
    head = op.rest[:end]
    return re.findall(r"%([\w.\-]+)", head)


def _dot_flops(op: Op, shapes: dict) -> float:
    _, result_dims = _shape_dims(op.type_str)
    operands = _operand_names(op)
    if not operands:
        return 0.0
    lhs_shape = shapes.get(operands[0])
    if lhs_shape is None:
        return 0.0
    m = re.search(r"lhs_contracting_dims=\{([\d,\s]*)\}", op.rest)
    contract = 1
    if m:
        for d in m.group(1).split(","):
            if d.strip():
                idx = int(d)
                if idx < len(lhs_shape):
                    contract *= lhs_shape[idx]
    n_out = 1
    for d in result_dims:
        n_out *= d
    return 2.0 * n_out * contract


def _op_map(ops):
    return {o.name: o for o in ops}


def _op_traffic(op: Op, ops, shapes, comps) -> float:
    """HBM traffic estimate for one op (fusion-boundary model).

    Slicing ops read only the slice, not the sliced operand;
    dynamic-update-slice writes in place (≈ 2× the update bytes); a fusion
    whose parameters are consumed only by slicing ops inside the fusion body
    reads slices, not full parameters.
    """
    out_b = _shape_bytes(op.type_str)
    om = _op_map(ops)
    operands = _operand_names(op)

    if op.opcode in ("dynamic-slice", "slice", "gather"):
        return 2.0 * out_b  # read the slice + write it
    if op.opcode == "dynamic-update-slice":
        upd = om.get(operands[1]) if len(operands) > 1 else None
        upd_b = _shape_bytes(upd.type_str) if upd else out_b
        return 2.0 * upd_b

    if op.opcode == "fusion":
        m = re.search(r"calls=%?([\w.\-]+)", op.rest)
        body = comps.get(m.group(1)) if m else None
        if body is not None:
            body_map = _op_map(body)
            params = {}
            for bop in body:
                if bop.opcode == "parameter":
                    idx = int(re.match(r"\(?(\d+)\)?", bop.rest).group(1))
                    params[bop.name] = idx
            # per-parameter: sliced-only consumption → slice bytes
            in_b = 0.0
            consumed = {name: [] for name in params}
            for bop in body:
                for nm in _operand_names(bop):
                    if nm in consumed:
                        consumed[nm].append(bop)
            for pname, users in consumed.items():
                idx = params[pname]
                full = (_shape_bytes(om[operands[idx]].type_str)
                        if idx < len(operands) and operands[idx] in om
                        else _shape_bytes(body_map[pname].type_str))
                if users and all(u.opcode in ("dynamic-slice", "slice", "gather")
                                 for u in users):
                    in_b += sum(_shape_bytes(u.type_str) for u in users)
                elif users and all(
                        u.opcode == "dynamic-update-slice"
                        and _operand_names(u)[:1] == [pname]
                        for u in users):
                    in_b += 0.0  # in-place updated buffer: aliased, not read
                else:
                    in_b += full
            root = body[-1] if body else None
            root_dus = [b for b in body if b.opcode == "dynamic-update-slice"]
            if root_dus and root is not None and \
                    root.opcode in ("dynamic-update-slice", "bitcast", "tuple"):
                out_b = sum(2.0 * _shape_bytes(
                    body_map[_operand_names(d)[1]].type_str)
                    for d in root_dus
                    if len(_operand_names(d)) > 1
                    and _operand_names(d)[1] in body_map)
            return in_b + out_b

    in_b = 0.0
    for nm in operands:
        src = om.get(nm)
        if src is not None:
            in_b += _shape_bytes(src.type_str)
    return in_b + out_b


def analyze_hlo(text: str) -> HLOAnalysis:
    res = HLOAnalysis()
    m = re.search(r"num_partitions=(\d+)", text)
    res.num_partitions = int(m.group(1)) if m else 1

    comps, entry = _parse_computations(text)
    if entry is None:
        return res

    # per-computation operand shape tables
    shape_tables = {}
    for cname, ops in comps.items():
        shape_tables[cname] = {op.name: _shape_dims(op.type_str)[1] for op in ops}

    # Two multiplier maps over the call graph:
    #  * flop_mult — every edge (body/cond × trip, calls/to_apply × 1):
    #    dots inside fusion bodies execute and must be counted.
    #  * exec_mult — control-flow edges only (ENTRY, while body/cond,
    #    branches): HBM traffic happens at *schedule level*; ops inside
    #    fusion/reduce bodies live in registers and are free.
    flop_mult: dict[str, float] = {entry: 1.0}
    exec_mult: dict[str, float] = {entry: 1.0}
    order = [entry]
    seen = {entry}
    while order:
        cname = order.pop(0)
        fmult = flop_mult.get(cname, 0.0)
        emult = exec_mult.get(cname, 0.0)
        for op in comps.get(cname, []):
            callees = _callees(op)
            trip = 1.0
            if op.opcode == "while":
                m = _TRIP_RE.search(op.rest)
                if m:  # XLA annotates known trip counts in backend_config
                    trip = float(m.group(1))
                else:  # fall back to the loop-condition compare constant
                    cond_name = dict(callees).get("condition")
                    if cond_name in comps:
                        trip = float(_trip_count(comps[cond_name]))
                res.while_trip_counts[op.name] = int(trip)
            for attr, callee in callees:
                if callee not in comps:
                    continue
                control = attr in ("body", "condition", "branch")
                scale = trip if attr in ("body", "condition") else 1.0
                flop_mult[callee] = flop_mult.get(callee, 0.0) + fmult * scale
                if control:
                    exec_mult[callee] = exec_mult.get(callee, 0.0) \
                        + emult * scale
                if callee not in seen:
                    seen.add(callee)
                    order.append(callee)

    # cost accumulation
    for cname, ops in comps.items():
        fmult = flop_mult.get(cname, 0.0)
        emult = exec_mult.get(cname, 0.0)
        if fmult <= 0 and emult <= 0:
            continue
        shapes = shape_tables[cname]
        for op in ops:
            if op.opcode in ("dot", "convolution") and fmult > 0:
                f = _dot_flops(op, shapes)
                res.flops += fmult * f
                key = int(fmult)
                res.flops_by_multiplier[key] = \
                    res.flops_by_multiplier.get(key, 0) + f
            if emult > 0 and op.opcode not in _FREE_OPS \
                    and op.opcode != "while":
                res.bytes_hbm += emult * _op_traffic(op, ops, shapes, comps)
            if emult > 0:
                for coll in COLLECTIVES:
                    if op.opcode == coll or op.opcode == coll + "-start":
                        g = _group_size(op, res.num_partitions)
                        out_b = _shape_bytes(op.type_str)
                        if coll == "all-reduce":
                            wire = 2.0 * (g - 1) / g * out_b
                        elif coll == "all-gather":
                            wire = (g - 1) / g * out_b
                        elif coll == "reduce-scatter":
                            wire = (g - 1) * out_b
                        elif coll == "all-to-all":
                            wire = (g - 1) / g * out_b
                        else:  # collective-permute
                            wire = out_b
                        res.collective_bytes += emult * wire
                        res.n_collectives += 1
                        res.collective_breakdown[coll] = \
                            res.collective_breakdown.get(coll, 0.0) \
                            + emult * wire
                        break
    return res
