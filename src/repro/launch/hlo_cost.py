"""Trip-count-aware cost analysis of optimized HLO.

``compiled.cost_analysis()`` counts while-loop bodies ONCE regardless of
trip count (verified empirically — a 16-step ``lax.scan`` of a 512^3 matmul
reports the flops of a single step).  Every model here scans over layers, so
naive cost_analysis undercounts flops/bytes/collectives by ~n_layers x.

This module parses the post-optimization HLO text instead:

  * splits the module into computations and ops;
  * builds the call graph (``calls=``, ``to_apply=``, ``body=``/
    ``condition=`` of whiles, fusions) and derives a *multiplicity* for each
    computation = product of enclosing while trip counts (trip counts are
    recovered from the loop-condition comparison constant, which is how XLA
    lowers ``lax.scan``);
  * flops: 2 * numel(out) * prod(contracting dims) per ``dot``, times
    multiplicity (dots inside fusion computations are attributed to their
    fusion call sites' multiplicity);
  * bytes: operand + output bytes of top-level (post-fusion) ops, times
    multiplicity — the same fusion-aware convention XLA's own bytes-accessed
    uses;
  * collectives: per-op output bytes times multiplicity, by collective kind.

Validated against fully-unrolled lowerings in tests/test_hlo_cost.py.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "token": 0, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"([a-z]+\d*|pred|token)\[([0-9,]*)\]")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*"n"\s*:\s*"(\d+)"')
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(|\.)")
_CALL_ATTR_RE = re.compile(r"(calls|to_apply|body|condition)=\{?%?([\w.\-]+)")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """XLA's own cost estimate of a compiled program (flops, bytes accessed)."""
    return compiled.cost_analysis()


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_numel(type_str: str) -> int:
    n_total = 0
    for _, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        n_total += n
    return n_total


@dataclass
class Op:
    name: str
    opcode: str
    out_type: str
    rest: str  # args + attributes
    operands: List[str] = field(default_factory=list)


def _match_paren(s: str, start: int = 0) -> int:
    """Index just past the close paren matching s[start] == '('."""
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(s)


def _parse_op_line(line: str) -> Optional[Op]:
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    eq = s.find(" = ")
    if eq <= 0:
        return None
    name = s[:eq].strip().lstrip("%")
    rhs = s[eq + 3 :].lstrip()
    if rhs.startswith("("):  # tuple type (may contain /*index=N*/ comments)
        end = _match_paren(rhs)
        out_type = rhs[:end]
        rest0 = rhs[end:].lstrip()
    else:
        m = re.match(r"([a-z]+\d*\[[0-9,]*\](?:\{[^}]*\})?)", rhs)
        if not m:
            return None
        out_type = m.group(1)
        rest0 = rhs[m.end() :].lstrip()
    m = re.match(r"([\w\-]+)\(", rest0)
    if not m:
        return None
    opcode = m.group(1)
    args_end = _match_paren(rest0, m.end() - 1)
    args = rest0[m.end() : args_end - 1]
    rest = rest0[m.end() :]
    operands = [o.lstrip("%") for o in re.findall(r"%([\w.\-]+)", args)]
    return Op(name=name, opcode=opcode, out_type=out_type, rest=rest, operands=operands)


@dataclass
class Computation:
    name: str
    ops: List[Op] = field(default_factory=list)
    params: Dict[str, str] = field(default_factory=dict)  # name -> type str
    is_entry: bool = False


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        s = line.strip()
        if not s or s.startswith("//"):
            continue
        if cur is None:
            if s.endswith("{") and "=" not in s.split("(")[0]:
                hdr = s[:-1].strip()
                is_entry = hdr.startswith("ENTRY")
                if is_entry:
                    hdr = hdr[len("ENTRY"):].strip()
                name = hdr.split("(")[0].strip().lstrip("%").rstrip(". ")
                cur = Computation(name=name, is_entry=is_entry)
                # parameters in the signature
                sig = hdr[hdr.find("(") + 1 : hdr.rfind(")")] if "(" in hdr else ""
                for pm in re.finditer(r"([\w.\-]+)\s*:\s*((?:\([^)]*\)|[a-z]+\d*\[[0-9,]*\](?:\{[^}]*\})?))", sig):
                    cur.params[pm.group(1)] = pm.group(2)
            continue
        if s == "}" or s.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        op = _parse_op_line(line)
        if op is not None:
            cur.ops.append(op)
    return comps


def _trip_count(cond: Computation) -> int:
    """lax.scan conditions compare the induction var against a constant."""
    consts = []
    for op in cond.ops:
        if op.opcode == "constant":
            mc = re.search(r"constant\((-?\d+)\)", "constant(" + op.rest)
            if mc:
                consts.append(int(mc.group(1)))
    pos = [c for c in consts if c > 0]
    return max(pos) if pos else 1


def _type_of(comp: Computation, name: str, type_cache: Dict[Tuple[str, str], str]) -> Optional[str]:
    key = (comp.name, name)
    if key in type_cache:
        return type_cache[key]
    for op in comp.ops:
        if op.name == name:
            type_cache[key] = op.out_type
            return op.out_type
    if name in comp.params:
        type_cache[key] = comp.params[name]
        return comp.params[name]
    return None


def _dot_flops(comp: Computation, op: Op, type_cache) -> float:
    out_numel = _shape_numel(op.out_type)
    lhs_type = _type_of(comp, op.operands[0], type_cache) if op.operands else None
    if lhs_type is None:
        return 0.0
    mdims = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.rest)
    cdims = [int(d) for d in mdims.group(1).split(",")] if mdims and mdims.group(1) else []
    shapes = _SHAPE_RE.findall(lhs_type)
    if not shapes:
        return 0.0
    dims = [int(d) for d in shapes[0][1].split(",") if d]
    k = 1
    for cd in cdims:
        if cd < len(dims):
            k *= dims[cd]
    return 2.0 * out_numel * k


# Ops whose operand/output bytes approximate real HBM traffic post-fusion.
_MEM_OPS = {
    "fusion", "dot", "convolution", "copy", "copy-start", "dynamic-slice",
    "dynamic-update-slice", "gather", "scatter", "reduce", "broadcast",
    "transpose", "concatenate", "pad", "slice", "reverse", "sort", "rng",
    "reduce-window", "select-and-scatter", "iota", "custom-call", "cholesky",
    "triangular-solve", "exponential", "log", "add", "multiply", "subtract",
    "divide", "tanh", "select", "compare", "maximum", "minimum", "convert",
} | set(_COLLECTIVES) | {c + "-start" for c in _COLLECTIVES}

_SKIP_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "while", "call", "conditional", "after-all", "partition-id", "replica-id",
    "bitcast-convert", "reshape",
}


@dataclass
class CostSummary:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    collectives: Dict[str, float] = field(default_factory=lambda: defaultdict(float))
    collective_count: int = 0
    unknown_flop_ops: int = 0
    # Bytes attributable to the chunked-attention inner loop (op_name
    # metadata contains "jit(attention)").  On TPU the Pallas flash kernel
    # keeps these tiles in VMEM — EXPERIMENTS.md §Perf uses this split to
    # report the kernel-deployment memory term.
    attention_bytes: float = 0.0
    attention_flops: float = 0.0


def analyze(text: str) -> CostSummary:
    comps = parse_hlo(text)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        raise ValueError("no ENTRY computation found")

    # Call graph with while-trip multiplicity.
    mult: Dict[str, float] = defaultdict(float)
    mult[entry.name] = 1.0
    order = [entry.name]
    seen = {entry.name}
    # BFS; HLO call graphs are acyclic.
    i = 0
    while i < len(order):
        cname = order[i]
        i += 1
        comp = comps.get(cname)
        if comp is None:
            continue
        m = mult[cname]
        for op in comp.ops:
            calls = _CALL_ATTR_RE.findall(op.rest)
            if op.opcode == "while":
                body = next((c for k, c in calls if k == "body"), None)
                cond = next((c for k, c in calls if k == "condition"), None)
                mt = _TRIP_RE.search(op.rest)
                if mt:
                    trips = int(mt.group(1))
                else:
                    trips = _trip_count(comps[cond]) if cond in comps else 1
                if body:
                    mult[body] += m * trips
                    if body not in seen:
                        seen.add(body)
                        order.append(body)
                if cond:
                    mult[cond] += m * (trips + 1)
                    if cond not in seen:
                        seen.add(cond)
                        order.append(cond)
            else:
                for kind, target in calls:
                    if target in comps:
                        mult[target] += m
                        if target not in seen:
                            seen.add(target)
                            order.append(target)

    # Which computations are fusion bodies / reducers (bytes counted at call site)?
    fused: set = set()
    for comp in comps.values():
        for op in comp.ops:
            if op.opcode in ("fusion", "reduce", "sort", "scatter", "reduce-window", "select-and-scatter", "custom-call", "map"):
                for _, target in _CALL_ATTR_RE.findall(op.rest):
                    fused.add(target)

    type_cache: Dict[Tuple[str, str], str] = {}
    out = CostSummary()
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        top_level = cname not in fused
        for op in comp.ops:
            in_attn = "jit(attention)" in op.rest
            if op.opcode == "dot":
                f = m * _dot_flops(comp, op, type_cache)
                out.flops += f
                if in_attn:
                    out.attention_flops += f
            elif op.opcode == "convolution":
                # conv flops ~ 2 * out_numel * prod(kernel dims) * Cin: rare
                out.unknown_flop_ops += 1
            if not top_level:
                continue
            base = op.opcode[:-6] if op.opcode.endswith("-start") else op.opcode
            if base in _COLLECTIVES:
                b = _shape_bytes(op.out_type)
                out.collectives[base] += m * b
                out.collective_bytes += m * b
                out.collective_count += int(m)
            if op.opcode in _SKIP_OPS:
                continue
            if op.opcode in _MEM_OPS:
                b = _shape_bytes(op.out_type)
                for operand in op.operands:
                    t = _type_of(comp, operand, type_cache)
                    if t is not None:
                        b += _shape_bytes(t)
                out.bytes += m * b
                if in_attn:
                    out.attention_bytes += m * b
    out.collectives = dict(out.collectives)
    return out
