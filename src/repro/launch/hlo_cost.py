"""Trip-count-aware cost analysis over optimized (post-SPMD) HLO text.

Why this exists: XLA's ``compiled.cost_analysis()`` counts a ``while`` body
ONCE — a scanned 88-layer transformer reports ~1/88th of its real FLOPs, and
collectives inside the scan (FSDP all-gathers, EP psums) are invisible to a
flat regex.  This walker parses the HLO module into computations, walks the
entry recursively, and multiplies every instruction's cost by the product of
enclosing ``while`` trip counts (taken from the backend_config
``known_trip_count``, falling back to the s32 constant in the loop
condition).

Costs per instruction (shapes in post-SPMD HLO are already per-partition):
  * dot            2 · |result| · Π(contracting dims)           → flops
  * elementwise    |result|                                     → flops
                   (transcendentals also tallied separately)
  * every top-level instr   |result| + Σ|operands|              → bytes
    (inside fusions only flops are counted — fused internals stay in
    registers; the fusion instruction itself pays the boundary bytes)
  * collectives    ring-model wire bytes (see ``_WIRE``), tallied per kind

This is the primary §Roofline source; ``cost_analysis()`` is kept as a
cross-check (it should match for unrolled modules — asserted in tests).
"""

from __future__ import annotations

import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "select", "compare", "and", "or", "xor", "not", "clamp",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even", "sign",
    "convert", "remainder", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "atan2",
}
_TRANSCENDENTAL = {"tanh", "exponential", "log", "power", "rsqrt", "sqrt",
                   "sine", "cosine", "logistic", "expm1", "log1p", "cbrt",
                   "erf"}
_REDUCES = {"reduce", "reduce-window"}
_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "after-all", "partition-id", "replica-id", "iota", "rng",
         "rng-bit-generator", "rng-get-and-update-state", "broadcast",
         "reshape", "copy-done", "send-done", "recv-done", "add-dependency",
         "opt-barrier", "custom-call", "infeed", "outfeed", "domain"}
_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute"}

_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_TRIP_RE = re.compile(r'known_trip_count[^\d]*(\d+)')
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")


def _shape_elems_bytes(type_str: str) -> Tuple[int, int]:
    """(elements, bytes) summed over all array components of a type string."""
    elems = tot = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        tot += n * _DTYPE_BYTES[dtype]
    return elems, tot


@dataclass
class Instr:
    name: str
    type_str: str
    op: str
    operands: List[str]
    attrs: str


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    # symbol table: instr/param name -> type string
    types: Dict[str, str] = field(default_factory=dict)


_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-_]+)\s*\((.*)\)\s*->\s*(.+?)\s*\{\s*$")
_INSTR_LHS = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-_]+)\s*=\s*")
_OP_CALL = re.compile(r"^([\w\-]+)\(")
_COMMENT = re.compile(r"/\*.*?\*/")


def _parse_instr(line: str) -> Optional[Tuple[str, str, str, str]]:
    """(name, type_str, op, rest-after-open-paren) or None."""
    line = _COMMENT.sub("", line)
    m = _INSTR_LHS.match(line)
    if m is None:
        return None
    name, rest = m.group(1), line[m.end():]
    if rest.startswith("("):               # tuple type: match parens
        depth, i = 0, 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        type_str, rest = rest[: i + 1], rest[i + 1:].lstrip()
    else:
        parts = rest.split(" ", 1)
        if len(parts) < 2:
            return None
        type_str, rest = parts[0], parts[1].lstrip()
    m2 = _OP_CALL.match(rest)
    if m2 is None:
        return None
    return name, type_str, m2.group(1), rest[m2.end():]


def _split_top(s: str) -> List[str]:
    """Split on commas at paren/brace depth 0."""
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return out


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        s = line.strip()
        if cur is None:
            m = _COMP_HDR.match(s)
            if m:
                cur = Computation(m.group(1))
                # header params: "p: f32[2,3], q: (s32[], f32[4])"
                for part in _split_top(m.group(2)):
                    if ":" in part:
                        pname, ptype = part.split(":", 1)
                        cur.types[pname.strip().lstrip("%")] = ptype.strip()
                comps[cur.name] = cur
            continue
        if s == "}":
            cur = None
            continue
        parsed = _parse_instr(line)
        if parsed is None:
            continue
        name, type_str, op, rest = parsed
        # operands live before the matching close paren of the op's open paren
        depth, i = 1, 0
        while i < len(rest) and depth:
            if rest[i] in "([{":
                depth += 1
            elif rest[i] in ")]}":
                depth -= 1
            i += 1
        opnd_str, attrs = rest[: i - 1], rest[i:]
        operands = [t.strip().split(" ")[-1].lstrip("%")
                    for t in _split_top(opnd_str) if t.strip()]
        instr = Instr(name, type_str, op, operands, attrs)
        cur.instrs.append(instr)
        cur.types[name] = type_str
        # parameters restate their type
        if op == "parameter" and name not in cur.types:
            cur.types[name] = type_str
    return comps


def _called(attrs: str, key: str) -> List[str]:
    m = re.search(key + r"=%?([\w\.\-_]+)", attrs)
    if m:
        return [m.group(1)]
    m = re.search(key + r"=\{([^}]*)\}", attrs)
    if m:
        return [t.strip().lstrip("%") for t in m.group(1).split(",") if t.strip()]
    return []


def _trip_count(instr: Instr, comps: Dict[str, Computation]) -> int:
    m = _TRIP_RE.search(instr.attrs)
    if m:
        return int(m.group(1))
    for cname in _called(instr.attrs, "condition"):
        cond = comps.get(cname)
        if cond:
            consts = _CONST_RE.findall("\n".join(i.type_str + " " + i.op + "(" +
                                                 i.attrs for i in cond.instrs))
            # fallback: largest s32 constant in the condition
            text = "\n".join(f"{i.type_str} {i.op}({','.join(i.operands)}){i.attrs}"
                             for i in cond.instrs)
            consts = re.findall(r"constant\((\d+)\)", text)
            if consts:
                return max(int(c) for c in consts)
    return 1


def _group_size(attrs: str, total: int) -> int:
    m = _GROUPS_BRACE_RE.search(attrs)
    if m:
        return max(1, len([x for x in m.group(1).split(",") if x.strip()]))
    m = _GROUPS_IOTA_RE.search(attrs)
    if m:
        return max(1, int(m.group(2)))
    return total


def _wire_bytes(kind: str, out_bytes: int, n: int) -> float:
    """Ring-algorithm per-device wire bytes, from the RESULT size."""
    n = max(2, n)
    if kind == "all-gather":
        return out_bytes * (n - 1) / n          # result is the gathered array
    if kind == "reduce-scatter":
        return out_bytes * (n - 1)              # input = out·n; wire = in·(n-1)/n
    if kind == "all-reduce":
        return 2 * out_bytes * (n - 1) / n
    if kind == "all-to-all":
        return out_bytes * (n - 1) / n
    return float(out_bytes)                     # collective-permute


# Ops whose results almost always fuse into their consumers on TPU (XLA:TPU
# fusion is far more aggressive than XLA:CPU, whose HLO we are reading) —
# excluded from the fused-byte estimate.
_FUSES_AWAY = (_ELEMENTWISE | _TRANSCENDENTAL
               | {"broadcast", "iota", "convert", "reshape", "bitcast",
                  "compare", "select", "reduce"})


@dataclass
class Cost:
    flops: float = 0.0
    transcendentals: float = 0.0
    bytes_accessed: float = 0.0   # CPU-fusion granularity (upper bound)
    bytes_fused: float = 0.0      # TPU-fusion estimate (major ops only)
    wire_bytes: float = 0.0
    coll_ops: Dict[str, int] = field(default_factory=lambda: defaultdict(int))
    coll_bytes: Dict[str, float] = field(default_factory=lambda: defaultdict(float))

    def add(self, other: "Cost", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.transcendentals += other.transcendentals * mult
        self.bytes_accessed += other.bytes_accessed * mult
        self.bytes_fused += other.bytes_fused * mult
        self.wire_bytes += other.wire_bytes * mult
        for k, v in other.coll_ops.items():
            self.coll_ops[k] += v * mult
        for k, v in other.coll_bytes.items():
            self.coll_bytes[k] += v * mult

    def as_dict(self) -> dict:
        return {"flops": self.flops, "transcendentals": self.transcendentals,
                "bytes_accessed": self.bytes_accessed,
                "bytes_fused": self.bytes_fused,
                "wire_bytes": self.wire_bytes,
                "collective_ops": dict(self.coll_ops),
                "collective_bytes": dict(self.coll_bytes)}


def _dot_flops(instr: Instr, comp: Computation) -> float:
    out_elems, _ = _shape_elems_bytes(instr.type_str)
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.attrs)
    contract = 1
    if m and instr.operands:
        lhs_type = comp.types.get(instr.operands[0], "")
        shapes = _SHAPE_RE.findall(lhs_type)
        if shapes:
            dims = [int(d) for d in shapes[0][1].split(",") if d]
            for idx in m.group(1).split(","):
                if idx and int(idx) < len(dims):
                    contract *= dims[int(idx)]
    return 2.0 * out_elems * contract


def _comp_cost(comp: Computation, comps: Dict[str, Computation],
               cache: Dict[Tuple[str, bool], Cost], total_devices: int,
               in_fusion: bool) -> Cost:
    key = (comp.name, in_fusion)
    if key in cache:
        return cache[key]
    cost = Cost()
    cache[key] = cost          # recursion guard (HLO call graphs are acyclic)
    for instr in comp.instrs:
        op = instr.op
        base = op[:-6] if op.endswith("-start") else op
        out_elems, out_bytes = _shape_elems_bytes(instr.type_str)
        opnd_bytes = sum(_shape_elems_bytes(comp.types.get(o, ""))[1]
                         for o in instr.operands)
        if base in _COLLECTIVES:
            if op.endswith("-start"):
                # result of *-start is (input, output); take the output half
                parts = _split_top(instr.type_str.strip("()"))
                out_bytes = _shape_elems_bytes(parts[-1])[1] if parts else out_bytes
                if base == "all-reduce" and parts:
                    out_bytes = _shape_elems_bytes(parts[-1])[1]
            n = _group_size(instr.attrs, total_devices)
            cost.coll_ops[base] += 1
            w = _wire_bytes(base, out_bytes, n)
            cost.coll_bytes[base] += w
            cost.wire_bytes += w
            if not in_fusion:
                cost.bytes_accessed += out_bytes + opnd_bytes
                cost.bytes_fused += out_bytes + opnd_bytes
            continue
        if op == "while":
            trip = _trip_count(instr, comps)
            for cname in _called(instr.attrs, "body"):
                cost.add(_comp_cost(comps[cname], comps, cache, total_devices,
                                    in_fusion), trip)
            for cname in _called(instr.attrs, "condition"):
                cost.add(_comp_cost(comps[cname], comps, cache, total_devices,
                                    in_fusion), trip)
            continue
        if op in ("dynamic-slice", "slice", "gather"):
            # HBM touches the sliced REGION, not the operand (a scan body's
            # dynamic-slice would otherwise count the whole stacked array
            # once per iteration — a ~200× overcount on deep models)
            if not in_fusion:
                cost.bytes_accessed += 2 * out_bytes
                cost.bytes_fused += 2 * out_bytes
            continue
        if op in ("dynamic-update-slice", "scatter"):
            # in-place update: read+write of the update region only
            upd = (_shape_elems_bytes(comp.types.get(instr.operands[1], ""))[1]
                   if len(instr.operands) > 1 else out_bytes)
            if not in_fusion:
                cost.bytes_accessed += 2 * upd
                cost.bytes_fused += 2 * upd
            continue
        if op in ("fusion",):
            for cname in _called(instr.attrs, "calls"):
                cost.add(_comp_cost(comps[cname], comps, cache, total_devices,
                                    True))
            if not in_fusion:
                # fused slicing reads only what it touches: cap each operand's
                # contribution at the fusion's result size (elementwise
                # fusions are unaffected; dots never fuse on this backend)
                capped = sum(min(_shape_elems_bytes(comp.types.get(o, ""))[1],
                                 out_bytes) for o in instr.operands)
                cost.bytes_accessed += out_bytes + capped
                cost.bytes_fused += out_bytes + capped
            continue
        if op in ("call", "conditional", "map", "sort", "scatter", "reduce",
                  "reduce-window", "select-and-scatter"):
            for key_ in ("to_apply", "calls", "branch_computations"):
                for cname in _called(instr.attrs, key_):
                    if cname in comps:
                        cost.add(_comp_cost(comps[cname], comps, cache,
                                            total_devices, True), out_elems
                                 if op in _REDUCES else 1.0)
            if op in _REDUCES:
                # reduce flops ≈ input element count
                cost.flops += sum(_shape_elems_bytes(comp.types.get(o, ""))[0]
                                  for o in instr.operands[:1])
            if not in_fusion:
                cost.bytes_accessed += out_bytes + opnd_bytes
                if op not in _FUSES_AWAY:
                    cost.bytes_fused += out_bytes + opnd_bytes
            continue
        if base in _FREE:
            if op == "copy" and not in_fusion:
                cost.bytes_accessed += out_bytes + opnd_bytes
                cost.bytes_fused += out_bytes + opnd_bytes
            continue
        # arithmetic / data movement
        if op in _ELEMENTWISE:
            cost.flops += out_elems
        elif op in _TRANSCENDENTAL:
            cost.flops += out_elems
            cost.transcendentals += out_elems
        elif op == "dot":
            cost.flops += _dot_flops(instr, comp)
        elif op == "convolution":
            cost.flops += 2.0 * out_elems  # lower bound; no convs in this repo
        if not in_fusion:
            cost.bytes_accessed += out_bytes + opnd_bytes
            if op not in _FUSES_AWAY:
                cost.bytes_fused += out_bytes + opnd_bytes
    return cost


def analyze(hlo_text: str, total_devices: int,
            entry: Optional[str] = None) -> Cost:
    comps = parse_module(hlo_text)
    if not comps:
        return Cost()
    name = entry
    if name is None:
        m = re.search(r"^ENTRY\s+%?([\w\.\-_]+)", hlo_text, re.MULTILINE)
        name = m.group(1) if m else next(iter(comps))
    # computations reachable only from the entry (dead comps are listed too)
    cache: Dict[Tuple[str, bool], Cost] = {}
    total = Cost()
    total.add(_comp_cost(comps[name], comps, cache, total_devices, False))
    return total
