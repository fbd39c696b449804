"""Loop-aware HLO cost model.

NOTE (CPU-legalization discount): the dry-run lowers for the CPU backend,
which legalises bf16 matmuls by materialising f32 CONVERTs of the operands —
traffic that does not exist on the TPU target (bf16 x bf16 -> f32 is native
MXU). ``analyze(..., discount_converts=True)`` therefore zero-costs convert
ops and convert-only fusions. Real model-level casts (f32 master params ->
bf16 compute) are orders of magnitude smaller and noted in EXPERIMENTS.md.

``compiled.cost_analysis()`` counts each computation ONCE — a ``lax.scan``
over 48 layers reports 1/48th of the real FLOPs (verified empirically). This
module parses the post-optimization HLO text, builds the call graph, extracts
while-loop trip counts from loop conditions, and accumulates

    * flops              (dot: 2*M*N*K; elementwise/reduce: 1/elem)
    * bytes              (operand + result bytes of non-fused top-level ops)
    * collective bytes   (per-device wire bytes per collective, ring model)

with every computation weighted by its loop multiplicity. Fusion callees are
folded into their fusion op (operand/result bytes counted once, internals 0),
matching XLA's own bytes-accessed semantics.
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "rsqrt", "sqrt", "negate", "abs", "sign", "cosine", "sine", "logistic",
    "floor", "ceil", "round-nearest-afz", "select", "compare", "and", "or",
    "xor", "not", "clamp", "remainder", "atan2", "cbrt", "erf",
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast", "ragged-all-to-all")


@dataclass
class Shape:
    dtype: str
    dims: Tuple[int, ...]
    tuple_elems: Optional[List["Shape"]] = None

    @property
    def elems(self) -> int:
        return math.prod(self.dims) if self.tuple_elems is None else 0

    @property
    def bytes(self) -> int:
        if self.tuple_elems is not None:
            return sum(s.bytes for s in self.tuple_elems)
        return self.elems * _DTYPE_BYTES.get(self.dtype, 4)


def _parse_shape(text: str, pos: int = 0) -> Tuple[Shape, int]:
    """Parse one shape starting at text[pos]. Handles tuples recursively."""
    if text[pos] == "(":
        elems = []
        pos += 1
        while text[pos] != ")":
            s, pos = _parse_shape(text, pos)
            elems.append(s)
            if text[pos] == ",":
                pos += 1
                while text[pos] == " ":
                    pos += 1
        return Shape("tuple", (), elems), pos + 1
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", text[pos:])
    if not m:
        # e.g. token[] style or unranked; consume identifier
        m2 = re.match(r"(\w+)", text[pos:])
        return Shape(m2.group(1) if m2 else "opaque", ()), pos + (m2.end() if m2 else 1)
    dtype = m.group(1)
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    end = pos + m.end()
    # skip layout {...} and memory space annotations
    while end < len(text) and text[end] == "{":
        depth = 0
        while end < len(text):
            if text[end] == "{":
                depth += 1
            elif text[end] == "}":
                depth -= 1
                if depth == 0:
                    end += 1
                    break
            end += 1
    return Shape(dtype, dims), end


@dataclass
class Op:
    name: str
    shape: Shape
    opcode: str
    operands: List[str]
    attrs: str
    args: str = ""


@dataclass
class Computation:
    name: str
    ops: List[Op] = field(default_factory=list)
    by_name: Dict[str, Shape] = field(default_factory=dict)


_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(|\w+\[)")
_CALL_ATTRS = ("calls=", "body=", "condition=", "to_apply=",
               "true_computation=", "false_computation=", "branch_computations=")


def _parse_operands(rest: str) -> Tuple[str, List[str], str, str]:
    """rest starts at opcode: 'dot(%a, %b), attrs...'."""
    m = re.match(r"([\w\-]+)\(", rest)
    if not m:
        return rest.strip(), [], "", ""
    opcode = m.group(1)
    depth, i = 0, m.end() - 1
    start = m.end()
    while i < len(rest):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                break
        i += 1
    args = rest[start:i]
    attrs = rest[i + 1:]
    operands = re.findall(r"%([\w.\-]+)", args)
    return opcode, operands, attrs, args


def parse_hlo(text: str) -> Tuple[Dict[str, Computation], str]:
    """Returns ({name: Computation}, entry_name)."""
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped or stripped.startswith(("//", "#")):
            continue
        # computation header: `%name (params...) -> type {` or `ENTRY %name ... {`
        # (param lists contain nested parens for tuple types, so detect by the
        # trailing "{" plus absence of "=" before the first paren)
        if stripped.endswith("{") and "=" not in stripped.split("(", 1)[0] \
                and not stripped.startswith("HloModule"):
            hm = re.match(r"(ENTRY\s+)?%?([\w.\-~!]+)", stripped)
            if hm:
                cur = Computation(hm.group(2))
                comps[cur.name] = cur
                if hm.group(1):
                    entry = cur.name
                continue
        if stripped == "}":
            cur = None
            continue
        if cur is None:
            continue
        om = _OP_RE.match(line)
        if not om:
            continue
        name = om.group(1)
        eq = line.index("=", om.start())
        shape, pos = _parse_shape(line, eq + 2 if line[eq + 1] == " " else eq + 1)
        rest = line[pos:].strip()
        opcode, operands, attrs, args = _parse_operands(rest)
        op = Op(name, shape, opcode, operands, attrs, args)
        cur.ops.append(op)
        cur.by_name[name] = shape
    if entry is None and comps:
        entry = next(iter(comps))
    return comps, entry


def _called(op: Op) -> List[str]:
    out = []
    for key in _CALL_ATTRS:
        for m in re.finditer(re.escape(key) + r"(\{[^}]*\}|%?[\w.\-]+)", op.attrs):
            val = m.group(1)
            out.extend(re.findall(r"%?([\w.\-]+)", val.strip("{}")))
    return [c.lstrip("%") for c in out]


def _trip_count(cond: Computation, body: Computation) -> int:
    """Scan loops compare the induction var against a constant bound."""
    consts = []
    for op in cond.ops:
        if op.opcode == "constant" and op.shape.dtype in ("s32", "u32", "s64", "u64"):
            m = re.search(r"(\d+)", op.args)
            if m:
                consts.append(int(m.group(1)))
    return max(consts) if consts else 1


def _group_size(attrs: str, n_devices: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", attrs)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", attrs)
    if m:
        return len(m.group(1).split(","))
    return n_devices


def _dot_flops(op: Op, comp: Computation) -> int:
    out_elems = op.shape.elems
    lhs = comp.by_name.get(op.operands[0]) if op.operands else None
    if lhs is None:
        return 2 * out_elems
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.attrs)
    cdims = [int(d) for d in m.group(1).split(",")] if m and m.group(1) else []
    k = math.prod(lhs.dims[d] for d in cdims) if cdims else 1
    return 2 * out_elems * k


def _fusion_bytes(op: Op, comp: Computation, comps: Dict[str, Computation]) -> float:
    """HBM traffic of a fusion op.

    Walks the fused computation tracing each parameter through TRANSPARENT
    ops (convert/bitcast/reshape/transpose/copy — no HBM traffic of their
    own inside a fusion) to its effective consumers:
      * consumed only by dynamic-slice(operand 0)  -> count slice bytes
      * aliased through a root dynamic-update-slice -> count 2x update bytes
      * anything else                               -> full buffer bytes
    This captures both native scan slicing AND the CPU-legalised
    convert(DUS(convert(...))) cache write-back pattern.
    """
    callees = [comps[c] for c in _called(op) if c in comps]
    if not callees:
        return sum(comp.by_name.get(o, Shape("opaque", ())).bytes
                   for o in op.operands) + op.shape.bytes
    fc = callees[0]
    by_name = {o.name: o for o in fc.ops}
    TRANSPARENT = ("convert", "bitcast", "reshape", "transpose", "copy")

    param_idx = {}
    for fop in fc.ops:
        if fop.opcode == "parameter" and fop.args.strip().isdigit():
            param_idx[fop.name] = int(fop.args.strip())

    # consumers map: name -> [(op, operand_position)]
    consumers: Dict[str, list] = {}
    for fop in fc.ops:
        for pos, o in enumerate(fop.operands):
            consumers.setdefault(o, []).append((fop, pos))

    root = fc.ops[-1] if fc.ops else None

    def flows_to_root_transparent(name: str) -> bool:
        seen = set()
        stack = [name]
        while stack:
            n = stack.pop()
            if root is not None and n == root.name:
                return True
            for (cop, _pos) in consumers.get(n, ()):  # noqa: B007
                if cop.name in seen:
                    continue
                seen.add(cop.name)
                if cop.opcode in TRANSPARENT or cop is root:
                    stack.append(cop.name)
        return root is not None and name == root.name

    total = 0.0
    root_aliased = False
    for i, o in enumerate(op.operands):
        full = comp.by_name.get(o, Shape("opaque", ())).bytes
        pname = next((n for n, idx in param_idx.items() if idx == i), None)
        if pname is None:
            total += full
            continue
        # effective consumers through transparent chains
        eff = []
        seen = set()
        stack = [pname]
        while stack:
            n = stack.pop()
            for (cop, pos) in consumers.get(n, ()):
                if (cop.name, pos) in seen:
                    continue
                seen.add((cop.name, pos))
                if cop.opcode in TRANSPARENT:
                    stack.append(cop.name)
                else:
                    eff.append((cop, pos))
        if not eff:
            continue                                 # unused param
        b = 0.0
        fallback = False
        for (cop, pos) in eff:
            if cop.opcode == "dynamic-slice" and pos == 0:
                b += cop.shape.bytes
            elif cop.opcode == "dynamic-update-slice" and pos == 0 \
                    and flows_to_root_transparent(cop.name):
                upd = (fc.by_name.get(cop.operands[1], Shape("opaque", ()))
                       if len(cop.operands) > 1 else Shape("opaque", ()))
                b += 2 * upd.bytes
                root_aliased = True
            elif cop.opcode == "scatter" and pos == 0 \
                    and flows_to_root_transparent(cop.name):
                upd = (fc.by_name.get(cop.operands[-1], Shape("opaque", ()))
                       if len(cop.operands) >= 3 else Shape("opaque", ()))
                b += 2 * upd.bytes
                root_aliased = True
            else:
                fallback = True
                break
        total += full if fallback else b
    if not root_aliased:
        total += op.shape.bytes                      # output written in full
    return total


@dataclass
class CostTotals:
    flops: float = 0.0
    bytes: float = 0.0
    collective_wire_bytes: float = 0.0
    collectives: Dict[str, float] = field(default_factory=lambda: defaultdict(float))
    collective_counts: Dict[str, int] = field(default_factory=lambda: defaultdict(int))


def _op_wire_bytes(op: Op, n_devices: int) -> Tuple[str, float]:
    base = op.opcode.replace("-start", "")
    g = _group_size(op.attrs, n_devices)
    R = op.shape.bytes
    if base == "all-reduce":
        return base, 2 * R * (g - 1) / g
    if base in ("all-gather", "all-to-all", "collective-broadcast",
                "ragged-all-to-all"):
        return base, R * (g - 1) / g
    if base == "reduce-scatter":
        return base, R * (g - 1)
    if base.startswith("collective-permute"):
        return "collective-permute", R
    return base, 0.0


def _is_convert_only(callee: Computation) -> bool:
    for fop in callee.ops:
        if fop.opcode not in ("convert", "parameter", "bitcast", "copy",
                              "tuple", "get-tuple-element", "reshape",
                              "transpose"):
            return False
    return any(fop.opcode == "convert" for fop in callee.ops)


def analyze(text: str, n_devices: int, *,
            discount_converts: bool = True) -> CostTotals:
    comps, entry = parse_hlo(text)
    totals = CostTotals()
    # computations reachable only via fusion are folded into the fusion op
    fused: set = set()
    for comp in comps.values():
        for op in comp.ops:
            if op.opcode == "fusion":
                for c in _called(op):
                    fused.add(c)

    memo: Dict[str, CostTotals] = {}

    def cost_of(name: str) -> CostTotals:
        if name in memo:
            return memo[name]
        comp = comps.get(name)
        out = CostTotals()
        memo[name] = out
        if comp is None:
            return out
        for op in comp.ops:
            oc = op.opcode
            if oc.endswith("-done"):
                continue
            if discount_converts and oc == "convert":
                continue
            if discount_converts and oc == "fusion":
                callees = [comps[c] for c in _called(op) if c in comps]
                if callees and _is_convert_only(callees[0]):
                    continue
            if oc.replace("-start", "") in _COLLECTIVES:
                kind, wb = _op_wire_bytes(op, n_devices)
                out.collective_wire_bytes += wb
                out.collectives[kind] += wb
                out.collective_counts[kind] += 1
                out.bytes += op.shape.bytes
                continue
            if oc == "while":
                body, cond = None, None
                bm = re.search(r"body=%?([\w.\-]+)", op.attrs)
                cm = re.search(r"condition=%?([\w.\-]+)", op.attrs)
                if bm and cm and bm.group(1) in comps:
                    trips = _trip_count(comps[cm.group(1)], comps[bm.group(1)])
                    sub = cost_of(bm.group(1))
                    csub = cost_of(cm.group(1))
                    out.flops += trips * (sub.flops + csub.flops)
                    out.bytes += trips * (sub.bytes + csub.bytes)
                    out.collective_wire_bytes += trips * sub.collective_wire_bytes
                    for k, v in sub.collectives.items():
                        out.collectives[k] += trips * v
                        out.collective_counts[k] += trips * sub.collective_counts[k]
                continue
            if oc == "dynamic-slice":
                # reads only the slice, not the sliced operand
                out.bytes += 2 * op.shape.bytes
                continue
            if oc == "dynamic-update-slice":
                # in-place: traffic = read+write of the update region
                upd = (comp.by_name.get(op.operands[1], Shape("opaque", ()))
                       if len(op.operands) > 1 else Shape("opaque", ()))
                out.bytes += 2 * upd.bytes
                continue
            if oc == "scatter":
                # in-place on TPU: traffic = indices + 2x updates region
                upd = (comp.by_name.get(op.operands[-1], Shape("opaque", ()))
                       if len(op.operands) >= 3 else Shape("opaque", ()))
                idxs = (comp.by_name.get(op.operands[1], Shape("opaque", ()))
                        if len(op.operands) >= 2 else Shape("opaque", ()))
                out.bytes += 2 * upd.bytes + idxs.bytes
                continue
            if oc in ("fusion", "call", "conditional", "custom-call", "reduce",
                      "sort", "map", "reduce-window", "select-and-scatter"):
                # bytes at the op boundary; operands a fusion consumes only
                # through dynamic-slice count at slice size, and a fusion
                # rooted in dynamic-update-slice aliases its big operand
                out.bytes += _fusion_bytes(op, comp, comps) if oc == "fusion" \
                    else (sum(comp.by_name.get(o, Shape("opaque", ())).bytes
                              for o in op.operands) + op.shape.bytes)
                if oc == "reduce":
                    out.flops += sum(comp.by_name.get(o, Shape("opaque", ())).elems
                                     for o in op.operands[:len(op.operands) // 2])
                for c in _called(op):
                    if oc == "fusion":
                        fc = comps.get(c)
                        if fc:        # flops inside fusions still count
                            for fop in fc.ops:
                                if fop.opcode == "dot":
                                    out.flops += _dot_flops(fop, fc)
                                elif fop.opcode in _ELEMENTWISE:
                                    out.flops += fop.shape.elems
                                elif fop.opcode == "reduce":
                                    out.flops += sum(
                                        fc.by_name.get(o, Shape("opaque", ())).elems
                                        for o in fop.operands[:len(fop.operands) // 2])
                    else:
                        sub = cost_of(c)
                        out.flops += sub.flops
                        out.bytes += sub.bytes
                        out.collective_wire_bytes += sub.collective_wire_bytes
                        for k, v in sub.collectives.items():
                            out.collectives[k] += v
                            out.collective_counts[k] += sub.collective_counts[k]
                continue
            # plain op
            if oc == "dot":
                out.flops += _dot_flops(op, comp)
            elif oc == "convolution":
                # flops = 2 * out_elems * (kernel elems / out_channels)
                rhs = comp.by_name.get(op.operands[1]) if len(op.operands) > 1 else None
                kmul = (rhs.elems // max(rhs.dims[-1], 1)) if rhs and rhs.dims else 1
                out.flops += 2 * op.shape.elems * kmul
            elif oc in _ELEMENTWISE:
                out.flops += op.shape.elems
            if oc not in ("parameter", "constant", "get-tuple-element", "tuple",
                          "bitcast", "copy-start", "copy-done"):
                opnd = sum(comp.by_name.get(o, Shape("opaque", ())).bytes
                           for o in op.operands)
                out.bytes += opnd + op.shape.bytes
        return out

    ent = cost_of(entry)
    return ent
