"""Loop-aware analyzer over post-optimization HLO text.

``jax.stages.Compiled.cost_analysis()`` counts every computation once, so a
``lax.scan`` over 88 layers reports ~1/88 of the real flops.  This module
re-derives flops / HBM traffic / collective wire bytes from
``compiled.as_text()`` instead, multiplying ``while`` body costs by the trip
count recovered from the loop condition.  All numbers are *per device*: the
partitioned module already carries local shapes.

Outputs (``analyze_hlo_text``):
  flops          — dot/convolution flops, trip-count weighted
  bytes          — HBM traffic with fusions as emitted (operands + outputs
                   of every traffic-bearing op; fusions count as one op)
  bytes_unfused  — upper bound with every fusion expanded to its body ops
  wire_bytes     — per-collective link traffic (ring-algorithm accounting)
  collectives    — {base opcode: {"count": n, "bytes": wire_bytes}}
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

# --------------------------------------------------------------------------
# shapes
# --------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# a dtype token must directly abut '[' — "replica_groups=[2,4]" has '=' in
# between and therefore never matches as a shape
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _dims(dim_str: str) -> list:
    return [int(d) for d in dim_str.split(",") if d]


def _shape_bytes(shape: str) -> int:
    """Total bytes of a (possibly tuple) HLO shape string; strings that are
    not shapes (e.g. replica_groups annotations) contribute 0."""
    total = 0
    for dtype, dim_str in _SHAPE_RE.findall(shape):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        n = 1
        for d in _dims(dim_str):
            n *= d
        total += n * size
    return total


def _shape_dims(shape: str) -> list:
    """Dims of the first array shape in the string ([] for scalars/unknown)."""
    m = _SHAPE_RE.search(shape)
    return _dims(m.group(2)) if m else []


def _prod(xs) -> int:
    n = 1
    for x in xs:
        n *= x
    return n


# --------------------------------------------------------------------------
# parsing
# --------------------------------------------------------------------------

_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OP_HEAD_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s*([\w\-]+)\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CALL_ATTR_RE = re.compile(r"(?:to_apply|calls)=%?([\w.\-]+)")
_INT_RE = re.compile(r"-?\d+")
# the trip count XLA proved for a while loop, in its backend_config
_KNOWN_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')

_COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast"}

# opcodes that move no HBM traffic of their own
_NO_TRAFFIC = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "after-all", "partition-id", "replica-id",
               "domain", "opt-barrier", "get-dimension-size"}

# post-fusion ops that anchor real HBM traffic (used by launch/attribute.py
# to pick the rows worth displaying)
_FUSED_ANCHORS = {"fusion", "dot", "convolution", "custom-call", "copy",
                  "copy-start", "gather", "scatter", "reduce", "sort",
                  "dynamic-slice", "dynamic-update-slice", "reduce-window",
                  "select-and-scatter", "cholesky", "triangular-solve",
                  "concatenate", "pad", "rng", "rng-bit-generator",
                  "while", "conditional"}


@dataclass
class HloOp:
    name: str
    shape: str      # result shape string (may be a tuple shape)
    opcode: str
    rest: str       # operand list + attributes, from the opening paren on

    operands: list = field(default_factory=list)


def _split_result_shape(s: str):
    """Split '  <shape> <opcode>(...' -> (shape, remainder) handling tuple
    shapes with nested parens."""
    s = s.lstrip()
    if s.startswith("("):
        depth = 0
        for i, c in enumerate(s):
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if depth == 0:
                    return s[:i + 1], s[i + 1:]
        return s, ""
    m = re.match(r"[\w\[\],<=]+(?:\{[^}]*\})?", s)
    if m:
        return m.group(0), s[m.end():]
    return "", s


def _operand_segment(rest: str) -> str:
    """The balanced '(...)' operand list at the start of ``rest``."""
    if not rest.startswith("("):
        return ""
    depth = 0
    for i, c in enumerate(rest):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return rest[:i + 1]
    return rest


def _parse_op(line: str):
    m = _OP_HEAD_RE.match(line)
    if not m:
        return None
    name = m.group(1)
    shape, tail = _split_result_shape(line[m.end():])
    om = _OPCODE_RE.match(tail)
    if not om:
        return None
    opcode = om.group(1)
    rest = tail[om.end() - 1:]  # keep the opening paren
    op = HloOp(name=name, shape=shape, opcode=opcode, rest=rest)
    op.operands = _OPERAND_RE.findall(_operand_segment(rest))
    return op


def parse_computations(text: str):
    """-> (dict comp_name -> [HloOp], entry_comp_name)."""
    comps = {}
    entry = None
    cur = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        op = _parse_op(line)
        if op is not None:
            comps[cur].append(op)
    if entry is None and comps:
        entry = next(iter(comps))
    return comps, entry


# --------------------------------------------------------------------------
# analyzer
# --------------------------------------------------------------------------


class HloAnalyzer:
    def __init__(self, text: str):
        self.comps, self.entry = parse_computations(text)
        self.shape_of = {}
        self.op_by_name = {}
        for ops in self.comps.values():
            for op in ops:
                self.shape_of[op.name] = op.shape
                self.op_by_name[op.name] = op
        m = re.search(r"num_partitions=(\d+)", text)
        self.num_partitions = int(m.group(1)) if m else 1
        self._cost_memo = {}

    # -- per-op primitives -------------------------------------------------

    def _operand_bytes(self, op: HloOp) -> int:
        return sum(_shape_bytes(self.shape_of.get(n, ""))
                   for n in op.operands)

    def _op_traffic(self, op: HloOp) -> float:
        """operand reads + result write, in bytes."""
        return self._operand_bytes(op) + _shape_bytes(op.shape)

    def _group_size(self, op: HloOp) -> int:
        """Participants per replica group of a collective."""
        m = re.search(r"replica_groups=\{\{([^}]*)\}", op.rest)
        if m:
            return max(1, len([x for x in m.group(1).split(",") if x.strip()]))
        m = re.search(r"replica_groups=\[([\d,]+)\]<=", op.rest)
        if m:  # iota format [groups, group_size]
            dims = _dims(m.group(1))
            return dims[-1] if dims else 1
        if re.search(r"replica_groups=\{\}", op.rest):
            return self.num_partitions
        return self.num_partitions

    def _collective_payload(self, op: HloOp) -> int:
        """Payload bytes of a collective.  Async '-start' ops return a
        tuple aliasing (input, output); summing it double-counts, so take
        the largest single component instead."""
        out = _shape_bytes(op.shape)
        if op.opcode.endswith("-start") and op.shape.lstrip().startswith("("):
            comps = [_DTYPE_BYTES.get(d, 0) * _prod(_dims(s))
                     for d, s in _SHAPE_RE.findall(op.shape)]
            out = max(comps, default=0)
        return max(self._operand_bytes(op), out)

    def _wire_bytes(self, op: HloOp, base: str) -> float:
        """Ring-algorithm per-device link bytes for one collective."""
        n = self._collective_payload(op)
        g = self._group_size(op)
        if g <= 1:
            return 0.0
        if base == "all-reduce":
            return 2.0 * n * (g - 1) / g
        if base == "collective-permute":
            return float(n)
        return n * (g - 1) / g

    def _trip_count(self, cond_comp: str) -> int:
        """Trip count of a while loop whose op carries no
        ``known_trip_count``, from its condition computation: find the
        compare against a constant (counting loops emitted by
        lax.scan / fori_loop compare an induction var with direction LT/LE).
        Unknown patterns conservatively report 1."""
        consts = {}
        for op in self.comps.get(cond_comp, []):
            if op.opcode == "constant":
                m = _INT_RE.search(_operand_segment(op.rest))
                if m:
                    consts[op.name] = int(m.group(0))
        for op in self.comps.get(cond_comp, []):
            if op.opcode != "compare":
                continue
            d = re.search(r"direction=(\w+)", op.rest)
            if not d or len(op.operands) != 2:
                continue
            lhs, rhs = op.operands
            direction = d.group(1)
            if rhs in consts:        # iv <cmp> C
                c = consts[rhs]
                if direction == "LT":
                    return max(1, c)
                if direction == "LE":
                    return max(1, c + 1)
                if direction in ("GT", "GE"):  # count-down from unknown start
                    return 1
            if lhs in consts:        # C <cmp> iv
                c = consts[lhs]
                if direction == "GT":
                    return max(1, c)
                if direction == "GE":
                    return max(1, c + 1)
        return 1

    def _dot_flops(self, op: HloOp) -> float:
        """2 * |output| * contraction size (batch dims handled implicitly:
        they appear in the output and not in the contraction)."""
        out = _prod(_shape_dims(op.shape))
        contract = 1
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
        if m and op.operands:
            lhs_dims = _shape_dims(self.shape_of.get(op.operands[0], ""))
            for i in _dims(m.group(1)):
                if i < len(lhs_dims):
                    contract *= lhs_dims[i]
        return 2.0 * out * contract

    def _conv_flops(self, op: HloOp) -> float:
        """2 * |output| * (kernel taps per output element)."""
        out = _prod(_shape_dims(op.shape))
        if len(op.operands) < 2:
            return 2.0 * out
        kdims = _shape_dims(self.shape_of.get(op.operands[1], ""))
        taps = _prod(kdims)
        m = re.search(r"dim_labels=\w+_(\w+)->", op.rest)
        if m and kdims:
            o_pos = m.group(1).find("o")
            if 0 <= o_pos < len(kdims):
                taps //= max(1, kdims[o_pos])
        return 2.0 * out * taps

    # -- recursive cost ----------------------------------------------------

    def _comp_cost(self, comp: str):
        """(flops, bytes, bytes_unfused, wire, {base: [count, bytes]})."""
        if comp in self._cost_memo:
            return self._cost_memo[comp]
        # memoize-before-recurse guard against (malformed) cycles
        self._cost_memo[comp] = (0.0, 0.0, 0.0, 0.0, {})
        flops = nbytes = unfused = wire = 0.0
        colls = defaultdict(lambda: [0, 0.0])

        def absorb(sub, mult=1):
            nonlocal flops, nbytes, unfused, wire
            sf, sb, su, sw, sc = sub
            flops += sf * mult
            nbytes += sb * mult
            unfused += su * mult
            wire += sw * mult
            for k, (c, b) in sc.items():
                colls[k][0] += c * mult
                colls[k][1] += b * mult

        for op in self.comps.get(comp, []):
            oc = op.opcode
            if oc == "while":
                cm = re.search(r"condition=%?([\w.\-]+)", op.rest)
                bm = re.search(r"body=%?([\w.\-]+)", op.rest)
                known = _KNOWN_TRIP_RE.search(op.rest)
                trip = (int(known.group(1)) if known else
                        self._trip_count(cm.group(1)) if cm else 1)
                if bm:
                    absorb(self._comp_cost(bm.group(1)), trip)
                continue
            if oc in ("call", "async-start"):
                m = _CALL_ATTR_RE.search(op.rest)
                if m:
                    absorb(self._comp_cost(m.group(1)))
                continue
            if oc == "conditional":
                branches = re.search(r"branch_computations=\{([^}]*)\}",
                                     op.rest)
                names = (_OPERAND_RE.findall(branches.group(1))
                         if branches else
                         re.findall(r"(?:true|false)_computation=%?([\w.\-]+)",
                                    op.rest))
                if names:  # one branch executes; bound with the costliest
                    absorb(max((self._comp_cost(n) for n in names),
                               key=lambda c: (c[0], c[1])))
                continue
            if oc == "fusion":
                m = _CALL_ATTR_RE.search(op.rest)
                traffic = self._op_traffic(op)
                nbytes += traffic
                if m:
                    sub = self._comp_cost(m.group(1))
                    flops += sub[0]
                    unfused += max(sub[2], traffic)
                else:
                    unfused += traffic
                continue
            if oc in _NO_TRAFFIC:
                continue

            base = oc[:-6] if oc.endswith("-start") else oc
            if oc.endswith("-done") or oc.endswith("-update"):
                continue  # paired with the -start that carried the cost
            if base in _COLLECTIVES:
                w = self._wire_bytes(op, base)
                wire += w
                colls[base][0] += 1
                colls[base][1] += w
                traffic = self._operand_bytes(op) + self._collective_payload(op)
                nbytes += traffic
                unfused += traffic
                continue
            if oc == "dot":
                flops += self._dot_flops(op)
            elif oc == "convolution":
                flops += self._conv_flops(op)
            traffic = self._op_traffic(op)
            nbytes += traffic
            unfused += traffic

        result = (flops, nbytes, unfused, wire, dict(colls))
        self._cost_memo[comp] = result
        return result

    def analyze(self) -> dict:
        flops, nbytes, unfused, wire, colls = self._comp_cost(self.entry)
        return {
            "flops": int(flops),
            "bytes": float(nbytes),
            "bytes_unfused": float(unfused),
            "wire_bytes": float(wire),
            "collectives": {k: {"count": int(c), "bytes": float(b)}
                            for k, (c, b) in sorted(colls.items())},
        }


def analyze_hlo_text(text: str) -> dict:
    """Per-device flops / traffic / wire accounting of a partitioned,
    optimized HLO module (``compiled.as_text()``)."""
    return HloAnalyzer(text).analyze()
