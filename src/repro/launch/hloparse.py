"""Trip-count-aware cost extraction from compiled (scheduled) HLO text.

``compiled.cost_analysis()`` counts every while-loop body ONCE (validated
in tests/test_hloparse.py), which under-counts scanned-layer programs by
~n_layers x n_accum.  This walker reconstructs exact per-device costs:

  * splits the module into computations;
  * per instruction: dot FLOPs (2 * |result| * |contracted dims|, bucketed
    by operand dtype so int8 MXU work is separated), bytes accessed
    (operands + result, at fusion granularity — matching HloCostAnalysis
    semantics on the post-fusion module), collective bytes by kind;
  * multiplies while bodies by ``backend_config.known_trip_count`` and
    recurses through call/fusion/conditional (max over branches).

The result is the roofline numerator set: flops (bf16/int8), HBM bytes,
and per-kind collective bytes — all per device, loop-exact.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
                "u32": 4, "f16": 2, "bf16": 2, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[\d,]*\]\S*)\s+([\w\-]+)\(")
_CALLED_RE = re.compile(r"(?:calls=|to_apply=|body=)%([\w.\-]+)")
_COND_RE = re.compile(r"condition=%([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_BATCH_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")

_NO_BYTES_OPS = {"parameter", "tuple", "get-tuple-element", "bitcast",
                 "constant", "after-all", "custom-call"}


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (empty when the
    backend reports nothing)."""
    return dict(compiled.cost_analysis() or {})


def _operand_segment(line: str, op: str) -> str:
    """The balanced-paren operand list of ``op`` on this line.

    Operands are printed as bare names (``dot(%a, %b)``); the parens are
    matched by depth so nested tuples or calls in the list stay inside.
    """
    i = line.find(" " + op + "(")
    if i < 0:
        return ""
    start = line.index("(", i)
    depth = 0
    for j in range(start, len(line)):
        if line[j] == "(":
            depth += 1
        elif line[j] == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:j]
    return line[start + 1:]


def _shape_info(type_str: str) -> List[Tuple[str, int]]:
    """[(dtype, numel), ...] for a possibly-tuple type string."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((dt, n))
    return out


def _bytes_of(type_str: str) -> float:
    return sum(n * _DTYPE_BYTES[dt] for dt, n in _shape_info(type_str))


class Cost(dict):
    KEYS = ("flops", "flops_int8", "bytes", "bytes_dot", "coll_bytes",
            "transcendentals")

    def __init__(self):
        super().__init__({k: 0.0 for k in self.KEYS})
        self["coll"] = {}

    def add(self, other: "Cost", mult: float = 1.0) -> None:
        for k in self.KEYS:
            self[k] += other[k] * mult
        for kind, d in other["coll"].items():
            mine = self["coll"].setdefault(kind, {"count": 0.0, "bytes": 0.0})
            mine["count"] += d["count"] * mult
            mine["bytes"] += d["bytes"] * mult


def _parse_computations(hlo: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        if not line.strip():
            continue
        if not line.startswith(" ") and "{" in line and "(" in line:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", line)
            if m:
                cur = m.group(1)
                comps[cur] = []
                if line.startswith("ENTRY"):
                    comps["__entry__"] = comps[cur]
                continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line)
    return comps


def _dot_flops(line: str, result_type: str,
               types: Dict[str, str]) -> Tuple[float, bool]:
    """(flops, is_int8). flops = 2 * |result| * prod(contracted lhs dims)."""
    info = _shape_info(result_type)
    if not info:
        return 0.0, False
    result_n = info[0][1]
    names = _OPERAND_RE.findall(_operand_segment(line, "dot"))
    contract = 1
    lhs_dt = None
    m = _SHAPE_RE.search(types.get(names[0], "")) if names else None
    if m:
        lhs_dt = m.group(1)
        lhs_dims = [int(d) for d in m.group(2).split(",") if d]
        cm = _CONTRACT_RE.search(line)
        if cm and cm.group(1):
            for i in (int(x) for x in cm.group(1).split(",")):
                if i < len(lhs_dims):
                    contract *= lhs_dims[i]
    # Integer dots are the int8-container path (quantized serving / int8
    # KV attention).  On TPU the operands stay s8; the CPU backend widens
    # them to s32 inside a fusion before the dot, so classify by "any
    # integer accumulate" rather than chasing converts through fusions.
    is_int8 = lhs_dt in ("s8", "u8", "s16", "u16", "s32", "u32")
    return 2.0 * result_n * contract, is_int8


def analyze(hlo: str) -> Cost:
    comps = _parse_computations(hlo)
    cache: Dict[str, Cost] = {}

    def comp_cost(name: str) -> Cost:
        if name in cache:
            return cache[name]
        cost = Cost()
        cache[name] = cost                       # cycle guard
        defs = [d.groups() + (line,) for line in comps.get(name, [])
                for d in [_DEF_RE.match(line)] if d]
        # operands print as bare names: resolve them through this
        # computation's definitions (parameters included)
        types = {var: rtype for var, rtype, _, _ in defs}

        def operand_bytes(line: str, op: str) -> float:
            return sum(_bytes_of(types.get(v, "")) for v in
                       _OPERAND_RE.findall(_operand_segment(line, op)))

        for _, result_type, op, line in defs:
            if op == "while":
                body = _CALLED_RE.search(line)
                trip = _TRIP_RE.search(line)
                n = float(trip.group(1)) if trip else 1.0
                if body:
                    cost.add(comp_cost(body.group(1)), n)
                continue
            if op == "conditional":
                br = _BRANCHES_RE.search(line)
                if br:
                    branch_costs = [comp_cost(b.strip().lstrip("%"))
                                    for b in br.group(1).split(",")]
                    best = max(branch_costs,
                               key=lambda c: c["flops"] + c["bytes"])
                    cost.add(best)
                continue
            if op in ("fusion", "call"):
                callee = _CALLED_RE.search(line)
                if callee:
                    inner = comp_cost(callee.group(1))
                    # dots/collectives inside count; bytes at fusion boundary
                    part = Cost()
                    part.add(inner)
                    part["bytes"] = 0.0
                    cost.add(part)
                cost["bytes"] += _bytes_of(result_type) + operand_bytes(
                    line, op)
                continue
            if op == "dot":
                fl, is8 = _dot_flops(line, result_type, types)
                cost["flops_int8" if is8 else "flops"] += fl
                b = _bytes_of(result_type) + operand_bytes(line, op)
                cost["bytes"] += b
                cost["bytes_dot"] += b
                continue
            for kind in COLLECTIVES:
                if op == kind or op == kind + "-start":
                    b = _bytes_of(result_type)
                    dd = cost["coll"].setdefault(
                        kind, {"count": 0.0, "bytes": 0.0})
                    dd["count"] += 1
                    dd["bytes"] += b
                    cost["coll_bytes"] += b * (2.0 if kind == "all-reduce"
                                               else 1.0)
                    break
            if op in _NO_BYTES_OPS or op.endswith("-done"):
                continue
            cost["bytes"] += _bytes_of(result_type) + operand_bytes(
                line, op)
        return cost

    return comp_cost("__entry__" if "__entry__" in comps
                     else next(iter(comps)))


def summarize(hlo: str) -> dict:
    """bytes      — HloCostAnalysis semantics on the *CPU-fused* module
                    (pessimistic: the CPU backend fuses less than TPU, so
                    elementwise chains over-count HBM traffic);
       bytes_opt  — ideal-fusion floor: dot operands/results + collective
                    traffic (everything between dots fuses into them).
    The true TPU memory term lies between; §Roofline reports both."""
    c = analyze(hlo)
    return {
        "flops": c["flops"],
        "flops_int8": c["flops_int8"],
        "bytes": c["bytes"],
        "bytes_opt": c["bytes_dot"] + c["coll_bytes"],
        "collective_bytes": c["coll_bytes"],
        "collectives": {k: {"count": v["count"], "bytes": v["bytes"]}
                        for k, v in c["coll"].items()},
    }
