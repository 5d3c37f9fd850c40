"""Post-SPMD HLO analysis: collective inventory and wire-byte accounting.

``collective_stats`` scans optimized HLO text for all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute ops, takes each op's RESULT
shape, parses its replica-group size, and converts to *wire bytes per device*
with the standard ring-algorithm factors:

    all-gather          (n-1)/n x result
    all-reduce        2 (n-1)/n x result
    reduce-scatter      (n-1)   x result      (operand = n x result)
    all-to-all          (n-1)/n x result
    collective-permute          1 x result

Ops inside while-loop bodies are multiplied by the loop trip count, which is
recovered from the loop-condition's comparison constant (scan lowers to a
while with a counter compared against a literal).  This matters because XLA's
``cost_analysis`` counts a while body exactly once.

``pool_relayouts`` finds copies and transposes of whole layers of a serve
state's page pool: a program that updates its pool in place has none.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_WIRE_FACTOR = {
    "all-gather": lambda n: (n - 1) / max(n, 1),
    "all-reduce": lambda n: 2 * (n - 1) / max(n, 1),
    "reduce-scatter": lambda n: float(n - 1),
    "all-to-all": lambda n: (n - 1) / max(n, 1),
    "collective-permute": lambda n: 1.0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"(?!-)\b")  # (?!-) rejects the -done halves of async pairs
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_COMPUTATION_RE = re.compile(r"^(\s*)%?([\w.\-]+)\s*\(.*\)\s*->")
_WHILE_RE = re.compile(
    r"while\(.*\).*condition=%?([\w.\-]+).*body=%?([\w.\-]+)|"
    r"while\(.*\).*body=%?([\w.\-]+).*condition=%?([\w.\-]+)")
_CMP_CONST_RE = re.compile(r"compare\(")
_CONST_RE = re.compile(r"=\s*s(?:32|64)\[\]\s+constant\((\d+)\)")


@dataclasses.dataclass
class CollectiveStats:
    total_wire_bytes: float
    by_kind: Dict[str, float]
    count: int
    ops: List[Tuple[str, float, int]]  # (kind, wire_bytes, group_size)


def _bytes_of_shape_str(s: str) -> float:
    """Sum bytes over all array shapes appearing in a result-type string."""
    total = 0.0
    for m in _SHAPE_RE.finditer(s):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur = "__toplevel__"
    comps[cur] = []
    for line in hlo.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m and not line.lstrip().startswith("//"):
            cur = m.group(2)
            comps[cur] = []
        comps[cur].append(line)
    return comps


def _trip_counts(comps: Dict[str, List[str]]) -> Dict[str, int]:
    """body-computation name -> trip count (best-effort constant parse)."""
    trips: Dict[str, int] = {}
    for _name, lines in comps.items():
        for line in lines:
            if " while(" not in line:
                continue
            mcond = re.search(r"condition=%?([\w.\-]+)", line)
            mbody = re.search(r"body=%?([\w.\-]+)", line)
            if not (mcond and mbody):
                continue
            cond = comps.get(mcond.group(1), [])
            bound = None
            for cl in cond:
                mc = _CONST_RE.search(cl)
                if mc:
                    bound = int(mc.group(1))
            if bound is not None:
                trips[mbody.group(1)] = max(bound, 1)
    return trips


def _expand_trips(comps, trips) -> Dict[str, int]:
    """Multiply nested loop bodies (body within body)."""
    eff: Dict[str, int] = dict(trips)
    # fixpoint over nesting (bounded depth)
    for _ in range(4):
        changed = False
        for name, lines in comps.items():
            outer = eff.get(name)
            if not outer:
                continue
            for line in lines:
                mbody = re.search(r"body=%?([\w.\-]+)", line)
                if mbody and mbody.group(1) in trips:
                    want = trips[mbody.group(1)] * outer
                    if eff.get(mbody.group(1), 0) < want:
                        eff[mbody.group(1)] = want
                        changed = True
        if not changed:
            break
    return eff


def collective_stats(hlo: str) -> CollectiveStats:
    comps = _split_computations(hlo)
    trips = _expand_trips(comps, _trip_counts(comps))
    by_kind: Dict[str, float] = defaultdict(float)
    ops = []
    count = 0
    for cname, lines in comps.items():
        mult = trips.get(cname, 1)
        for line in lines:
            m = _OP_RE.match(line)
            if not m:
                continue
            kind = m.group(2).replace("-start", "")
            if kind not in _COLL_KINDS:
                continue
            shape_bytes = _bytes_of_shape_str(m.group(1))
            if "-start" in m.group(2) and m.group(1).startswith("("):
                shape_bytes /= 2  # async-start result tuple repeats in+out
            g = 1
            mg = _GROUP_IOTA_RE.search(line)
            if mg:
                g = int(mg.group(2))
            else:
                ml = _GROUP_LIST_RE.search(line)
                if ml:
                    g = len([x for x in ml.group(1).split(",") if x.strip()])
            wire = shape_bytes * _WIRE_FACTOR[kind](g) * mult
            by_kind[kind] += wire
            ops.append((kind, wire, g))
            count += mult
    return CollectiveStats(sum(by_kind.values()), dict(by_kind), count, ops)


_HLO_TYPE = {"bfloat16": "bf16", "float16": "f16", "float32": "f32",
             "int8": "s8"}
_MOVE_RE = re.compile(
    r"=\s+\(?([a-z0-9]+)\[([0-9,]*)\][^=]*?\s(copy|copy-start|transpose)\(")


def pool_relayouts(hlo: str, leaves) -> List[str]:
    """The copies, copy-starts and transposes in ``hlo`` that hold whole
    layers of a stacked pool leaf ``(layers, rows, width)`` (anything with
    ``dtype`` and ``shape``): the leaf's element type, a whole multiple of
    one layer's elements, and a dim equal to its row count or row width.
    Returns their HLO lines."""
    found = []
    for line in hlo.splitlines():
        m = _MOVE_RE.search(line)
        if not m:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        n = 1
        for d in dims:
            n *= d
        for leaf in leaves:
            per_layer = 1
            for d in leaf.shape[1:]:
                per_layer *= d
            if (m.group(1) == _HLO_TYPE.get(str(leaf.dtype))
                    and n >= per_layer and n % per_layer == 0
                    and set(leaf.shape[1:]) & set(dims)):
                found.append(line.strip())
                break
    return found
