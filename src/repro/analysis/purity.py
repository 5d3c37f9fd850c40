"""JAX hot-path purity lint.

Three sub-checks, one theme: the decode/prefill step loops must stay on
the device, and jitted program builders must stay deterministic.

* **HOST_SYNC** — ``.item()``, ``np.asarray(...)``, ``np.array(...)``,
  ``jax.device_get(...)``, ``.block_until_ready()`` inside any function
  reachable from a hot root (the engine step loops and backend admit /
  decode / handoff / spill / fault paths).  Each of these forces a
  device->host transfer and stalls the dispatch pipeline; the handful that
  are *by design* (e.g. the one token sync per decode step) live in the
  allowlist with a justification.
* **HOST_SYNC_LOOP** — the same sync calls when they sit lexically inside a
  loop or comprehension in a hot-reachable function.  A sync *per
  iteration* (e.g. one ``jax.device_get`` per prompt page in a handoff
  export) multiplies the stall by the trip count; it gets its own rule so
  an allowlisted single sync in a function can never mask a reintroduced
  per-item sync loop in the same function.
* **IMPURE_BUILDER** — wall-clock / Python RNG (``time.*``, ``random.*``,
  ``np.random.*``, ``datetime.*``) inside the closures that ``make_*``
  program builders return.  Those closures are traced by ``jax.jit``:
  impure calls bake a trace-time value into the compiled program and
  silently desync replicas that compiled at different moments.
* **KERNEL_GUARD** — every ``kernels/<name>/ops.py`` must expose a
  ``supported(...)`` gate containing a ``%`` divisibility check or a ``<=``
  size bound, so block shapes that don't tile the Pallas grid, or don't fit
  its VMEM, fall back to the reference path instead of mis-launching.

Reachability is a deliberately simple call graph: hot roots are matched by
*name* (so a new backend's ``admit`` is hot the day it is written), edges
follow ``self.<m>()`` calls within a class hierarchy and bare-name calls to
module-level functions anywhere in the scanned tree.  No type inference —
over-approximate and allowlist beats under-approximate and silent.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.common import (Finding, SourceFile, attr_chain,
                                   func_defs, self_field)

HOST_SYNC = "HOST_SYNC"
HOST_SYNC_LOOP = "HOST_SYNC_LOOP"
IMPURE_BUILDER = "IMPURE_BUILDER"
KERNEL_GUARD = "KERNEL_GUARD"

# Functions with these names are hot roots wherever they appear: the engine
# step loops, admission, the backend fast paths they dispatch into, and the
# tiered-memory movers (spill/fault run between decode steps on the same
# engine loop thread).
HOT_ROOTS = {
    "step", "_decode_once", "_decode_device", "decode_step",
    "_admit", "_admit_one", "admit", "_admit_cold", "_admit_resume",
    "import_handoff", "export_handoff", "prefill_to_handoff",
    "_spill", "_fault_in",
}

_LOOP_NODES = (ast.For, ast.AsyncFor, ast.While, ast.ListComp, ast.SetComp,
               ast.DictComp, ast.GeneratorExp)

_SYNC_ATTRS = {"item", "block_until_ready"}
_NP_SYNC = {"asarray", "array", "ascontiguousarray", "copyto"}
_IMPURE_MODULES = {"time", "random", "datetime", "secrets"}


def _is_host_sync(call: ast.Call) -> Optional[str]:
    fn = call.func
    if isinstance(fn, ast.Attribute):
        if fn.attr in _SYNC_ATTRS:
            return f".{fn.attr}()"
        chain = attr_chain(fn)
        if chain:
            head, _, rest = chain.partition(".")
            if head in ("np", "numpy") and rest in _NP_SYNC:
                return f"{chain}()"
            if chain == "jax.device_get":
                return "jax.device_get()"
    return None


def _is_impure(call: ast.Call) -> Optional[str]:
    chain = attr_chain(call.func)
    if not chain:
        return None
    head = chain.split(".", 1)[0]
    if head in _IMPURE_MODULES:
        return chain + "()"
    if chain.startswith(("np.random.", "numpy.random.")):
        return chain + "()"
    return None


class _FuncInfo:
    def __init__(self, src: SourceFile, qualname: str, cls: Optional[str],
                 node: ast.FunctionDef):
        self.src = src
        self.qualname = qualname
        self.cls = cls
        self.node = node
        self.self_calls: Set[str] = set()
        self.name_calls: Set[str] = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                field = self_field(sub.func)
                if field:
                    self.self_calls.add(field)
                elif isinstance(sub.func, ast.Name):
                    self.name_calls.add(sub.func.id)


def _class_bases(sources: List[SourceFile]) -> Dict[str, List[str]]:
    bases: Dict[str, List[str]] = {}
    for src in sources:
        for node in src.tree.body:
            if isinstance(node, ast.ClassDef):
                bases[node.name] = [b.id for b in node.bases
                                    if isinstance(b, ast.Name)]
    return bases


_FuncKey = Tuple[str, str]      # (file path, qualname) — unique tree-wide


def _reachable(funcs: Dict[_FuncKey, _FuncInfo],
               bases: Dict[str, List[str]]) -> Dict[_FuncKey, Set[str]]:
    """(path, qualname) -> set of root names it is reachable from.  Keys
    carry the file path because qualnames alone collide across modules
    (two files each defining ``decode_step`` must both be checked)."""
    by_name: Dict[str, List[_FuncKey]] = {}   # module-level fns, bare name
    by_qual: Dict[str, List[_FuncKey]] = {}   # every def, by qualname
    for key, info in funcs.items():
        by_qual.setdefault(info.qualname, []).append(key)
        if info.cls is None:
            by_name.setdefault(info.node.name, []).append(key)

    def method_on(cls: str, name: str) -> List[_FuncKey]:
        seen: Set[str] = set()
        queue = [cls]
        while queue:
            c = queue.pop(0)
            if c in seen:
                continue
            seen.add(c)
            hits = by_qual.get(f"{c}.{name}")
            if hits:
                # same-named classes in different files over-approximate
                # on purpose: better a spurious hot tag than a silent miss
                return list(hits)
            queue.extend(bases.get(c, []))
        return []

    roots = [k for k, info in funcs.items() if info.node.name in HOT_ROOTS]
    tag: Dict[_FuncKey, Set[str]] = {}
    for root in roots:
        label = funcs[root].node.name
        stack = [root]
        while stack:
            key = stack.pop()
            if label in tag.setdefault(key, set()):
                continue
            tag[key].add(label)
            info = funcs[key]
            nxt: List[_FuncKey] = []
            if info.cls:
                for m in info.self_calls:
                    nxt.extend(method_on(info.cls, m))
            for n in info.name_calls:
                # bare-name calls: module-level functions only (methods
                # need a receiver), matched across the whole scanned tree.
                nxt.extend(by_name.get(n, ()))
            stack.extend(nxt)
    return tag


def _check_host_syncs(sources: List[SourceFile]) -> List[Finding]:
    funcs: Dict[_FuncKey, _FuncInfo] = {}
    for src in sources:
        for qual, cls, node in func_defs(src.tree):
            funcs[(src.path, qual)] = _FuncInfo(src, qual, cls, node)
    tag = _reachable(funcs, _class_bases(sources))
    findings: List[Finding] = []
    for key, roots in sorted(tag.items()):
        info = funcs[key]
        # Nodes lexically inside a loop/comprehension (excluding nested
        # defs, whose bodies get their own walk if they are hot-reachable):
        # a sync there stalls once per iteration and is reported under the
        # stricter HOST_SYNC_LOOP rule.
        in_loop: Set[int] = set()
        for sub in ast.walk(info.node):
            if isinstance(sub, _LOOP_NODES):
                for inner in ast.walk(sub):
                    in_loop.add(id(inner))
        for node in ast.walk(info.node):
            if isinstance(node, ast.Call):
                what = _is_host_sync(node)
                if what:
                    looped = id(node) in in_loop
                    findings.append(Finding(
                        HOST_SYNC_LOOP if looped else HOST_SYNC,
                        info.src.path, node.lineno, info.qualname,
                        f"host sync {what} "
                        f"{'inside a loop ' if looped else ''}on hot path "
                        f"(reachable from: {', '.join(sorted(roots))})"))
    return findings


def _check_builders(sources: List[SourceFile]) -> List[Finding]:
    findings: List[Finding] = []
    for src in sources:
        for qual, _cls, node in func_defs(src.tree):
            if not node.name.lstrip("_").startswith("make_"):
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)) \
                        or sub is node:
                    continue
                for call in ast.walk(sub):
                    if isinstance(call, ast.Call):
                        what = _is_impure(call)
                        if what:
                            findings.append(Finding(
                                IMPURE_BUILDER, src.path, call.lineno,
                                f"{qual}.{sub.name}",
                                f"impure call {what} inside a jitted "
                                f"program builder: the traced value is "
                                f"frozen at compile time"))
    return findings


def _check_kernels(sources: List[SourceFile]) -> List[Finding]:
    findings: List[Finding] = []
    for src in sources:
        parts = src.path.split("/")
        if len(parts) < 3 or parts[-1] != "ops.py" \
                or "kernels" != parts[-3]:
            continue
        supported = None
        for node in src.tree.body:
            if isinstance(node, ast.FunctionDef) and \
                    node.name == "supported":
                supported = node
                break
        if supported is None:
            findings.append(Finding(
                KERNEL_GUARD, src.path, 1, "<module>",
                "kernel ops module has no supported() gate: callers "
                "cannot check Pallas block-shape constraints before launch"))
            continue
        has_check = any(
            (isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mod))
            or (isinstance(n, ast.Compare)
                and any(isinstance(op, ast.LtE) for op in n.ops))
            for n in ast.walk(supported))
        if not has_check:
            findings.append(Finding(
                KERNEL_GUARD, src.path, supported.lineno,
                "supported",
                "supported() has no '%' block-shape divisibility check "
                "or '<=' size bound"))
    return findings


def run(sources: List[SourceFile]) -> List[Finding]:
    findings: List[Finding] = []
    findings.extend(_check_host_syncs(sources))
    findings.extend(_check_builders(sources))
    findings.extend(_check_kernels(sources))
    return findings
