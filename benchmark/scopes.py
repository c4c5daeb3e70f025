"""Device time by phase: the program's ``named_scope`` paths joined with
a profiler trace through the compiled program's text.

The v5e trace's operations carry only their HLO instruction's name, not
its scope.  The compiled program's text (``compiled.as_text()``) names
the same instructions and keeps each one's ``op_name`` metadata, where
the scopes the program opens (``babble_ingest`` with its ``la`` / ``fd``
/ ``rounds`` children, ``babble_fame``, ``babble_order``) appear as path
components.  ``scope_map`` reads that text into an instruction -> scope
path map; ``reduce_scopes`` sums a trace's operations by it, as the
union of each path's intervals per device (a ``while`` and the body
operations it holds count once), averaged over the devices as
``trace.reduce`` does."""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional

from benchmark import trace

#: the children of a ``babble_*`` scope that the map keeps
SUBSCOPES = ("la", "fd", "rounds")
UNSCOPED = "unscoped"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(
    r"\b(?:calls|to_apply|condition|body|true_computation|false_computation)"
    r"=%([\w.\-]+)")
_CALLEES = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")


def scope_of(op_name: str) -> Optional[str]:
    """The scope path in one ``op_name``: its first ``babble_*``
    component and the ``SUBSCOPES`` child right under it, if any."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if part.startswith("babble_"):
            if i + 1 < len(parts) and parts[i + 1] in SUBSCOPES:
                return part + "/" + parts[i + 1]
            return part
    return None


def _common(paths: Iterable[Optional[str]]) -> Optional[str]:
    """The longest scope path that all of ``paths`` (None skipped)
    share, or None."""
    split = [p.split("/") for p in paths if p]
    if not split:
        return None
    out = []
    for parts in zip(*split):
        if any(x != parts[0] for x in parts):
            break
        out.append(parts[0])
    return "/".join(out) or None


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope path, for every instruction of a
    compiled program's text.

    An instruction takes the scope of its own ``op_name``.  One with
    none takes the common scope of the scoped instructions inside the
    computations it calls (a fusion's fused computation), else the
    scope of the instructions that call the computation it sits in (a
    while body's operations take the ``while``'s).  Where scopes
    disagree, the longest path they share is taken (``babble_ingest``
    for a fusion of ``la`` and ``rounds`` work); what is left is
    ``UNSCOPED``."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, List[str]] = {}
    home: Dict[str, str] = {}
    body: Dict[str, List[str]] = {}
    callers: Dict[str, List[str]] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            body[comp] = []
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else None
        callees = _CALLEE.findall(line) + [
            c.strip().lstrip("%") for group in _CALLEES.findall(line)
            for c in group.split(",") if c.strip()]
        calls[name] = callees
        home[name] = comp
        body[comp].append(name)
        for c in callees:
            callers.setdefault(c, []).append(name)

    inner: Dict[str, Optional[str]] = {}

    def from_inside(name: str) -> Optional[str]:
        if name not in inner:
            inner[name] = own[name] or _common(
                from_inside(i) for c in calls[name] for i in body.get(c, ()))
        return inner[name]

    outer: Dict[str, Optional[str]] = {}

    def from_outside(comp: str) -> Optional[str]:
        if comp not in outer:
            outer[comp] = _common(resolve(c) for c in callers.get(comp, ()))
        return outer[comp]

    def resolve(name: str) -> Optional[str]:
        return from_inside(name) or from_outside(home[name])

    return {name: resolve(name) or UNSCOPED for name in own}


def reduce_scopes(trace_dir: str, scopes: Dict[str, str]) -> dict:
    """Seconds of device time per scope path: on each device the union
    of the intervals of the operations that ``scopes`` maps to the path
    (operations it does not name are ``UNSCOPED``), averaged over the
    devices that ran any.  ``ops_busy_s`` is the union of all the
    operations, for a check of the paths' sum."""
    devices, _ = trace.read(trace.find_xplane(trace_dir))
    used = {k: v["ops"] for k, v in devices.items()
            if v["ops"] or v["modules"]}
    if not used:
        raise RuntimeError("the trace holds no device operation")
    per: Dict[str, float] = {}
    ops_busy_ns = 0.0
    for ops in used.values():
        by: Dict[str, list] = {}
        for s, e, name in ops:
            by.setdefault(scopes.get(name, UNSCOPED), []).append((s, e))
        for path, iv in by.items():
            per[path] = per.get(path, 0.0) + sum(
                e - s for s, e in trace.union(iv))
        ops_busy_ns += sum(e - s for s, e in trace.union(
            [(s, e) for s, e, _ in ops]))
    k = len(used)
    return {"devices": k, "ops_busy_s": ops_busy_ns / k / 1e9,
            "scopes": {p: v / k / 1e9 for p, v in sorted(per.items())}}

