"""Reduce a profiler trace (``.xplane.pb``) of the measured window to
device busy and idle time, device time by operation, and the device's
idle time labelled by the host span open during it.

Device planes are those named ``/device:TPU:<i>``.  An operation is an
event on a device plane's ``XLA Ops`` line, a program's run an event
on its ``XLA Modules`` line.  Busy time is the union of both kinds of
interval, averaged over the devices that ran any: the profiler drops
operation events when a program runs millions of them (a 38,000-level
scan), and a program's run covers its operations.  (On
the v5e trace these events carry no named-scope stat, only the HLO
instruction, so time by ``jax.named_scope`` is not read here.)  Host
spans are the ``TraceAnnotation`` events on a host plane whose names
start with one of ``SPAN_PREFIXES``: the program's ``babble_flush_*``
regions and the benchmark's own ``bench_*`` spans.  All times are on
the profiler's one clock; the window starts at the first operation or
span it holds."""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

SPAN_PREFIXES = ("babble_", "bench_")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: gaps shorter than this (ns) are the ordinary seams between
#: back-to-back operations, not idle time anyone can act on
MIN_GAP_NS = 10_000


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float,
         min_ns: float = MIN_GAP_NS) -> List[Tuple[float, float]]:
    """Idle intervals of [lo, hi] outside the (merged) busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s - t >= min_ns:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if hi - t >= min_ns:
        out.append((t, hi))
    return out


def label_gap(gap: Tuple[float, float],
              spans: Sequence[Tuple[float, float, str]]) -> str:
    """The host span that covers most of the gap ('no span' if none)."""
    best, best_cover = "no span", 0.0
    for s, e, name in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def op_name(name: str) -> str:
    """An operation's short name: the HLO instruction's name, without
    the text of its shapes and operands that the trace carries."""
    return name.split(" = ", 1)[0].lstrip("%")


def read(path: str):
    """Per device plane, its operations and its programs' runs as
    (start_ns, end_ns, name), and host spans as (start_ns, end_ns,
    name)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {
                kind: [(ev.start_ns, ev.start_ns + ev.duration_ns,
                        op_name(ev.name))
                       for line in plane.lines if line.name == name
                       for ev in line.events]
                for kind, name in (("ops", OPS_LINE),
                                   ("modules", MODULES_LINE))}
        elif plane.name.startswith("/host:"):
            spans.extend(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for line in plane.lines for ev in line.events
                if ev.name.startswith(SPAN_PREFIXES))
    return devices, spans


def reduce(trace_dir: str, window_s: float) -> dict:
    """Busy seconds, the top operations and the idle time by host span,
    each averaged over the devices that ran operations in the window."""
    devices, spans = read(find_xplane(trace_dir))
    used = {k: v["ops"] + v["modules"] for k, v in devices.items()
            if v["ops"] or v["modules"]}
    if not used:
        raise RuntimeError("the trace holds no device operation")
    ops_of = {k: devices[k]["ops"] for k in used}
    lo = min([s for evs in used.values() for s, _, _ in evs]
             + [s for s, _, _ in spans])
    hi = lo + window_s * 1e9
    busy_ns = ops_busy_ns = 0.0
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for dev, evs in used.items():
        merged = union([(s, e) for s, e, _ in evs])
        busy_ns += sum(e - s for s, e in merged)
        ops_busy_ns += sum(e - s for s, e in union(
            [(s, e) for s, e, _ in ops_of[dev]]))
        for s, e, name in ops_of[dev]:
            ops[name] = ops.get(name, 0.0) + (e - s)
        for g in gaps(merged, lo, max(hi, merged[-1][1])):
            lab = label_gap(g, spans)
            idle[lab] = idle.get(lab, 0.0) + (g[1] - g[0])
    k = len(used)
    top = sorted(ops.items(), key=lambda x: -x[1])[:10]
    top_idle = sorted(idle.items(), key=lambda x: -x[1])[:10]
    return {
        "devices": k,
        "busy_s": busy_ns / k / 1e9,
        # the operations' own union and count, to see what the profiler
        # dropped
        "ops_busy_s": ops_busy_ns / k / 1e9,
        "op_events": sum(len(v) for v in ops_of.values()),
        "window_s": window_s,
        "top_ops": [[n, v / k / 1e9] for n, v in top],
        "top_idle": [[n, v / k / 1e9] for n, v in top_idle],
    }
