"""From the profiler's trace to numbers: device busy and idle time, the
device operations that took most time, and the longest idle gaps labelled by
what the host was doing.

``load`` turns an ``.xplane.pb`` into plain lists (``planes``), and every
reduction works on those lists, so the tests run on a small recorded trace
kept as JSON. A device plane is one whose name matches ``/device:TPU:<n>``;
its operations are the events of the line named ``XLA Ops``, each named by
its HLO instruction and opcode (``short_name``). The host plane
holds the benchmark's own ``TraceAnnotation`` (``bench_window`` with the
``perf_counter`` reading), which gives the offset between the host clock the
program's spans use and the trace's clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
OPS_LINE = "XLA Ops"
ANCHOR = "bench_window"


def short_name(event_name: str) -> str:
    """The TPU trace names an operation by its whole HLO line
    (``%fusion.679 = (f32[768,30522]{...}, ...) fusion(...)``). Kept are
    the instruction's name and its opcode: ``fusion.679 fusion``,
    ``transpose_jvp___.12 custom-call`` (a Pallas kernel)."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name[:120]
    opcode = _OPCODE.search(" " + rest)
    return f"{head.lstrip('%')} {opcode.group(1) if opcode else '?'}"[:120]


def newest_xplane(logdir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str, keep_lines=None) -> List[dict]:
    """Planes as ``{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns, stats]]}]}``. Of the host plane only events named ``ANCHOR`` are
    kept, of device planes the lines in ``keep_lines`` (default: all)."""
    import jax

    planes = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            if device and keep_lines and line.name not in keep_lines:
                continue
            events = []
            for e in line.events:
                if device:
                    events.append([short_name(e.name), float(e.start_ns),
                                   float(e.duration_ns), {}])
                elif e.name == ANCHOR:
                    events.append([e.name, float(e.start_ns),
                                   float(e.duration_ns), dict(e.stats)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def clock_offset_ns(planes: List[dict]) -> Optional[float]:
    """Trace clock minus host ``perf_counter`` clock, from the anchor."""
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, _dur, stats in line["events"]:
                if name == ANCHOR and "pc_ns" in stats:
                    return start - float(stats["pc_ns"])
    return None


def device_ops(planes: List[dict]) -> Dict[str, list]:
    """Per device plane, its operation events sorted by start."""
    out = {}
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out[plane["name"]] = sorted(line["events"],
                                            key=lambda e: e[1])
    return out


def busy_intervals(events, t0: float, t1: float) -> List[Tuple[float, float]]:
    """Union of the events' intervals, clipped to ``[t0, t1]``."""
    merged: List[List[float]] = []
    for _name, start, dur, *_ in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def label_gap(a: float, b: float, host_spans, default: str) -> str:
    """What the host was doing in ``[a, b]`` (trace clock, ns): the label of
    the host span that covers most of it, ``default`` where none covers any."""
    best, best_cover = default, 0.0
    for label, s, e in host_spans:
        cover = min(b, e) - max(a, s)
        if cover > best_cover:
            best, best_cover = label, cover
    return best


def reduce(planes: List[dict], host_spans=(), top: int = 10,
           between: str = "between_spans") -> Optional[dict]:
    """The reduction the readers and ``breakdown`` take.

    ``host_spans`` are ``(label, start_s, end_s)`` on the ``perf_counter``
    clock. The traced window is from the first to the last device operation
    over all device planes; busy time is averaged over the planes. Returns
    ``None`` where the trace has no device operation."""
    per_device = device_ops(planes)
    if not per_device or not any(per_device.values()):
        return None
    t0 = min(ev[0][1] for ev in per_device.values() if ev)
    t1 = max(max(e[1] + e[2] for e in ev) for ev in per_device.values() if ev)
    offset = clock_offset_ns(planes)
    spans_ns = []
    if offset is not None:
        spans_ns = [(label, s * 1e9 + offset, e * 1e9 + offset)
                    for label, s, e in host_spans]
    busy, ops, gaps = [], {}, []
    for i, events in enumerate(per_device.values()):
        merged = busy_intervals(events, t0, t1)
        busy.append(sum(b - a for a, b in merged))
        for name, _start, dur, *_ in events:
            ops[name] = ops.get(name, 0.0) + dur
        if i == 0:   # gaps of one device: the others run the same program
            edges = [t0] + [x for ab in merged for x in ab] + [t1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    label = (label_gap(a, b, spans_ns, between)
                             if offset is not None else "unattributed")
                    gaps.append((label, b - a))
    n = len(per_device)
    by_label: Dict[str, float] = {}
    for label, dur in gaps:
        by_label[label] = by_label.get(label, 0.0) + dur
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "devices": n,
        "ops_s": {k: v / n / 1e9 for k, v in ops.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            gaps, key=lambda kv: -kv[1])[:top]],
        "idle_by_label_s": {k: v / 1e9 for k, v in by_label.items()},
        "clock_offset_known": offset is not None,
    }
