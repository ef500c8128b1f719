"""What ``xplane.load`` leaves behind, read from the same ``.xplane.pb``: the
scope path of every device operation, and the host plane's events with
their stats.

On the TPU an ``XLA Ops`` event carries only its timing; what the program
said about the operation stands on the event's *metadata*: the stat ``tf_op``
holds the ``jax.named_scope`` path down to the primitive
(``jit(step)/transpose(jvp(mlp))/dot_general:``), and a Pallas kernel's
``name`` is both its HLO instruction's name (``mha_packed_bwd.20``) and a
component of that path. ``jax.profiler.ProfileData`` shows no metadata stat,
so this module reads the protocol buffer's wire format itself (schema:
``tsl/profiler/protobuf/xplane.proto``; only the fields below are followed).

``load`` gives plain lists, as ``xplane.load`` does, so the reductions are
tested on a small recorded trace kept as JSON (``write`` / ``read``):

    device plane: {"name", "lines": [{"name": "XLA Ops", "events":
                   [[short_name, start_ns, dur_ns, scope_path]]}]}
    host plane:   {"name", "lines": [{"name", "events":
                   [[name, start_ns, dur_ns, stats]]}]}

The program's ``OpProfiler`` spans are ``TraceAnnotation`` events of the
host plane, on the same clock as the device operations.
"""
from __future__ import annotations

import json
import os
import re
import struct
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from benchmarks.lib import xplane

# where ``run.py`` has every runner put the traced window
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".cache", "run", "trace")
SCOPE_STAT = "tf_op"
_IDENT = re.compile(r"[A-Za-z_][\w.\-]*")


# ---------------------------------------------------------------- wire format
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[tuple]:
    """(field number, wire type, value) of one message: an int for varint
    and fixed fields, ``(start, end)`` for a length-delimited one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = (i, i + size)
            i += size
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, span, stat_names):
    """One XStat as (name, value); a ``ref_value`` names a stat metadata."""
    name = value = None
    for no, wire, v in _fields(buf, *span):
        if no == 1:
            name = stat_names.get(v, str(v))
        elif no == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = _signed(v)
        elif no == 5:
            value = _text(buf, v)
        elif no == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf, span):
    key = value = None
    for no, _wire, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _plane(buf, span, host_names):
    name, lines, event_meta, stat_meta = "", [], [], []
    for no, _wire, v in _fields(buf, *span):
        if no == 2:
            name = _text(buf, v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            event_meta.append(v)
        elif no == 5:
            stat_meta.append(v)
    device = bool(xplane.DEVICE_PLANE.match(name))
    if not device and name != "/host:CPU":
        return None
    stat_names = {}
    for entry in stat_meta:
        key, value = _map_entry(buf, entry)
        for no, _wire, v in _fields(buf, *value):
            if no == 2:
                stat_names[key] = _text(buf, v)
    meta = {}     # metadata id -> (event name, scope path)
    for entry in event_meta:
        key, value = _map_entry(buf, entry)
        ev_name, scope = "", ""
        for no, _wire, v in _fields(buf, *value):
            if no == 2:
                ev_name = _text(buf, v)
            elif no == 5 and device:
                stat = _stat(buf, v, stat_names)
                if stat[0] == SCOPE_STAT:
                    scope = stat[1]
        meta[key] = (ev_name, scope)
    out = []
    for span_ in lines:
        line_name, t0_ns, events = "", 0, []
        for no, _wire, v in _fields(buf, *span_):
            if no == 2:
                line_name = _text(buf, v)
            elif no == 3:
                t0_ns = v
            elif no == 4:
                events.append(v)
        if device and line_name != xplane.OPS_LINE:
            continue
        kept = []
        for ev in events:
            mid = offset_ps = dur_ps = 0
            stats = []
            for no, _wire, v in _fields(buf, *ev):
                if no == 1:
                    mid = v
                elif no == 2:
                    offset_ps = v
                elif no == 3:
                    dur_ps = v
                elif no == 4 and not device:
                    stats.append(v)
            ev_name, scope = meta.get(mid, ("", ""))
            start = t0_ns + offset_ps / 1e3
            if device:
                kept.append([xplane.short_name(ev_name), start, dur_ps / 1e3,
                             scope])
            elif host_names is None or host_names.search(ev_name):
                kept.append([ev_name, start, dur_ps / 1e3,
                             dict(_stat(buf, s, stat_names) for s in stats)])
        if kept:
            out.append({"name": line_name, "events": kept})
    return {"name": name, "lines": out} if out else None


def load(path: str, host_names: Optional[str] = None) -> List[dict]:
    """The device planes' operations with their scope paths and the host
    plane's events with their stats. ``host_names`` (a regular expression,
    searched) keeps only the host events it matches."""
    with open(path, "rb") as f:
        buf = f.read()
    pattern = re.compile(host_names) if host_names else None
    planes = []
    for no, _wire, v in _fields(buf, 0, len(buf)):
        if no == 1:
            plane = _plane(buf, v, pattern)
            if plane is not None:
                planes.append(plane)
    return planes


def write(planes: List[dict], path: str) -> None:
    with open(path, "w") as f:
        json.dump(planes, f)


def read(path: str) -> List[dict]:
    with open(path) as f:
        return json.load(f)


_traced: Dict[tuple, List[dict]] = {}


def traced() -> Optional[List[dict]]:
    """The planes of the traced window this run took, or ``None`` where the
    run took none. Loaded once however many readers ask."""
    path = xplane.newest_xplane(TRACE_DIR)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _traced:
        _traced.clear()
        _traced[key] = load(path)
    return _traced[key]


# ----------------------------------------------------------------- reductions
def names_in(scope_path: str) -> List[str]:
    """The names a scope path holds, outermost first:
    ``jit(step)/transpose(jvp(mlp))/dot_general:`` gives ``jit``, ``step``,
    ``transpose``, ``jvp``, ``mlp``, ``dot_general``."""
    return _IDENT.findall(scope_path)


def innermost(scope_path: str, vocabulary: Iterable[str]) -> Optional[str]:
    """The innermost name of the path that the vocabulary has."""
    for name in reversed(names_in(scope_path)):
        if name in vocabulary:
            return name
    return None


def scope_seconds(planes: List[dict], vocabulary: Iterable[str]
                  ) -> Dict[Optional[str], float]:
    """Device time by scope, averaged over the chips: every operation under
    the innermost name of ``vocabulary`` in its path, or under ``None`` where
    it has none of them, so the values add up to the devices' busy time."""
    vocabulary = frozenset(vocabulary)
    per_device = xplane.device_ops(planes)
    by_scope: Dict[Optional[str], float] = {}
    memo: Dict[str, Optional[str]] = {}
    for events in per_device.values():
        for _name, _start, dur, scope_path in events:
            if scope_path not in memo:
                memo[scope_path] = innermost(scope_path, vocabulary)
            key = memo[scope_path]
            by_scope[key] = by_scope.get(key, 0.0) + dur
    n = max(1, len(per_device))
    return {k: v / n / 1e9 for k, v in by_scope.items()}


def host_intervals(planes: List[dict], name: str) -> List[Tuple[float, float]]:
    """(start_ns, end_ns) of the host's own time in every host-plane event
    called ``name``: the event less the events that give it as their
    ``parent``, so a span and its children are never both charged."""
    mine, children = [], []
    for plane in planes:
        if xplane.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for ev, start, dur, stats in line["events"]:
                if ev == name:
                    mine.append((start, start + dur))
                elif stats.get("parent") == name:
                    children.append((start, start + dur))
    children.sort()
    out = []
    for a, b in sorted(mine):
        for ca, cb in children:
            if ca >= b:
                break
            if cb <= a:
                continue
            if ca > a:
                out.append((a, ca))
            a = max(a, cb)
        if b > a:
            out.append((a, b))
    return out


def idle_under(planes: List[dict], name: str) -> Optional[Tuple[float, float]]:
    """(seconds the first device is idle during the host's own time in the
    events called ``name``, seconds of the traced window); the window runs
    from the first to the last device operation, as in ``xplane.reduce``."""
    per_device = xplane.device_ops(planes)
    if not per_device or not any(per_device.values()):
        return None
    t0 = min(ev[0][1] for ev in per_device.values() if ev)
    t1 = max(max(e[1] + e[2] for e in ev) for ev in per_device.values() if ev)
    first = next(iter(per_device.values()))
    edges = [t0] + [x for ab in xplane.busy_intervals(first, t0, t1)
                    for x in ab] + [t1]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    under = 0.0
    for s, e in host_intervals(planes, name):
        for a, b in idle:
            if a >= e:
                break
            under += max(0.0, min(b, e) - max(a, s))
    return under / 1e9, (t1 - t0) / 1e9
