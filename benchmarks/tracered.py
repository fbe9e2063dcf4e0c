"""Reduction of a profiler trace to the numbers the per-layer metrics
read. ``load`` turns an ``.xplane.pb`` into plain lists (the form the test
keeps a small recorded trace in); ``reduce`` is pure.

Device time comes from the ``XLA Ops`` line of each ``/device:TPU:n``
plane. Events nest there (a ``while`` spans its body), so busy time is
the union of the intervals and an operation's time is its self time: its
duration less that of the events inside it. A collective's time is the
union of its events on that line and of its start..done spans on the
``Async XLA Ops`` line (an asynchronous collective shows on ``XLA Ops``
only as its short start and done). The host's spans
(``jax.profiler.TraceAnnotation`` around the harness's own calls) are on
the same clock; an idle gap of the device is given to the span the host
was in.

Every device op keeps the program's own name for it: the innermost
``pbox.*`` ``jax.named_scope`` of its name stack (``scope_of``), which
lasts from compile to compile where ``fusion.251`` does not. This jaxlib
writes the stack as the stat ``tf_op`` of the op's *event metadata*, which
``jax.profiler.ProfileData`` does not surface, so ``op_scopes`` reads the
planes' metadata tables from the file's protobuf wire format (a copy of
``paddlebox_tpu/obs/xplane._op_names``: the yardstick imports nothing of
the program).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"   # start..done spans of asynchronous ops
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "collective-broadcast")
SPAN_WINDOW = "bench.traced"
SPAN_STATES = {"bench.wait": "wait", "bench.train": "train"}
#: the event-metadata stat that carries the HLO op_name (the name stack)
SCOPE_STAT = "tf_op"
#: the scope of an op whose name stack holds no ``pbox.*`` name
OTHER = "other"


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as
    {"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns], ...]}]}]}, keeping the device planes' op line and every host
    event that is one of the harness's spans, and the collectives of the
    devices' asynchronous line. An event of a device's op line has a
    fourth field, its scope (``scope_of`` its name stack)."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    path = max(paths, key=os.path.getmtime)
    scopes = op_scopes(path)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = set(SPAN_STATES) | {SPAN_WINDOW}
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        of = scopes.get(plane.name, {})
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE):
                continue
            scoped = device and line.name == OPS_LINE
            evs = [[short_name(ev.name) if device else ev.name,
                    float(ev.start_ns), float(ev.duration_ns)]
                   + ([of.get(ev.name, OTHER)] if scoped else [])
                   for ev in line.events
                   if (device or ev.name in spans)
                   and (line.name != ASYNC_LINE
                        or is_collective(short_name(ev.name)))]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


_HLO = re.compile(r"^%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])")


def short_name(name: str) -> str:
    """An HLO instruction's text (the profiler's name of a TPU op) cut to
    ``<instruction> <dtype[shape]>`` of its (first) result; other names
    stay as they are."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name.lstrip("%")


_SCOPE = re.compile(r"pbox\.[A-Za-z0-9_]+")


def scope_of(name_stack: str) -> str:
    """The ``pbox.*`` scope of one op from its name stack (such as
    ``jit(run)/while/body/transpose(jvp(pbox.pull))/gather``): the
    innermost catalog name, ``.bwd`` appended when its component of the
    stack is a ``transpose(...)`` (the backward of a differentiated
    scope); ``other`` when the stack holds none."""
    for part in reversed(name_stack.split("/")):
        m = _SCOPE.search(part)
        if m:
            bwd = part.startswith("transpose(")
            return m.group(0) + (".bwd" if bwd else "")
    return OTHER


def _varint(buf, i: int) -> Tuple[int, int]:
    """(value, next index) of the varint at ``buf[i]``."""
    val = shift = 0
    while True:
        byte = buf[i]
        i += 1
        val |= (byte & 0x7F) << shift
        if byte < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: an int
    for a varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield key >> 3, 0, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, 2, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")


def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {event name: scope}} from the ``tf_op`` stats of
    each device plane's event metadata. The fields read, by their
    numbers in ``xplane.proto``: XSpace.planes 1; XPlane.name 2,
    .event_metadata 4 and .stat_metadata 5 (maps: key 1, value 2);
    XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
    XStat.metadata_id 1, .str_value 5, .ref_value 7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for no, wire, plane in _fields(space):
        if no != 1 or wire != 2:
            continue
        name, events, stat_names = "", [], {}
        for no, wire, val in _fields(plane):
            if no == 2 and wire == 2:
                name = bytes(val).decode()
            elif no == 4 and wire == 2:
                events.append(val)
            elif no == 5 and wire == 2:
                key, meta = 0, None
                for n2, _, v2 in _fields(val):
                    if n2 == 1:
                        key = v2
                    elif n2 == 2:
                        meta = v2
                for n3, w3, v3 in _fields(meta or b""):
                    if n3 == 2 and w3 == 2:
                        stat_names[key] = bytes(v3).decode()
        if not name.startswith(DEVICE_PREFIX):
            continue
        found: Dict[str, str] = {}
        for entry in events:
            for n2, w2, meta in _fields(entry):
                if n2 != 2 or w2 != 2:
                    continue
                ev_name, stack = "", ""
                for n3, w3, v3 in _fields(meta):
                    if n3 == 2 and w3 == 2:
                        ev_name = bytes(v3).decode()
                    elif n3 == 5 and w3 == 2:
                        sid, sval = 0, ""
                        for n4, w4, v4 in _fields(v3):
                            if n4 == 1:
                                sid = v4
                            elif n4 == 5 and w4 == 2:
                                sval = bytes(v4).decode()
                            elif n4 == 7 and w4 == 0:
                                sval = stat_names.get(v4, "")
                        if stat_names.get(sid) == SCOPE_STAT:
                            stack = sval
                if stack:
                    found[ev_name] = scope_of(stack)
        out[name] = found
    return out


def _union(iv: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv
            if min(e, hi) > max(s, lo)]


def _self_times(events) -> List[Tuple[list, float]]:
    """(event, self time in ns) of one line's (nested) events, in the
    order they end."""
    out: List[Tuple[list, float]] = []
    stack: List[list] = []  # [event, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            ev, _, self_ns = stack.pop()
            out.append((ev, max(self_ns, 0.0)))

    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        close(ev[1])
        if stack:
            stack[-1][2] -= ev[2]
        stack.append([ev, ev[1] + ev[2], ev[2]])
    close(float("inf"))
    return out


def _overlap(a, b) -> float:
    """Total overlap of two sorted disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def reduce(trace: dict) -> dict:
    """-> {window_s, devices, busy_s (mean over devices), busy_s_each,
    ops: [[name, s]] by self time (mean over devices), scopes: {scope: s}
    the same self time by the program's ``pbox.*`` scope (``other`` for
    an op with none; sums to what ``ops`` sums to), op_scope: {name: the
    scope most of its self time lies in}, collective_s (union of the
    collectives' intervals, mean), gaps: {wait, train, other} seconds
    (mean)}. Seconds. A device event is [name, start_ns, dur_ns] or, with
    its scope, [name, start_ns, dur_ns, scope]."""
    host_spans: Dict[str, List[Tuple[float, float]]] = {}
    dev_lines, async_lines = [], []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PREFIX):
            by_name = {line["name"]: line["events"]
                       for line in plane["lines"]}
            if OPS_LINE in by_name:
                dev_lines.append(by_name[OPS_LINE])
                async_lines.append(by_name.get(ASYNC_LINE, []))
        else:
            for line in plane["lines"]:
                for name, s, d in line["events"]:
                    if name == SPAN_WINDOW or name in SPAN_STATES:
                        host_spans.setdefault(name, []).append((s, s + d))
    if not dev_lines:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    if SPAN_WINDOW in host_spans:
        lo = min(s for s, _ in host_spans[SPAN_WINDOW])
        hi = max(e for _, e in host_spans[SPAN_WINDOW])
    else:
        lo = min(e[1] for evs in dev_lines for e in evs)
        hi = max(e[1] + e[2] for evs in dev_lines for e in evs)
    states = {st: _union(_clip(host_spans.get(sp, []), lo, hi))
              for sp, st in SPAN_STATES.items()}
    n = len(dev_lines)
    busy_each = []
    self_s: Dict[Tuple[str, str], float] = {}   # (op, scope) -> seconds
    coll = 0.0
    coll_events = 0
    gaps = {st: 0.0 for st in states}
    gaps["other"] = 0.0
    for evs, async_evs in zip(dev_lines, async_lines):
        inside = [e for e in evs if e[1] + e[2] > lo and e[1] < hi]
        coll_iv = [(e[1], e[1] + e[2]) for e in inside + async_evs
                   if is_collective(e[0])]
        coll_events += len(coll_iv)
        coll += sum(e - s for s, e in _union(_clip(coll_iv, lo, hi))
                    ) / 1e9 / n
        busy = _union(_clip([(e[1], e[1] + e[2]) for e in inside], lo, hi))
        busy_ns = sum(e - s for s, e in busy)
        busy_each.append(busy_ns / 1e9)
        self_ns: Dict[Tuple[str, str], float] = {}
        for ev, ns in _self_times(inside):
            key = (ev[0], ev[3] if len(ev) > 3 else OTHER)
            self_ns[key] = self_ns.get(key, 0.0) + ns
        for key, ns in self_ns.items():
            self_s[key] = self_s.get(key, 0.0) + ns / 1e9 / n
        idle = []
        t = lo
        for s, e in busy:
            if s > t:
                idle.append((t, s))
            t = e
        if hi > t:
            idle.append((t, hi))
        idle_ns = sum(e - s for s, e in idle)
        given = 0.0
        for st, iv in states.items():
            o = _overlap(idle, iv)
            gaps[st] += o / 1e9 / n
            given += o
        gaps["other"] += max(idle_ns - given, 0.0) / 1e9 / n
    ops: Dict[str, float] = {}
    scopes: Dict[str, float] = {}
    op_scope: Dict[str, Tuple[str, float]] = {}
    for (name, scope), sec in self_s.items():
        ops[name] = ops.get(name, 0.0) + sec
        scopes[scope] = scopes.get(scope, 0.0) + sec
        if name not in op_scope or sec > op_scope[name][1]:
            op_scope[name] = (scope, sec)
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "busy_s": sum(busy_each) / n,
        "busy_s_each": busy_each,
        "ops": sorted(([k, v] for k, v in ops.items()),
                      key=lambda kv: -kv[1]),
        "scopes": scopes,
        "op_scope": {name: scope for name, (scope, _) in op_scope.items()},
        "collective_s": coll,
        "collective_events": coll_events,
        "gaps": gaps,
    }


def scoped_ops(red: dict, top: int = 10) -> List[list]:
    """The ``top`` ops of ``reduce``'s ``ops``, same order and seconds,
    each named ``<scope>/<short op name>``: the scope says what the op
    is for, whatever number the compiler gave its fusion this time."""
    of = red.get("op_scope", {})
    return [[f"{of.get(name, OTHER)}/{name}", sec]
            for name, sec in red["ops"][:top]]


def scope_ms_per_batch(trace, names: Sequence[str]):
    """Device self time a traced batch, in ms, of the scopes ``names``
    and their ``.bwd`` halves; nothing where the trace holds none of
    them (a reader then leaves its metric out of the line)."""
    if not trace or trace.get("batches", 0) <= 0:
        return None
    scopes = trace.get("scopes") or {}
    secs = [scopes[k] for n in names for k in (n, n + ".bwd") if k in scopes]
    return 1e3 * sum(secs) / trace["batches"] if secs else None
