"""Reduce a ``jax.profiler`` trace by the program's own names.

Two tables for an operator with a profile open (``scripts/
telemetry_report.py --xplane DIR`` prints them):

(a) **device self time by ``pbox.*`` scope** — the ``jax.named_scope``
    catalog of ``obs/trace`` (``SCOPE_*``) that the train step puts on
    its ops. Device time comes from the ``XLA Ops`` line of each
    ``/device:TPU:n`` plane; events nest there (a ``while`` spans its
    body), so an op's time is its self time. An op's scope is read from
    the HLO ``op_name`` — JAX's name stack, e.g.
    ``jit(run)/while/body/transpose(jvp(pbox.pull))/gather:``. This
    jaxlib (0.9.0, libtpu 0.0.34) writes it as the stat ``tf_op`` of the
    op's *event metadata* (found on the chip, PR 26), beside
    ``hlo_category``, ``flops``, ``bytes_accessed`` and ``source``;
    ``jax.profiler.ProfileData`` surfaces an event's own stats only
    (offset and duration), so ``load`` reads the planes'
    ``event_metadata`` tables from the file's protobuf wire format
    itself (``_op_names``; no protobuf package, no tensorflow). The
    innermost ``pbox.<name>`` of the stack is the op's scope; inside a
    ``transpose(...)`` (the backward of a differentiated scope) it folds
    to ``pbox.<name>.bwd``. Ops with no scope are ``other``.
(b) **each device's idle gaps by the innermost program span the ``main``
    lane was in** — the ``obs/trace.span`` annotations (``pass.train``,
    ``pass.mark_trained``, ...) that land in the same xplane on the
    device trace's own clock, with ``(outside)`` for idle time in no
    span.

``load`` turns an ``.xplane.pb`` into plain lists (the form the test
keeps a small recorded trace in); ``reduce`` is pure. The program may
not import ``benchmarks/``; this reducer is the one the benchmark's
``tracered`` can later be pointed at.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

from paddlebox_tpu.obs.trace import LANE_MAIN

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: the event-metadata stat that carries the HLO op_name (the name stack)
SCOPE_STAT = "tf_op"
OTHER = "other"
OUTSIDE = "(outside)"

_SCOPE = re.compile(r"pbox\.[A-Za-z0-9_]+")
_HLO = re.compile(r"^%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])")


def scope_of(name_stack: str) -> str:
    """The ``pbox.*`` scope of one op from its name stack: the innermost
    catalog name, ``.bwd`` appended when its component of the stack is a
    ``transpose(...)``; ``other`` when the stack holds none."""
    for part in reversed(name_stack.split("/")):
        m = _SCOPE.search(part)
        if m:
            bwd = part.startswith("transpose(")
            return m.group(0) + (".bwd" if bwd else "")
    return OTHER


def short_name(name: str) -> str:
    """An HLO instruction's text cut to ``<instruction> <dtype[shape]>``
    of its (first) result; other names stay as they are."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name.lstrip("%")


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


def _op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {event name: name stack}} from the ``tf_op`` stats
    of each device plane's event metadata. The fields read, by their
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
        stacks: Dict[str, str] = {}
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
                    stacks[ev_name] = stack
        out[name] = stacks
    return out


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as
    ``{"devices": [{"name", "ops": [[name, name_stack, start_ns,
    dur_ns], ...]}], "host": [[name, lane, start_ns, dur_ns], ...],
    "scope_stat": "tf_op" where any op had a name stack, else None}``:
    the op line of every device plane and every host event that carries
    a ``lane`` stat (the mark of an ``obs/trace`` span)."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    path = max(paths, key=os.path.getmtime)
    stacks = _op_names(path)
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            of = stacks.get(plane.name, {})
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices.append({"name": plane.name, "ops": [
                    [short_name(ev.name), of.get(ev.name, ""),
                     float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events]})
        else:
            for line in plane.lines:
                for ev in line.events:
                    lane = dict(ev.stats).get("lane")
                    if lane is not None:
                        host.append([ev.name, str(lane),
                                     float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"devices": devices, "host": host,
            "scope_stat": SCOPE_STAT if any(
                op[1] for d in devices for op in d["ops"]) else None}


def _union(iv: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(events) -> List[Tuple[int, float]]:
    """Self time of each of one line's (nested) events, as
    (index into ``events``, ns)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][-2], -events[i][-1]))
    out: List[Tuple[int, float]] = []
    stack: List[list] = []  # [index, end, self_ns]
    for i in order:
        s, d = events[i][-2], events[i][-1]
        while stack and stack[-1][1] <= s:
            j, _, self_ns = stack.pop()
            out.append((j, max(self_ns, 0.0)))
        if stack:
            stack[-1][2] -= d
        stack.append([i, s + d, d])
    out.extend((j, max(self_ns, 0.0)) for j, _, self_ns in stack)
    return out


def innermost_segments(spans) -> List[Tuple[float, float, str]]:
    """Nested spans ``[name, start, dur]`` of one thread flattened to
    disjoint ``(start, end, name)`` pieces, each piece carrying the
    innermost span that covers it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[list] = []  # [name, end]
    t = 0.0

    def emit(upto: float) -> None:
        nonlocal t
        if stack and upto > t:
            out.append((t, upto, stack[-1][0]))
        t = max(t, upto)

    for name, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        t = max(t, s)
        stack.append([name, s + d])
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def reduce(trace: dict, lane: str = LANE_MAIN) -> dict:
    """-> ``{window_s, devices, busy_s, idle_s, scopes: [[scope, s]],
    other_ops: [[op, s]] (the unscoped ops, largest first), gaps:
    [[span, s]], scope_stat}``; seconds, means over the devices, each
    list largest first. The window runs from the first to the last
    instant any device op or ``lane`` span covers."""
    devs = [d["ops"] for d in trace["devices"] if d["ops"]]
    if not devs:
        raise ValueError(f"the trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    spans = [[n, s, d] for n, ln, s, d in trace["host"] if ln == lane]
    starts = [e[2] for ops in devs for e in ops] + [s for _, s, _ in spans]
    ends = ([e[2] + e[3] for ops in devs for e in ops]
            + [s + d for _, s, d in spans])
    lo, hi = min(starts), max(ends)
    segs = innermost_segments(spans)
    n = len(devs)
    scopes: Dict[str, float] = {}
    other: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    busy_s = 0.0
    for ops in devs:
        for i, ns in _self_times(ops):
            sc = scope_of(ops[i][1])
            scopes[sc] = scopes.get(sc, 0.0) + ns / 1e9 / n
            if sc == OTHER:
                other[ops[i][0]] = other.get(ops[i][0], 0.0) + ns / 1e9 / n
        busy = _union([(e[2], e[2] + e[3]) for e in ops])
        busy_s += sum(e - s for s, e in busy) / 1e9 / n
        t = lo
        idle = []
        for s, e in busy:
            if s > t:
                idle.append((t, s))
            t = max(t, e)
        if hi > t:
            idle.append((t, hi))
        for a, b in idle:
            left = b - a
            for s, e, name in segs:
                o = min(b, e) - max(a, s)
                if o > 0:
                    gaps[name] = gaps.get(name, 0.0) + o / 1e9 / n
                    left -= o
            if left > 0:
                gaps[OUTSIDE] = gaps.get(OUTSIDE, 0.0) + left / 1e9 / n

    def ranked(d: Dict[str, float]) -> List[list]:
        return sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])

    window_s = (hi - lo) / 1e9
    return {"window_s": window_s, "devices": n, "busy_s": busy_s,
            "idle_s": window_s - busy_s, "scopes": ranked(scopes),
            "other_ops": ranked(other), "gaps": ranked(gaps),
            "scope_stat": trace.get("scope_stat")}


def render(red: dict, top_other: int = 5) -> str:
    """The two tables as text."""
    busy = max(red["busy_s"], 1e-12)
    idle = max(red["idle_s"], 1e-12)
    lines = [f"xplane: {red['devices']} device(s), window "
             f"{red['window_s']:.4f} s, busy {red['busy_s']:.4f} s, idle "
             f"{red['idle_s']:.4f} s (scopes read from stat "
             f"{red['scope_stat']!r})",
             "", "device self time by scope", "scope                 "
             "       s   share"]
    for name, sec in red["scopes"]:
        lines.append(f"{name:<24}{sec:>9.4f}{100 * sec / busy:>7.1f}%")
    if red["other_ops"]:
        lines.append("  largest unscoped ops: " + ", ".join(
            f"{n} {s:.4f}" for n, s in red["other_ops"][:top_other]))
    lines += ["", f"idle gaps by innermost {LANE_MAIN}-lane span",
              "span                         s   share"]
    for name, sec in red["gaps"]:
        lines.append(f"{name:<24}{sec:>9.4f}{100 * sec / idle:>7.1f}%")
    return "\n".join(lines)


def report(trace_dir: str, lane: str = LANE_MAIN) -> str:
    """Load, reduce and render the newest trace under ``trace_dir``."""
    return render(reduce(load(trace_dir), lane))
