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


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as
    {"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns], ...]}]}]}, keeping the device planes' op line and every host
    event that is one of the harness's spans, and the collectives of the
    devices' asynchronous line."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    spans = set(SPAN_STATES) | {SPAN_WINDOW}
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE):
                continue
            evs = [[short_name(ev.name) if device else ev.name,
                    float(ev.start_ns), float(ev.duration_ns)]
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


def _self_times(events) -> Dict[str, float]:
    """Sum of self time by name over one line's (nested) events."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
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
    ops: [[name, s]] by self time (mean over devices), collective_s
    (union of the collectives' intervals, mean), gaps: {wait, train,
    other} seconds (mean)}. Seconds."""
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
    ops: Dict[str, float] = {}
    coll = 0.0
    coll_events = 0
    gaps = {st: 0.0 for st in states}
    gaps["other"] = 0.0
    for evs, async_evs in zip(dev_lines, async_lines):
        inside = [e for e in evs if e[1] + e[2] > lo and e[1] < hi]
        coll_iv = [(s, s + d) for name, s, d in inside + async_evs
                   if is_collective(name)]
        coll_events += len(coll_iv)
        coll += sum(e - s for s, e in _union(_clip(coll_iv, lo, hi))
                    ) / 1e9 / n
        busy = _union(_clip([(s, s + d) for _, s, d in inside], lo, hi))
        busy_ns = sum(e - s for s, e in busy)
        busy_each.append(busy_ns / 1e9)
        for name, ns in _self_times(inside).items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9 / n
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
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "busy_s": sum(busy_each) / n,
        "busy_s_each": busy_each,
        "ops": sorted(([k, v] for k, v in ops.items()),
                      key=lambda kv: -kv[1]),
        "collective_s": coll,
        "collective_events": coll_events,
        "gaps": gaps,
    }
