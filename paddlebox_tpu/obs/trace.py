"""Causal span tracing over the async pass pipeline (ISSUE 10).

PRs 4-8 turned every pass into a 4-deep concurrent machine — preloader
worker builds k+2, stage queue wires k+1, main thread trains k, the
epilogue lane drains k-1's write-back plus eviction and SSD demotion —
but the PR 1 telemetry still saw it as main-thread stage timers plus
counters. This module adds the missing CAUSAL view:

**Spans.** ``span(name, ...)`` times a region and keeps a record
carrying ``(pass_seq, span_id, parent_id, lane)`` (the hub's span sinks
get it too, with ``trace_id=run``). ``lane`` names the EXECUTING
context — the catalog:

    main            the training/driver thread
    preload.worker  the depth-N PassPreloader worker (build + stage)
    preload.floats  the float half of a streamed build, beside the
                    worker's key half (ResidentPass.build_streamed)
    epilogue.lane   the PassEpilogue single-lane write-back worker
    ssd.compact     SSD watermark demotion + segment compaction (rides
                    the epilogue worker, rendered as its own service row)
    stream.reader   dataset reader threads

Parent ids nest automatically per thread (a ``pass.stage`` span opened
inside a ``pass.build`` span becomes its child). Cross-thread causality
uses explicit LINKS: the producer stashes its span id (e.g. the build
span's id rides the built pass as ``rp._trace_span_id``), and the
consumer opens its span with ``link_from=that_id`` — the Chrome sink
renders the link as a flow arrow from the source span's end to the
linked span's start, across lane rows.

**Always in memory, on the profiler's clock.** Every ``span()``
records, sink or no sink: on exit it appends one ``SpanRecord`` tuple
(``time.perf_counter_ns`` start and duration) to a process-wide bounded
ring (``recent_spans()``), and for its whole extent it holds a
``jax.profiler.TraceAnnotation`` of the same name. With no profiler
session the annotation is a flag test in C++; inside one
(``utils.profiler.trace()``, ``jax.profiler.start_trace``) the span
lands in the xplane's host plane on the device trace's own clock, on
the thread that ran it, with ``lane`` and ``pass_seq`` as its stats.
"Off" (no sink attached) means no sink call, no export, no counter and
no event payload; the ring and the annotation are what is left. That is
affordable because spans sit at pass and file granularity only — a span
inside a per-batch loop does not belong here.

**Names on the device.** The ``SCOPE_*`` constants are the catalog of
``jax.named_scope`` names the train step puts on its device ops
(``pbox.pull``, ``pbox.push``, ...); ``obs/xplane.py`` reduces a
profiler trace by them. The step and the reducer share the constants so
a refactor cannot drift them apart.

**Chrome rendering.** ``ChromeLaneTraceSink`` writes spans into a
``utils.profiler.ChromeTraceWriter`` with one STABLE tid row per lane
(thread-name metadata events name the rows) and flow ("s"/"f") events
for links — chrome://tracing / Perfetto shows the four-deep pipeline as
four labeled lanes with arrows from each pass's preloader build to its
main-thread consume.

**Critical path.** The pass drivers report each boundary stall into a
per-pass accumulator (``note_pass_part``); ``emit_pass_event`` consumes
it and attaches a ``critical_path`` block — wall time attributed across
train vs build-wait vs stage-wait vs fence-wait vs ssd-promote vs
evict-emergency — plus a per-pass ``bottleneck`` verdict, mirrored into
``pbox_pass_bottleneck_total{stage}``. Completed top-level spans
accumulate ``pbox_lane_busy_seconds_total{lane}``.
``scripts/telemetry_report.py`` renders the per-pass verdicts and the
whole-run summary ("7/8 passes device-bound, pass 2 build-bound").

See docs/OBSERVABILITY.md §Tracing for the span schema and the lane /
flow-link semantics.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

from paddlebox_tpu.obs.hub import get_hub
from paddlebox_tpu.utils.logging import get_logger

log = get_logger(__name__)

#: the lane catalog (docs/OBSERVABILITY.md §Tracing). Free-form lane
#: names are legal; these are the rows the shipped pipeline uses.
LANE_MAIN = "main"
LANE_PRELOAD = "preload.worker"
LANE_PRELOAD_FLOATS = "preload.floats"
LANE_EPILOGUE = "epilogue.lane"
LANE_SSD = "ssd.compact"
LANE_READER = "stream.reader"

#: the device scope catalog: ``jax.named_scope`` names on the train
#: step's ops (train/step.py, train/device_pass.py, train/sharded.py).
#: Metadata only — no operation, number or shape depends on them. The
#: backward ops of a scope carry ``transpose(jvp(pbox.<name>))``;
#: obs/xplane folds them into ``pbox.<name>.bwd``.
SCOPE_DECODE = "pbox.decode"        # wire unpack + slicing the staged pass
SCOPE_DEDUP = "pbox.dedup"          # in-trace dedup of the compact wire
SCOPE_PULL = "pbox.pull"            # row gather, pull values, expand
SCOPE_POOL_CVM = "pbox.pool_cvm"    # fused_seqpool_cvm
SCOPE_DENSE = "pbox.dense"          # model.apply
SCOPE_LOSS = "pbox.loss"
SCOPE_PUSH = "pbox.push"            # merge, scatter, in-table optimizer
SCOPE_DENSE_OPT = "pbox.dense_opt"  # tx.update + apply_updates
SCOPE_AUC = "pbox.auc"
SCOPE_A2A_PULL = "pbox.a2a_pull"    # sharded step: the pull all_to_all
SCOPE_A2A_PUSH = "pbox.a2a_push"    # sharded step: the grad all_to_all
#: scopes of the single-chip step (every one is in its lowered text)
STEP_SCOPES = (SCOPE_DECODE, SCOPE_DEDUP, SCOPE_PULL, SCOPE_POOL_CVM,
               SCOPE_DENSE, SCOPE_LOSS, SCOPE_PUSH, SCOPE_DENSE_OPT,
               SCOPE_AUC)
#: the sharded step adds the exchange
SHARDED_SCOPES = (SCOPE_A2A_PULL, SCOPE_A2A_PUSH)
#: a sequence model's step (train/step.SeqTrainStep over
#: models/nemotron_h.py) in place of pool_cvm / dense / auc. One word
#: after ``pbox.``: the reducers read a scope's name up to the first
#: character that is no letter, digit or ``_``
SCOPE_SSM_PROJ = "pbox.ssm_proj"    # norm, in/out projections, gated norm
SCOPE_SSM_CONV = "pbox.ssm_conv"    # causal depthwise conv + silu
SCOPE_SSM_SCAN = "pbox.ssm_scan"    # the chunked state-space scan
SCOPE_ATTN = "pbox.attn"            # norm, q/k/v/o (a model's q/k norms
#                                     and rotary), blockwise attention
SCOPE_MOE_ROUTE = "pbox.moe_route"  # norm, router, top-k, weights
SCOPE_MOE_EXPERTS = "pbox.moe_experts"  # sort, grouped products, combine
SCOPE_MOE_SHARED = "pbox.moe_shared"    # the shared expert
SCOPE_HEAD = "pbox.head"            # final norm + the output head
SEQ_STEP_SCOPES = (SCOPE_DECODE, SCOPE_DEDUP, SCOPE_PULL, SCOPE_SSM_PROJ,
                   SCOPE_SSM_CONV, SCOPE_SSM_SCAN, SCOPE_ATTN,
                   SCOPE_MOE_ROUTE, SCOPE_MOE_EXPERTS, SCOPE_MOE_SHARED,
                   SCOPE_HEAD, SCOPE_LOSS, SCOPE_PUSH, SCOPE_DENSE_OPT)
#: the same step over models/lfm2.py: a gated short convolution in place
#: of the Mamba-2 mixer, a leading dense feed-forward, no shared expert
SCOPE_CONV_PROJ = "pbox.conv_proj"  # operator norm, in_proj, out_proj
SCOPE_CONV_MIX = "pbox.conv_mix"    # the two gates and the depthwise conv
SCOPE_MLP = "pbox.mlp"              # the dense SwiGLU feed-forward
CONV_SEQ_STEP_SCOPES = (SCOPE_DECODE, SCOPE_DEDUP, SCOPE_PULL,
                        SCOPE_CONV_PROJ, SCOPE_CONV_MIX, SCOPE_ATTN,
                        SCOPE_MLP, SCOPE_MOE_ROUTE, SCOPE_MOE_EXPERTS,
                        SCOPE_HEAD, SCOPE_LOSS, SCOPE_PUSH, SCOPE_DENSE_OPT)
#: the same step over models/mellum.py: every layer attention then routed
#: experts. A SLIDING layer's whole sublayer (norm, projections, head
#: norms, rotary, windowed attention, ``o``) is under its own scope; a
#: full layer stays under ``pbox.attn``, which so means "full causal
#: attention" for every model
SCOPE_ATTN_WINDOW = "pbox.attn_window"
WINDOW_SEQ_STEP_SCOPES = (SCOPE_DECODE, SCOPE_DEDUP, SCOPE_PULL, SCOPE_ATTN,
                          SCOPE_ATTN_WINDOW, SCOPE_MOE_ROUTE,
                          SCOPE_MOE_EXPERTS, SCOPE_HEAD, SCOPE_LOSS,
                          SCOPE_PUSH, SCOPE_DENSE_OPT)
#: the same step over models/ouro.py: one stack of attention + dense
#: feed-forward layers run several times with the same weights, the head
#: read after every run. The exit gate (its product and sigmoid, the exit
#: distribution, its entropy, the mixing of the exits' per-position
#: losses) has a scope of its own; every run's attention is under
#: ``pbox.attn``, feed-forward under ``pbox.mlp``, head read under
#: ``pbox.head`` / ``pbox.loss``
SCOPE_EXIT_GATE = "pbox.exit_gate"
LOOP_SEQ_STEP_SCOPES = (SCOPE_DECODE, SCOPE_DEDUP, SCOPE_PULL, SCOPE_ATTN,
                        SCOPE_MLP, SCOPE_EXIT_GATE, SCOPE_HEAD, SCOPE_LOSS,
                        SCOPE_PUSH, SCOPE_DENSE_OPT)
#: the same step over models/kimi_linear.py: Kimi Delta Attention mixers
#: beside latent attention ones (under ``pbox.attn``, full causal
#: attention, with their latent projections and norm), a leading dense
#: feed-forward (``pbox.mlp``), routed and shared experts after it
SCOPE_KDA_PROJ = "pbox.kda_proj"    # norm, the seven projections, decay,
#                                     beta, the gated output norm
SCOPE_KDA_CONV = "pbox.kda_conv"    # three depthwise convs, SiLU, L2 norms
SCOPE_KDA_SCAN = "pbox.kda_scan"    # the chunked delta rule (ops/kda.py)
KDA_SEQ_STEP_SCOPES = (SCOPE_DECODE, SCOPE_DEDUP, SCOPE_PULL,
                       SCOPE_KDA_PROJ, SCOPE_KDA_CONV, SCOPE_KDA_SCAN,
                       SCOPE_ATTN, SCOPE_MLP, SCOPE_MOE_ROUTE,
                       SCOPE_MOE_EXPERTS, SCOPE_MOE_SHARED, SCOPE_HEAD,
                       SCOPE_LOSS, SCOPE_PUSH, SCOPE_DENSE_OPT)
#: a head-wise output gate's projection, sigmoid and product with the
#: heads (models/laguna.py), inside its attention layer's scope
SCOPE_ATTN_GATE = "pbox.attn_gate"

#: spans kept in memory (about 13 a resident pass: hundreds of passes)
RING_SPANS = 8192


class SpanRecord(NamedTuple):
    """One completed span as the ring keeps it."""
    name: str
    lane: str
    pass_seq: Optional[int]
    span_id: int
    parent_id: int
    link_from: int
    t0_ns: int      # time.perf_counter_ns() at entry
    dur_ns: int
    attrs: Optional[Dict]


_RING: "collections.deque[SpanRecord]" = collections.deque(
    maxlen=RING_SPANS)

# .lane: str, .stack: List[(span id, pass_seq)] of the open spans
_TLS = threading.local()
_ID_LOCK = threading.Lock()
_NEXT_ID = 1
_NEXT_PASS = 1


def _new_span_id() -> int:
    global _NEXT_ID
    with _ID_LOCK:
        sid = _NEXT_ID
        _NEXT_ID += 1
    return sid


def next_pass_seq() -> int:
    """The process's next pass identifier: whoever makes a pass (the
    preloader, ``ResidentPass.build``) draws one, the pass carries it
    (``rp.pass_seq``) and every span of that pass on every lane gets it.
    """
    global _NEXT_PASS
    with _ID_LOCK:
        seq = _NEXT_PASS
        _NEXT_PASS += 1
    return seq


def tracing_active() -> bool:
    """True iff spans reach a SINK: the hub is active AND at least one
    span sink is attached. (The ring and the profiler annotation get
    every span regardless; probes that re-run work only to time it
    guard on this.)"""
    hub = get_hub()
    return hub.active and bool(hub._span_sinks)


# ---- lanes -------------------------------------------------------------
def current_lane() -> str:
    """The calling thread's lane; defaults to ``main`` on the main
    thread and the thread's name elsewhere (workers that matter set
    their lane explicitly — PassPreloader, PassEpilogue, readers)."""
    lane = getattr(_TLS, "lane", None)
    if lane is not None:
        return lane
    t = threading.current_thread()
    return LANE_MAIN if t is threading.main_thread() else t.name


def set_lane(lane: str) -> None:
    """Pin the calling thread's lane for its lifetime (worker-thread
    entry points call this once at start)."""
    _TLS.lane = lane


@contextlib.contextmanager
def lane_scope(lane: str) -> Iterator[None]:
    """Temporarily relabel the calling thread's lane — e.g. the SSD
    demote/compact slot rides the epilogue worker but renders as the
    ``ssd.compact`` service row."""
    prev = getattr(_TLS, "lane", None)
    _TLS.lane = lane
    try:
        yield
    finally:
        _TLS.lane = prev


# ---- spans -------------------------------------------------------------
class SpanHandle:
    """What ``span()`` yields: enough identity for cross-thread links
    (stash ``span_id`` on the object crossing threads and pass it as the
    consumer span's ``link_from``)."""

    __slots__ = ("span_id", "lane", "name", "pass_seq", "attrs")

    def __init__(self, span_id: int, lane: str, name: str,
                 pass_seq: Optional[int] = None,
                 attrs: Optional[Dict] = None) -> None:
        self.span_id = span_id
        self.lane = lane
        self.name = name
        #: ``pass_seq`` and ``attrs`` may be set inside the span, for
        #: what is known only at its end (the pass a wait popped); the
        #: ring's record reads both at exit
        self.pass_seq = pass_seq
        self.attrs = {} if attrs is None else attrs


def current_span_id() -> int:
    """The calling thread's innermost OPEN span id (0 when none) — the
    producer-side id for a cross-thread link created mid-span (e.g.
    end_pass links its submit span to the epilogue job it enqueues)."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1][0] if stack else 0


def current_pass_seq() -> Optional[int]:
    """The ``pass_seq`` of the calling thread's innermost open span
    (None when none is open or it has none): what a span opened on
    ANOTHER thread for the same pass is given, since only a thread's own
    spans inherit it."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1][1] if stack else None


def recent_spans() -> List[SpanRecord]:
    """A copy of the in-memory ring, oldest first (completion order:
    a child precedes its parent)."""
    return list(_RING)


_ANNOTATION = None


def _annotation_cls():
    """``jax.profiler.TraceAnnotation``, imported at the first span so
    that importing this package stays free of jax."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


@contextlib.contextmanager
def span(name: str, pass_seq: Optional[int] = None,
         lane: Optional[str] = None, link_from: int = 0,
         **attrs) -> Iterator[SpanHandle]:
    """Timed causal span → the ring, the profiler's trace, and the hub's
    span sinks when any is attached. A span with no ``pass_seq`` of its
    own takes its parent's. ``link_from`` names a producer span on
    another thread; rich sinks render it as a flow arrow. Attrs ride
    the record (small, JSON-able values only); the handle's ``attrs``
    dict may be filled inside the span for values known only at its end.
    """
    ln = lane or current_lane()
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    parent, parent_seq = stack[-1] if stack else (0, None)
    if pass_seq is None:
        pass_seq = parent_seq
    sid = _new_span_id()
    handle = SpanHandle(sid, ln, name, pass_seq, attrs)
    note = {"lane": ln} if pass_seq is None else {
        "lane": ln, "pass_seq": pass_seq}
    stack.append((sid, pass_seq))
    with _annotation_cls()(name, **note):
        t0 = time.perf_counter_ns()
        try:
            yield handle
        finally:
            rec = SpanRecord(name, ln, handle.pass_seq, sid, parent,
                             link_from, t0, time.perf_counter_ns() - t0,
                             attrs or None)
            stack.pop()
            _RING.append(rec)
            hub = get_hub()
            if hub.active and hub._span_sinks:
                _to_sinks(hub, rec)


def span_dict(rec: SpanRecord) -> Dict:
    """A ring record as the sinks and the flight recorder's bundle
    carry it: seconds on the ``perf_counter`` clock (what
    ``ChromeTraceWriter`` takes), optional fields left out."""
    out = {"name": rec.name, "span_id": rec.span_id,
           "parent_id": rec.parent_id, "lane": rec.lane,
           "t0": rec.t0_ns / 1e9, "dur": rec.dur_ns / 1e9,
           "link_from": rec.link_from}
    if rec.pass_seq is not None:
        out["pass_seq"] = rec.pass_seq
    if rec.attrs:
        out["attrs"] = rec.attrs
    return out


def _to_sinks(hub, rec: SpanRecord) -> None:
    """The sink fan-out of one completed span."""
    full = span_dict(rec)
    full["trace_id"] = hub.run_id
    for s in hub._span_sinks:
        try:
            s.span_full(full)
        except Exception:
            log.warning("trace span sink failed", exc_info=True)
    if rec.parent_id == 0:
        # lane occupancy counts TOP-LEVEL spans only (children are
        # contained in their parent's wall — counting both would
        # double-book the lane)
        hub.counter("pbox_lane_busy_seconds_total",
                    "seconds each pipeline lane spent in top-level "
                    "spans").inc(full["dur"], lane=rec.lane)


# ---- Chrome sink: per-lane rows + flow arrows --------------------------
class ChromeLaneTraceSink:
    """Span sink rendering causal spans as PER-LANE tid rows with flow
    arrows for cross-thread links in a chrome://tracing JSON.

    Rows are the LANE catalog, not OS thread ids: one stable tid per
    lane name, labeled via thread-name metadata, ordered by first
    appearance. A span whose ``link_from`` names an already-rendered
    span gets a flow ("s" at the source span's end, "f" at this span's
    start) so the build→consume hand-off draws as an arrow across
    lanes.

    Pass an explicit ``utils.profiler.ChromeTraceWriter`` (then call
    ``writer.save(path)`` yourself), or None to follow whatever writer
    ``utils.profiler.set_chrome_trace`` installed at span time."""

    _DONE_CAP = 1024   # remembered (end, tid) of recent spans for links

    def __init__(self, writer=None) -> None:
        self._writer = writer
        self._lock = threading.Lock()
        self._lane_tids: Dict[str, int] = {}
        self._done: "collections.OrderedDict[int, tuple]" = \
            collections.OrderedDict()

    def _resolve(self):
        w = self._writer
        if w is None:
            from paddlebox_tpu.utils.profiler import chrome_trace
            w = chrome_trace()
        return w

    def _tid(self, w, lane: str) -> int:
        with self._lock:
            tid = self._lane_tids.get(lane)
            if tid is None:
                tid = self._lane_tids[lane] = len(self._lane_tids) + 1
                w.thread_meta(tid, lane, sort_index=tid)
            return tid

    def span_full(self, rec: Dict) -> None:
        w = self._resolve()
        if w is None:
            return
        tid = self._tid(w, rec["lane"])
        args = dict(rec.get("attrs") or {})
        args["span_id"] = rec["span_id"]
        if rec.get("parent_id"):
            args["parent_id"] = rec["parent_id"]
        if "pass_seq" in rec:
            args["pass_seq"] = rec["pass_seq"]
        args["lane"] = rec["lane"]
        t0, dur = rec["t0"], rec["dur"]
        w.complete(rec["name"], t0, dur, tid=tid, **args)
        link = rec.get("link_from", 0)
        with self._lock:
            self._done[rec["span_id"]] = (t0 + dur, tid)
            while len(self._done) > self._DONE_CAP:
                self._done.popitem(last=False)
            src = self._done.get(link) if link else None
        if src is not None:
            src_end, src_tid = src
            # the arrow leaves the source span's END and binds to this
            # span's START; a source that outlived its consumer's start
            # (a submit span closing after its job began) clamps so the
            # arrow still flows forward
            w.flow(link, "s", min(src_end, t0), src_tid,
                   name=rec["name"])
            w.flow(link, "f", t0, tid, name=rec["name"])

    def close(self) -> None:
        pass


# ---- per-pass critical-path attribution --------------------------------
#: boundary stage keys the drivers report (note_pass_part); "train" is
#: implicit (the pass event's elapsed_sec). Order = report/docs order.
BOUNDARY_STAGES = ("build_wait", "stage_wait", "fence_wait",
                   "ssd_promote", "evict_emergency", "evict_scatter",
                   "end_submit")

_PARTS_LOCK = threading.Lock()
_PENDING_PARTS: Dict[str, float] = {}


def note_pass_part(stage: str, sec: float) -> None:
    """Report one boundary stall component for the UPCOMING pass event
    (drivers call this as each boundary phase completes: preload wait,
    begin-stall pieces, the previous pass's end-submit and fence wait).
    Inert without sinks — the parts exist to ride the pass event."""
    if sec <= 0 or not get_hub().active:
        return
    with _PARTS_LOCK:
        _PENDING_PARTS[stage] = _PENDING_PARTS.get(stage, 0.0) + sec


def consume_pass_parts() -> Dict[str, float]:
    """Pop the accumulated boundary parts (emit_pass_event calls this
    exactly once per pass event)."""
    with _PARTS_LOCK:
        if not _PENDING_PARTS:
            return {}
        parts = dict(_PENDING_PARTS)
        _PENDING_PARTS.clear()
        return parts


def critical_path_block(train_sec: float,
                        parts: Dict[str, float]) -> Dict:
    """Attribute one pass's wall time across lanes: ``wall_sec`` =
    train + every reported boundary part (so the block SUMS to the
    pass's critical-path wall by construction), with a ``bottleneck``
    verdict — ``device`` when training dominates, else the largest
    stall's stage name, with that stall's seconds as ``stall_sec``."""
    parts = {k: round(float(v), 6) for k, v in parts.items() if v > 0}
    wall = float(train_sec) + sum(parts.values())
    block: Dict = {"train_sec": round(float(train_sec), 6)}
    for k in BOUNDARY_STAGES:
        if k in parts:
            block[f"{k}_sec"] = parts[k]
    for k in sorted(parts):   # free-form extra stages still ship
        if k not in BOUNDARY_STAGES:
            block[f"{k}_sec"] = parts[k]
    block["wall_sec"] = round(wall, 6)
    worst = max(parts, key=parts.get) if parts else None
    if worst is None or train_sec >= parts[worst]:
        block["bottleneck"] = "device"
        block["stall_sec"] = round(max(wall - train_sec, 0.0), 6)
    else:
        block["bottleneck"] = worst
        block["stall_sec"] = parts[worst]
    return block


def reset() -> None:
    """Test hook: drop pending parts and the span ring (span and pass
    ids keep counting — they only need process-uniqueness)."""
    with _PARTS_LOCK:
        _PENDING_PARTS.clear()
    _RING.clear()
