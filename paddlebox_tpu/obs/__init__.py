"""Unified telemetry: typed instruments, run events, Prometheus export,
straggler watchdog (see docs/OBSERVABILITY.md for the catalog)."""

from paddlebox_tpu.obs.alerts import AlertEngine, Rule, default_rules
from paddlebox_tpu.obs.flightrec import FlightRecorder
from paddlebox_tpu.obs.hub import (TelemetryHub, configure_from_flags,
                                   emit_pass_event, get_hub, reset_hub)
from paddlebox_tpu.obs.instruments import Counter, Gauge, Histogram
from paddlebox_tpu.obs.quality import QualityMonitor
from paddlebox_tpu.obs.sinks import JsonlSink, MemorySink
from paddlebox_tpu.obs.trace import (ChromeLaneTraceSink, lane_scope,
                                     set_lane, span, tracing_active)
from paddlebox_tpu.obs.watchdog import (DirHeartbeatStore,
                                        LocalHeartbeatStore,
                                        StragglerReport, StragglerTimeout,
                                        StragglerWatchdog)

__all__ = [
    "TelemetryHub", "get_hub", "reset_hub", "configure_from_flags",
    "emit_pass_event", "Counter", "Gauge", "Histogram",
    "JsonlSink", "MemorySink", "ChromeLaneTraceSink",
    "span", "lane_scope", "set_lane", "tracing_active",
    "StragglerWatchdog", "StragglerReport", "StragglerTimeout",
    "LocalHeartbeatStore", "DirHeartbeatStore",
    "FlightRecorder", "QualityMonitor", "AlertEngine", "Rule",
    "default_rules",
]
