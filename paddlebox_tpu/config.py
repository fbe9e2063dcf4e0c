"""Global flag/config system.

TPU-native equivalent of the reference's three config layers (SURVEY.md §5.6):
gflags env-settable ``FLAGS_*`` (reference: paddle/fluid/platform/flags.cc,
padbox block :946-975), the ``TrainerDesc``/``DataFeedDesc`` protos, and
per-wrapper config maps. Here: one typed dataclass, every field overridable
from the environment as ``FLAGS_<name>`` at import time or via
``FLAGS.update(...)`` / ``flags_scope(...)`` at runtime.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any, Iterator


def _env_cast(raw: str, ty: type) -> Any:
    if ty is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    if ty is int:
        return int(raw)
    if ty is float:
        return float(raw)
    return raw


@dataclasses.dataclass
class Flags:
    """Process-wide tunables. Defaults mirror the reference's flag defaults
    where a counterpart exists (cited per field)."""

    # --- data pipeline (reference: platform/flags.cc:946-975) ---
    record_pool_max_size: int = 2_000_000
    read_thread_num: int = 8
    channel_capacity: int = 65536
    # native C++ file→columnar parse fast path (data/parser.py,
    # native/slot_parser.cpp); falls back to per-line python parsing
    native_parse: bool = True

    # --- trainer (reference: boxps_worker.cc) ---
    check_nan_inf: bool = False

    # --- embedding store ---
    # Default per-shard row capacity; tables are statically sized for XLA.
    table_capacity_per_shard: int = 1 << 20
    # host-RAM backing store capacity (Phase 5; rows beyond HBM)
    host_store_capacity: int = 1 << 24
    # --- SSD third tier (ps/ssd.SsdTier; docs/STORAGE.md) ---
    # directory for disk-tier segment files; non-empty auto-attaches a
    # tier (unique subdir per HostStore). "" = no tier unless a table
    # passes ssd_dir explicitly or spill_cold lazily creates one.
    ssd_dir: str = ""
    # rows per log-structured segment before it seals (append-only;
    # sealed segments are immutable — the manifest/compaction unit)
    ssd_segment_rows: int = 1 << 15
    # background compaction rewrites a sealed segment when its live-row
    # fraction falls below this (<= 0 disables compaction)
    ssd_compact_live_frac: float = 0.5
    # host-RAM occupancy fraction that triggers background demotion of
    # the coldest rows to the SSD tier (runs on the async-epilogue
    # worker after each end_pass write-back; <= 0 disables — rows then
    # demote only under hard capacity pressure or manual spill_cold)
    host_demote_watermark: float = 0.92
    # demotion drains RAM occupancy down to this fraction
    host_demote_target: float = 0.8
    # feature shrink: drop rows whose decayed show falls below this
    shrink_delete_threshold: float = 0.0
    show_click_decay_rate: float = 0.98
    # online-learning daemon (online.py; docs/ONLINE.md): run a shrink
    # cycle every N completed stream windows, counted on the dataset's
    # monotone windows_completed clock so the cadence survives
    # preemption/resume; the boundary checkpoint after a shrink is
    # forced to a BASE save (deltas cannot carry whole-table decay).
    # 0 = lifecycle aging off (keys then accrete without bound — fine
    # for finite jobs, a slow-motion OOM for always-on streams).
    shrink_every_windows: int = 0

    # --- pallas kernels (ops/pallas_kernels.py; interpret-mode off-TPU;
    # docs/PERFORMANCE.md §Device kernels) ---
    # table line-gather via the scalar-prefetch Pallas gather
    # (ps/table.gather_full_rows) instead of XLA's per-element gather
    use_pallas_gather: bool = False
    # route the seqpool family through the fused Pallas embed-pool-CVM
    # kernel: ops/seqpool_cvm.fused_seqpool_cvm{,_with_conv} forward →
    # fused_pool_cvm_forward (MXU one-hot pooling + in-VMEM CVM),
    # backward → segment_gather_mxu (transposed one-hot matmul), and
    # every _pool_core/segment_sum call → segment_sum_mxu. The trivial
    # (segments=None) layout keeps its free reshape path. Off (default)
    # = the XLA composition, byte-for-byte today's program; parity is
    # gated in tier-1 (tests/test_pallas_kernels.py,
    # tests/test_pallas_train_gate.py — forward AND pushed grads,
    # uniform + zipf shapes).
    use_pallas_seqpool: bool = False
    # route the remaining CTR op family through the fused Pallas device
    # kernels (ops/pallas_ctr.py — ISSUE 13, the PR 11 seam pattern
    # applied to rank_attention/batch_fc/cross_norm_hadamard). Each op
    # reads its flag at ONE dispatch seam in its module; a shape that
    # overflows the kernel's VMEM residency budget falls back to the
    # XLA composition. Off (default) = the XLA composition,
    # byte-for-byte today's program; parity matrices are gated in
    # tier-1 (tests/test_pallas_ctr.py, tests/test_pallas_train_gate.py).
    # block-grouped rank attention: ≤ max_rank² VMEM-resident param
    # blocks, keep-mask folded into a one-hot × gathered-X MXU matmul
    # (never materializing the [N, K, D, P] param gather)
    use_pallas_rank_attention: bool = False
    # per-slot blocked batched GEMM with the bias add fused in-VMEM
    # (default, batchcount and transpose_weight modes)
    use_pallas_batch_fc: bool = False
    # one VMEM pass producing the [a, b, a⊙b, a·b] cross blocks with
    # the data_norm mean/scale applied in the same residency (summary
    # update and the sharded sync_stats psum stay outside, unchanged)
    use_pallas_cross_norm: bool = False

    # --- fused computation-collective sharded step (ISSUE 11;
    # docs/PERFORMANCE.md §Sharded-step overlap) ---
    # number of slot-group chunks the sharded pull exchange decomposes
    # into: chunk k+1's embedding all_to_all is in flight while chunk
    # k's expand_pull → fused_seqpool_cvm pooling runs, and the push
    # grad all_to_all interleaves with the independent dense sync.
    # 1 (default) = the monolithic exchange-then-compute schedule,
    # byte-for-byte today's program. >1 requires slot-qualified keys
    # (each key belongs to one slot — the criteo/CTR schema); a plan
    # build that finds a key spanning slot groups falls back to the
    # monolithic schedule for that batch, loudly. Chunked and
    # monolithic schedules are BIT-IDENTICAL (gated in tier-1:
    # tests/test_sharded.py digest parity, scripts/scaling_check.py).
    a2a_chunks: int = 1

    # --- metrics (reference: metrics.h:46 table_size 1e6+1) ---
    auc_num_buckets: int = 1_000_000
    # False (default) = exact f64 host finalize — BasicAucCalculator::compute
    # semantics (metrics.cc:288-304). True = reduce the AUC bucket tables to
    # scalars ON DEVICE in f32 (~1e-5 AUC drift) and fetch ~8 floats instead
    # of pulling [2, nbins] to host each pass.
    auc_device_reduce: bool = False

    # --- async pass epilogue (ps/epilogue; docs/PERFORMANCE.md) ---
    # end_pass snapshots touched rows, dispatches the D2H gather, and
    # hands the HostStore write-back to a background worker so pass N+1
    # trains while pass N drains; every host-tier read and lifecycle op
    # fences first (bit-for-bit identical to the synchronous path —
    # scripts/pipeline_check.py is the gate). False = write back inline
    # before end_pass returns (the pre-overlap behavior).
    async_end_pass: bool = True
    # --- async capacity eviction (ps/tiered._evict_ahead; ISSUE 9) ---
    # with queued feed-pass stages (the tiered pass pipeline,
    # train/device_pass.PassPipeline), capacity-pressure eviction for
    # the NEXT pass runs on the end_pass epilogue lane right after each
    # write-back lands (clean rows only — release + accounting, no D2H)
    # so steady-state begin_pass pays only for genuinely-new rows; the
    # inline eviction in begin_pass remains as the emergency path
    # (reported as evict_emergency_sec vs evict_async_sec in the table's
    # last_pass_stats). False = eviction stays fully inline at
    # begin_pass (the pre-pipeline behavior).
    async_capacity_evict: bool = True

    # --- pass-boundary scatter (ps/table.scatter_logical_rows) ---
    # fixed chunk size for the begin_pass delta scatter: one compiled
    # executable per table geometry instead of one per delta size
    scatter_chunk_rows: int = 1 << 14
    # warm the chunk-scatter executable in a background thread at tiered
    # table construction, so the first pass boundary doesn't pay the
    # compile (utils/compile_cache + ps/tiered)
    warmup_pass_scatter: bool = True

    # --- deep pass preload pipeline (train/device_pass.PassPreloader;
    # docs/PERFORMANCE.md §Deep pass pipeline) ---
    # passes in flight (building or staged) ahead of training; 1 = the
    # old double-buffer. The effective depth self-clamps under the HBM
    # budget below.
    preload_depth: int = 2
    # staged-pass HBM budget: the preloader estimates bytes per staged
    # pass from the first build and clamps its effective depth to
    # max(1, budget // bytes_per_pass) — loudly, instead of OOMing
    # (<= 0 disables the guard)
    preload_hbm_budget_mb: int = 4096
    # index pack/upload chunk (batches): uniq/gidx blocks encode and
    # start their H2D transfer as each chunk completes instead of after
    # the full pack (<= 0 = whole pass, the pre-pipeline behavior)
    preload_pack_chunk_batches: int = 8
    # q8 float wire on NON-columnar re-iterable datasets: True streams
    # per-column min/max batch-by-batch and casts on a second walk —
    # no full-pass f32 staging, but heavy-tailed columns lose
    # quantize_floats' winsorized-range clip and the batches rebuild
    # twice. False restores the staged whole-pass quantization
    # (winsorize + one walk, at the full-pass f32 host cost).
    q8_streaming_front: bool = True

    # --- telemetry (obs/ TelemetryHub; docs/OBSERVABILITY.md) ---
    # path → attach a JSONL event sink (one structured record per pass)
    telemetry_jsonl: str = ""
    # ≥0 → serve Prometheus text exposition over HTTP (0 = ephemeral
    # port); -1 disables the endpoint
    telemetry_prom_port: int = -1
    # multihost straggler watchdog (obs/watchdog, train/multihost):
    # shared directory for heartbeat files ("" = watchdog not started
    # by make_straggler_watchdog unless a dir/store is passed)
    straggler_heartbeat_dir: str = ""
    straggler_step_lag: int = 1000
    straggler_timeout_sec: float = 120.0
    # >0 → a stall persisting this long arms an abort: the training
    # thread's next heartbeat raises StragglerTimeout
    straggler_abort_sec: float = 0.0
    # JSONL sink rotation (always-on daemon: bound the event log).
    # >0 → when the live segment exceeds this many MiB it rotates to
    # <path>.1 (older segments shift to .2, .3, ...); 0 = one unbounded
    # file (the seed behavior). telemetry_report reads rotated sets in
    # order automatically.
    telemetry_jsonl_max_mb: float = 0.0
    # rotated segments kept per JSONL path (the live file rides on top)
    telemetry_jsonl_keep: int = 3
    # quarantine a telemetry sink after this many CONSECUTIVE
    # emit/span failures (pbox_sink_errors_total books every failure;
    # a broken sink must never take the training hot path down)
    telemetry_sink_errors_max: int = 8

    # --- anomaly flight recorder (obs/flightrec;
    # docs/OBSERVABILITY.md §Flight recorder) ---
    # non-empty → keep a bounded in-memory ring of recent events/spans
    # and publish a self-contained postmortem bundle (ring + instrument
    # snapshot + critical-path blocks + FLAGS + live thread stacks)
    # into this directory whenever a trigger fires (NaN rollback,
    # reload degrade, pipeline hang, watchdog escalation, SLO breach,
    # hub.dump_blackbox). "" = recorder off (zero per-event cost).
    flightrec_dir: str = ""
    # ring capacity (events + spans, newest win)
    flightrec_ring_events: int = 512
    # per-trigger debounce: repeat fires inside this window are
    # suppressed (counted in pbox_flightrec_suppressed_total) — an
    # anomaly storm yields ONE bundle per trigger per window
    flightrec_debounce_sec: float = 60.0
    # newest bundles kept on disk per recorder dir (retention cap)
    flightrec_keep: int = 16

    # --- model-quality drift monitor (obs/quality;
    # docs/OBSERVABILITY.md §Model quality) ---
    # >0 → windowed per-pass quality stats ride every train/stream
    # pass event: key coverage/churn, embedding-norm drift vs the
    # trailing baseline, predicted-vs-observed CTR calibration buckets
    # and a windowed AUC trend with a degradation verdict
    # (pbox_quality_* instruments + quality_window events). 0 = off.
    quality_window_passes: int = 0
    # windowed-AUC degradation verdict: trailing-half mean AUC below
    # leading-half mean by more than this → pbox_quality_degraded=1
    quality_auc_drop: float = 0.01
    # coarse calibration buckets the 1e6-bin AUC tables collapse into
    quality_calibration_buckets: int = 10

    # --- SLO alert engine (obs/alerts; docs/OBSERVABILITY.md §Alerts) ---
    # >0 → evaluate the default alert rules on a cadence thread this
    # often (serving staleness / p99 / stream lag / hang / NaN-rollback
    # rate / AUC degradation → pbox_alerts_active{rule,severity},
    # alert_fired/alert_cleared events, /alertz). 0 = engine not
    # started (construct AlertEngine explicitly for manual evaluation).
    alerts_eval_interval_sec: float = 0.0
    # default-rule thresholds (staleness reuses
    # serving_staleness_max_sec; hang / NaN-rollback fire on any
    # counter increase between evaluations)
    alerts_serving_p99_ms: float = 250.0
    alerts_stream_lag_files: int = 100
    # online daemon lifecycle rules (docs/ONLINE.md): shrink_overdue
    # fires when pbox_online_windows_since_shrink exceeds this; 0 =
    # auto (2 × shrink_every_windows, rule absent when aging is off).
    # backlog_growth fires on a rising pbox_stream_lag_files trend.
    alerts_shrink_overdue_windows: int = 0

    # --- resilience (resilience/; docs/RESILIENCE.md) ---
    # RetryPolicy.from_flags defaults, applied at the IO seams
    # (CommandBackend CLI calls, checkpoint file IO, dataset file opens)
    retry_max_attempts: int = 4
    retry_base_delay_sec: float = 0.05
    retry_max_delay_sec: float = 2.0
    # wall-clock cap for one retried operation (<=0 = no deadline)
    retry_deadline_sec: float = 30.0
    # backoff jitter fraction in [0,1]; seeded from FLAGS.seed + site,
    # so delay sequences are deterministic per run seed
    retry_jitter: float = 0.25
    # kill a hung CommandBackend CLI after this many seconds (<=0 = none)
    command_timeout_sec: float = 300.0
    # max dataset files quarantined per load before the load fails
    # (0 = quarantine disabled: first bad file aborts, the seed behavior)
    poison_budget_files: int = 0
    # max dropped/corrupt records tolerated per FILE before the file is
    # declared poisoned and quarantined (-1 = unlimited silent drops,
    # the seed behavior)
    poison_budget_records: int = -1
    # bounded retry-from-last-checkpoint attempts in Trainer.run_pass
    # (0 = a failed pass raises immediately)
    pass_retry_limit: int = 0
    # deterministic fault-injection plan spec (resilience/faults.py
    # grammar, e.g. "file_mgr.command:fail:nth=1"); "" = no injection
    fault_plan: str = ""

    # --- preemption & mid-pass resume (resilience/preemption,
    # resilience/consensus; docs/RESILIENCE.md) ---
    # install SIGTERM/SIGINT -> graceful-stop handlers at Trainer init;
    # the loop then halts at a batch boundary with an emergency
    # checkpoint + resume cursor instead of dying mid-step
    graceful_shutdown: bool = False
    # >0: periodic in-pass checkpoint (delta + cursor.json) every N
    # batches, so a preempted pass replays seconds, not hours; needs
    # run_pass(checkpoint=...) and an in-memory dataset
    ckpt_every_batches: int = 0
    # shared dir (NFS/FUSE) for multihost-consistent recovery: restore-
    # step agreement + shared quarantine ("" = consensus helpers must be
    # constructed explicitly)
    restore_consensus_dir: str = ""
    # how long a consensus gather waits for the full mesh to publish
    consensus_timeout_sec: float = 60.0
    # elastic membership (distributed/elastic, train/multihost): shared
    # directory backing the FileKVStore lease/rendezvous protocol
    # ("" = make_elastic_manager requires an explicit store)
    elastic_dir: str = ""
    # lease TTL: a host whose heartbeat mtime is older than this is a
    # candidate death (confirmed after elastic_dead_checks polls)
    elastic_ttl_sec: float = 10.0
    # dead-rank hysteresis: consecutive boundary polls a host must miss
    # before a scale event fires (1 = legacy immediate detection; the
    # default 2 absorbs one delayed-but-alive heartbeat)
    elastic_dead_checks: int = 2

    # --- streaming ingest (data/dataset.QueueDataset windowed mode +
    # Trainer.train_stream; docs/RESILIENCE.md §Streaming) ---
    # >0: QueueDataset consumes its filelist in bounded WINDOWS of N
    # files — no record crosses a window boundary, completed windows are
    # tracked per file, and the v2 stream cursor (cursor.json) records
    # fully-consumed files + the open window so a preempted streaming
    # job resumes by skipping completed files and replaying the open
    # window AT-LEAST-ONCE. 0 = legacy unwindowed streaming (no cursor
    # resume; start_batch != 0 keeps refusing).
    stream_window_files: int = 0
    # Trainer.train_stream publishes a stream-boundary checkpoint every
    # N completed windows (bounds replay after a hard kill)
    stream_ckpt_every_windows: int = 1

    # --- artifact/publishing layer (artifacts.py; docs/RESILIENCE.md
    # §Publishing) ---
    # registry dir for versioned model artifacts; non-empty →
    # CheckpointManager auto-attaches an ArtifactStore and publishes
    # every BOUNDARY checkpoint (incl. train_stream stream-boundary
    # saves) as a lineage-linked version. "" = publishing off.
    artifact_root: str = ""
    # reader-lease staleness TTL: a lease whose heartbeat mtime is
    # older than this (or whose same-host writer pid is dead) is
    # provably stale and may be reaped by the retention sweep; readers
    # fence every access against lease loss (ArtifactLeaseLostError)
    artifact_lease_ttl_sec: float = 300.0
    # versions kept by ArtifactStore.retain (plus leased versions and
    # lineage parents, which are NEVER swept); <=0 = keep everything
    artifact_keep: int = 0

    # --- concurrent serving (serving.py; docs/SERVING.md) ---
    # background hot-reload cadence: serving.ReloadLoop polls the
    # ArtifactStore tip this often while healthy (failed polls back off
    # on the seeded RetryPolicy schedule instead — site serving.reload)
    serving_reload_poll_sec: float = 2.0
    # snapshot-staleness SLO: when a newer adoptable version has been
    # published for longer than this without the serving snapshot
    # advancing, the reload loop marks the serving block stale
    # (healthz "serving".stale, pbox_serving_staleness_sec) and logs
    # loudly — the degrade state is visible, never silent
    serving_staleness_max_sec: float = 60.0
    # predict_many micro-batch cap (instances per forward); <=0 = the
    # model desc's batch_size (one compiled bucket). Smaller caps trade
    # throughput for per-query latency under mixed traffic.
    serving_batch_max: int = 0

    # --- pipeline hang deadline (ps/epilogue.PassEpilogue.fence,
    # train/device_pass.PassPreloader.wait) ---
    # >0: a pipeline wait that sees no job/build COMPLETE for this long
    # raises PipelineHangError naming the stuck stage (with queue-depth
    # telemetry) instead of blocking forever on a wedged worker — set
    # above the worst-case single job duration (progress is observed at
    # whole-job granularity); 0 = wait indefinitely (the pre-deadline
    # behavior)
    pipeline_wait_timeout_sec: float = 0.0

    # --- runtime ---
    profile: bool = False
    log_period_steps: int = 100
    seed: int = 0

    def update(self, **kwargs: Any) -> None:
        for k, v in kwargs.items():
            if not hasattr(self, k):
                raise AttributeError(f"unknown flag: {k}")
            setattr(self, k, v)

    @classmethod
    def from_env(cls) -> "Flags":
        self = cls()
        for f in dataclasses.fields(self):
            raw = os.environ.get(f"FLAGS_{f.name}")
            if raw is not None:
                ty = type(getattr(self, f.name))
                try:
                    setattr(self, f.name, _env_cast(raw, ty))
                except ValueError as e:
                    raise ValueError(f"bad value for env flag FLAGS_{f.name}={raw!r}: {e}") from None
        return self


FLAGS = Flags.from_env()


@contextlib.contextmanager
def flags_scope(**kwargs: Any) -> Iterator[Flags]:
    """Temporarily override flags (tests use this heavily)."""
    old = {k: getattr(FLAGS, k) for k in kwargs}
    FLAGS.update(**kwargs)
    try:
        yield FLAGS
    finally:
        FLAGS.update(**old)
