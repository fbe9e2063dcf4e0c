"""Trainer runtime: per-pass training loop with host/device pipelining.

Reference: framework/boxps_trainer.cc (BoxPSTrainer::Run :282 — worker per
device) + boxps_worker.cc (TrainFiles :1278 hot loop, NaN guard :1326,
AddAucMonitor :1267) + the Python surface ``exe.train_from_dataset``
(python/paddle/fluid/executor.py:2412).

TPU-native redesign: instead of one CPU thread per GPU running an op
interpreter, ONE jit step consumes the whole device mesh (data parallelism
lives inside the step as shardings, §parallel); the host side is a prefetch
thread doing what the reference's DataFeed+dedup CUDA kernels did — batch
build + key dedup + row assignment — overlapped with device compute through
a bounded channel.
"""

from __future__ import annotations

import math
import threading
import time
from typing import (Any, Callable, Dict, Iterable, Iterator, List,
                    Optional, Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from paddlebox_tpu.config import FLAGS
from paddlebox_tpu.data.batch import SlotBatch
from paddlebox_tpu.data.dataset import Dataset, InMemoryDataset
from paddlebox_tpu.metrics import (AucResult, MetricRegistry, auc_compute,
                                   init_auc_state)
from paddlebox_tpu.ps.table import EmbeddingTable, PullIndex
from paddlebox_tpu.train.step import (DeviceBatch, SeqTrainStep, StepState,
                                      TrainStep, make_device_batch)
from paddlebox_tpu.utils import Channel, Timer
from paddlebox_tpu.utils.logging import get_logger

log = get_logger(__name__)


class NanInfError(RuntimeError):
    pass


class Trainer:
    """Single-replica trainer (multi-chip variant in parallel/)."""

    def __init__(
        self,
        model,
        table: EmbeddingTable,
        desc,                       # DataFeedDesc
        tx: Optional[optax.GradientTransformation] = None,
        use_cvm: bool = True,
        prefetch: int = 4,
        seed: int = 0,
        lr_map: Optional[dict] = None,
        lr_map_base: float = 1.0,
    ) -> None:
        """``lr_map`` — per-param dense lr overrides, name
        (path-substring) → lr against ``lr_map_base``; implemented by
        chaining a per-leaf update scaler after ``tx``
        (box_wrapper.cc:1303-1335, boxps_worker.cc:199-204)."""
        self.model = model
        self.table = table
        self.desc = desc
        self.tx = tx or optax.adam(1e-3)
        params = None
        if lr_map:
            from paddlebox_tpu.train.dense_modes import (build_lr_scales,
                                                         lr_map_transform)
            params = TrainStep.init_params_for(
                model, desc.batch_size, len(desc.sparse_slots),
                table.mf_dim, desc.dense_dim, use_cvm=use_cvm)
            scales = build_lr_scales(params, lr_map, lr_map_base)
            self.tx = optax.chain(self.tx, lr_map_transform(scales))
        if getattr(model, "sequence_model", False):
            # not (pooled, dense) -> logit: the slot pulled unpooled, an
            # id label and a loss a position (train/step.SeqTrainStep);
            # trained through train_pass_resident
            if lr_map or not getattr(desc, "seq_len", 0):
                raise ValueError("a sequence model takes a sequence feed "
                                 "(desc.seq_len) and no lr_map")
            self.step_fn = SeqTrainStep(model, self.tx, table.cfg,
                                        desc.batch_size, desc.seq_len)
        else:
            self.step_fn = TrainStep(
                model, self.tx, table.cfg, desc.batch_size,
                len(desc.sparse_slots), use_cvm=use_cvm, rng_seed=seed)
        if params is None:
            params = self.step_fn.init_params(table.mf_dim, desc.dense_dim)
        self.state = self.step_fn.init_state(table.state, params,
                                             init_auc_state())
        # table.state now lives inside self.state; keep table's handle in
        # sync lazily (sync_table()) for save/shrink.
        self.metrics = MetricRegistry()
        self.prefetch = prefetch
        self._rng = jax.random.PRNGKey(seed + 1)
        self.global_step = 0
        self._dump_cfg = None
        self._resident_runners: Dict[Any, Any] = {}
        # per-pass stage timers (PrintSyncTimer role, box_wrapper.cc:1182)
        from paddlebox_tpu.utils.profiler import StageTimers
        self.stage_timers = StageTimers()
        # attach flag-selected telemetry sinks (obs/hub; no-op when the
        # telemetry flags are off)
        from paddlebox_tpu.obs.hub import configure_from_flags
        configure_from_flags()
        # install the env-selected fault plan (no-op without
        # FLAGS.fault_plan; chaos runs need no code changes)
        from paddlebox_tpu.resilience.faults import install_from_flags
        install_from_flags()
        # graceful preemption: SIGTERM/SIGINT become a stop flag the
        # pass loop honors at batch boundaries (resilience/preemption)
        if FLAGS.graceful_shutdown:
            from paddlebox_tpu.resilience import preemption
            preemption.install_signal_handlers()
        self._pass_seq = 0
        # optional per-batch hook, called AFTER the step's state update
        # with the host SlotBatch — streaming record accounting and the
        # at-least-once gates (scripts/stream_check.py) key off it
        self.on_batch_trained: Optional[Callable[[SlotBatch], None]] = None
        # per-window hook for the online daemon (online.OnlineLearner):
        # called from _stream_loop AFTER a window's accounting/telemetry
        # and BEFORE the boundary-save decision, with the completed
        # window index and the dataset — the shrink scheduler and
        # /healthz bookkeeping run here, never mid-pass
        self.on_window_complete: Optional[Callable[[int, object],
                                                   None]] = None
        # set (by the hook) to publish a boundary checkpoint at THIS
        # window boundary regardless of the stream_ckpt_every_windows
        # cadence — a shrink cycle must persist before training resumes
        self.stream_save_now = False
        # set to force the next stream-boundary save to a BASE: shrink
        # decays EVERY row without marking it touched, so a delta save
        # would silently miss the decay on untouched rows and a restore
        # would diverge from the live table. Cleared only after a save
        # actually lands (the no-op dedup path keeps it pending).
        self.stream_force_base = False
        # lifecycle bookkeeping published into every checkpoint cursor
        # (and the boundary artifact manifest): shrink cycle count,
        # last shrink window/rows, live rows — a restore replays to the
        # same live-key set and the daemon resumes its cadence from it
        self.lifecycle: Optional[Dict[str, float]] = None
        # elastic membership poll (train/multihost.ElasticController
        # .poll or equivalent): called at every completed window
        # boundary, AFTER on_window_complete and BEFORE the save
        # decision. A truthy decision is a scale event: the loop
        # publishes a boundary checkpoint and returns (coordinated
        # stop) so the launcher can rebuild the world at the new size
        # and resume from the stream cursor — membership is only ever
        # acted on at completed boundaries, never mid-pass
        self.stream_membership: Optional[Callable[[], object]] = None

    # ---- host-side prefetch: batch build + dedup + row assign + H2D ----
    def _prefetch_iter(
        self, batches: Iterable[SlotBatch], prepare=None,
    ) -> Iterator[Tuple[SlotBatch, DeviceBatch]]:
        """Two chained producer threads — stage 1 does dedup + row assign
        (mutates the host index, so single-threaded), stage 2 does the
        device transfer — so the main thread only dispatches jit steps.
        This is the role split of the reference's DataFeed read thread +
        MiniBatchGpuPack H2D stage, with both overlapped against device
        compute through bounded channels."""
        from paddlebox_tpu.utils.prefetch import prefetch_iter
        prep = prepare or self.table.prepare
        st = self.stage_timers

        def do_prep(b):
            with st.stage("prepare"):
                return b, prep(b)

        def do_h2d(t):
            with st.stage("h2d"):
                return t[0], make_device_batch(t[0], t[1])

        prepared = prefetch_iter(batches, do_prep, capacity=self.prefetch,
                                 name="trainer.prepare")
        return prefetch_iter(prepared, do_h2d, capacity=self.prefetch,
                             name="trainer.h2d")

    def set_dump(self, cfg) -> None:
        """Enable per-sample prediction dump for subsequent passes
        (dump_fields, boxps_worker.cc:1595; pass None to disable)."""
        self._dump_cfg = cfg

    def dump_param(self, path: str) -> int:
        """Named dense-parameter dump (DumpParam, boxps_worker.cc:1633)."""
        from paddlebox_tpu.utils.dump import dump_param
        return dump_param(self.state.params, path)

    def train_pass(self, dataset: Dataset, log_prefix: str = "",
                   checkpoint=None,
                   start_cursor: Optional[dict] = None
                   ) -> Dict[str, float]:
        """One pass over the dataset — train_from_dataset analogue.

        Preemption-safe (docs/RESILIENCE.md §Preemption & mid-pass
        resume): the loop polls the graceful-stop flag at every batch
        boundary; a stop finishes the in-flight step, writes an
        emergency checkpoint with a resume cursor (when ``checkpoint``
        is a CheckpointManager and the dataset's batch order is
        deterministic) and raises ``PreemptedError``. With
        ``FLAGS.ckpt_every_batches > 0`` the same cursor checkpoint is
        also written periodically, bounding replay after a HARD kill.
        ``start_cursor`` (from ``CheckpointManager.load_cursor``)
        resumes a preempted pass: the already-trained batch prefix is
        skipped instead of replayed."""
        from paddlebox_tpu.resilience import preemption
        timer = Timer()
        timer.start()
        self.stage_timers.reset()  # this pass's stages only (report below)
        nb = 0
        stats = None
        dump_writer = None
        if self._dump_cfg is not None:
            from paddlebox_tpu.utils.dump import DumpWriter
            dump_writer = DumpWriter(self._dump_cfg)
        n_ex = 0
        st = self.stage_timers
        skip = 0
        if start_cursor is not None:
            skip = int(start_cursor.get("batch_index", 0))
            log.info("%sresuming pass from cursor: skipping %d "
                     "already-trained batches (step %d)", log_prefix,
                     skip, self.global_step)
        cursor_ok = (checkpoint is not None
                     and getattr(dataset, "supports_cursor_resume",
                                 False))
        # consumption feedback for windowed streams: fold a window into
        # the completed set only once its last batch has TRAINED (the
        # reader group runs ahead of training; docs/RESILIENCE.md
        # §Streaming)
        note_consumed = getattr(dataset, "note_batches_consumed", None)
        every = FLAGS.ckpt_every_batches if cursor_ok else 0
        last_save = (-1, None)  # (batch_index, path) of the newest save
        for batch, dev in self._prefetch_iter(
                dataset.batches(start_batch=skip) if skip
                else dataset.batches()):
            n_ex += int((batch.show > 0).sum())
            self.global_step += 1
            rng = jax.random.fold_in(self._rng, self.global_step)
            # "step" times the jit DISPATCH (host cost of launching the
            # fused step; device completion is async) — with prepare/h2d
            # on the prefetch threads, a slow pass now attributes to
            # host dispatch vs starved prefetch vs device-bound
            with st.stage("step"):
                self.state, stats = self.step_fn(self.state, dev, rng)
            nb += 1
            if note_consumed is not None:
                note_consumed(nb)
            if self.on_batch_trained is not None:
                self.on_batch_trained(batch)
            if len(self.metrics):
                # AddAucMonitor hook: feed registered metric variants.
                # Side channels stay HOST numpy — device metrics convert
                # on device, host metrics (wuauc) avoid a round trip;
                # pred stays the device array (host metrics sync on it).
                ins_w = (batch.show > 0).astype(np.float32)
                with st.stage("metrics"):
                    self.metrics.add_batch(
                        stats["pred"], batch.label, ins_w,
                        uid=batch.uid, rank=batch.rank, cmatch=batch.cmatch)
            if dump_writer is not None and nb % self._dump_cfg.interval == 0:
                dump_writer.add_batch(
                    batch.ins_ids,
                    {"pred": stats["pred"], "label": batch.label,
                     "show": batch.show, "clk": batch.clk},
                    int((batch.show > 0).sum()))
            # loss fetch forces a device sync — only on guard/log steps
            if FLAGS.check_nan_inf or nb % FLAGS.log_period_steps == 0:
                loss = float(stats["loss"])
                if math.isnan(loss) or math.isinf(loss):
                    # reference aborts and dumps scope (boxps_worker.cc:1326)
                    raise NanInfError(
                        f"nan/inf loss at step {self.global_step}")
                if nb % FLAGS.log_period_steps == 0:
                    log.info("%spass step %d loss=%.5f", log_prefix,
                             self.global_step, loss)
            # ---- batch boundary: periodic cursor checkpoint + stop poll
            if every > 0 and nb % every == 0:
                last_save = (skip + nb,
                             self._save_inpass(checkpoint, dataset,
                                               skip + nb,
                                               reason="periodic"))
            if preemption.stop_requested():
                # the dispatched step is already folded into self.state;
                # snapshot it, mark the restart, and exit the pass
                if dump_writer is not None:
                    dump_writer.close()  # flush buffered dump records
                path = None
                if cursor_ok:
                    if last_save[0] == skip + nb:
                        # the periodic save already snapshotted THIS
                        # boundary — a second save at the same step
                        # would only churn (or demote a base to delta)
                        path = last_save[1]
                        from paddlebox_tpu.obs.hub import get_hub
                        if get_hub().active:
                            get_hub().emit(
                                "emergency_checkpoint",
                                reason="preempt", reused=True,
                                batch_index=int(skip + nb),
                                global_step=int(self.global_step),
                                path=path)
                    else:
                        path = self._save_inpass(checkpoint, dataset,
                                                 skip + nb,
                                                 reason="preempt")
                    preemption.write_resume_marker(
                        checkpoint.root, step=int(self.global_step),
                        batch_index=skip + nb,
                        reason=preemption.stop_reason())
                else:
                    log.warning(
                        "%sstop requested but no checkpoint manager / "
                        "deterministic dataset — exiting WITHOUT an "
                        "emergency checkpoint (pass will replay)",
                        log_prefix)
                raise preemption.PreemptedError(
                    f"preempted ({preemption.stop_reason()}) at batch "
                    f"{skip + nb}, step {self.global_step}"
                    + ("" if path is None else f"; emergency checkpoint "
                       f"{path}"),
                    step=int(self.global_step), batch_index=skip + nb,
                    checkpoint_path=path)
        last_loss = float(stats["loss"]) if stats is not None else float("nan")
        if dump_writer is not None:
            dump_writer.close()
        timer.pause()
        self.sync_table()
        if note_consumed is not None:
            # the loop has fully drained the generator, so every window
            # mark is set by now — fold the tail window the in-loop
            # note may have raced (its mark lands when the producer
            # thread resumes past the final yield)
            note_consumed(nb)
        streaming = (getattr(dataset, "stream_cursor_state", None)
                     is not None and getattr(dataset, "windowed", False))
        if cursor_ok and (last_save[0] >= 0 or skip > 0
                          or (streaming and start_cursor is not None)):
            # the pass completed after writing (or resuming from) a
            # mid-pass cursor checkpoint: publish a pass-boundary
            # checkpoint so the newest restorable state does not resume
            # into a pass that already finished. For a windowed stream
            # the boundary checkpoint still carries the STREAM cursor
            # (completed files, empty open window) — losing the
            # completed-file set here would retrain the whole stream on
            # the next restart.
            kw = {}
            if streaming:
                kw = dict(cursor=self._boundary_cursor(dataset),
                          clear_touched=True,
                          metrics=(self.metrics if len(self.metrics)
                                   else None))
            try:
                checkpoint.save(self, delta=checkpoint.has_base(), **kw)
            except ValueError:
                # the cadence hit the pass length exactly and the save
                # at this step is the first BASE — a delta re-save over
                # it is refused, so supersede it with a fresh base
                checkpoint.save(self, delta=False, **kw)
        res = auc_compute(self.state.auc)
        out = res.as_dict()
        # ex/s counts THIS pass's instances (res.ins_num is cumulative
        # across passes until reset_metrics, like the reference registry)
        out.update(batches=nb, examples=n_ex,
                   elapsed_sec=timer.elapsed_sec(),
                   examples_per_sec=n_ex / max(timer.elapsed_sec(), 1e-9),
                   last_loss=last_loss)
        log.info("%spass done: %d batches, %.0f ex/s, auc=%.4f",
                 log_prefix, nb, out["examples_per_sec"], res.auc)
        if FLAGS.profile:
            self.stage_timers.report(log_prefix)  # PrintSyncTimer role
        self._emit_pass("train_pass", out, n_ex, stage_timers=True)
        return out

    # ---- mid-pass resume cursor glue (docs/RESILIENCE.md) ----
    def _pass_cursor(self, dataset, batch_index: int) -> dict:
        """The resume cursor stored with an in-pass checkpoint: enough
        to restart THIS pass at ``batch_index`` — the file-list identity
        + quarantine decisions pin the data, global_step pins both the
        trainer position and the per-step rng fold
        (``fold_in(rng, global_step)``), and the AUC/metric accumulators
        ride the checkpoint itself (dense.pkl / metrics.pkl).

        Schema v2 (backward-compatible: v1 cursors — no ``version`` —
        keep their batch-index semantics): windowed streaming datasets
        add a ``stream`` block (completed files + open window,
        ``QueueDataset.stream_cursor_state``) — resume then skips
        completed files and replays the open window at-least-once
        instead of splicing by batch index."""
        cur = {
            "version": 2,
            "pass_seq": int(self._pass_seq) + 1,
            "fingerprint": dataset.filelist_fingerprint(),
            "files_consumed": len(getattr(dataset, "filelist", [])),
            "batch_index": int(batch_index),
            "global_step": int(self.global_step),
            "rng_fold": int(self.global_step),
            "quarantined_files": sorted(
                p for p, _ in getattr(dataset, "quarantined_files", [])),
        }
        state_fn = getattr(dataset, "stream_cursor_state", None)
        if state_fn is not None:
            s = state_fn(int(batch_index))
            if s is not None:
                cur["stream"] = s
        if self.lifecycle is not None:
            # shrink/aging decisions ride EVERY cursor (boundary and
            # emergency alike) so a restore replays to the same
            # live-key set and the daemon's cadence survives resume
            cur["lifecycle"] = dict(self.lifecycle)
        return cur

    def _boundary_cursor(self, dataset) -> Optional[dict]:
        """The cursor a BETWEEN-PASS checkpoint of a windowed streaming
        dataset must carry (completed files, empty open window) so a
        restart skips every consumed file; None for non-streaming
        datasets (their boundary checkpoints stay cursor-free)."""
        state_fn = getattr(dataset, "stream_cursor_state", None)
        if state_fn is None or not getattr(dataset, "windowed", False):
            return None
        return self._pass_cursor(dataset, 0)

    def _save_inpass(self, checkpoint, dataset, batch_index: int,
                     reason: str) -> str:
        """Write a mid-pass checkpoint (delta once a base exists) with
        the resume cursor + metric snapshot."""
        path = checkpoint.save(
            self, delta=checkpoint.has_base(),
            cursor=self._pass_cursor(dataset, batch_index),
            metrics=self.metrics if len(self.metrics) else None)
        from paddlebox_tpu.obs.hub import get_hub
        hub = get_hub()
        event = ("emergency_checkpoint" if reason == "preempt"
                 else "inpass_checkpoint")
        hub.counter("pbox_inpass_checkpoints_total",
                    "mid-pass cursor checkpoints written").inc(
                        reason=reason)
        if hub.active:
            hub.emit(event, reason=reason, batch_index=int(batch_index),
                     global_step=int(self.global_step), path=path)
        return path

    def _adopt_cursor(self, checkpoint, dataset,
                      step: Optional[int] = None) -> Optional[dict]:
        """Cursor for the trainer's CURRENT position, validated against
        this dataset. Returns the cursor to resume from, or None for a
        full pass. A cursor at our step whose data identity mismatches
        (different file list / different quarantine outcome) is
        dangerous — resuming would splice two different batch streams —
        so the trainer rolls BACK to the latest pass-boundary
        checkpoint instead. The same applies when the dataset cannot
        resume at all (non-deterministic batch order): the trainer
        sits on MID-PASS state, and training a "full" pass from it
        would double-train the consumed prefix."""
        cur = checkpoint.load_cursor(step)
        if cur is None:
            return None
        if int(cur.get("global_step", -1)) != int(self.global_step):
            return None  # cursor belongs to a different position
        reason = None
        stream = cur.get("stream")
        stream = stream if isinstance(stream, dict) else None
        if stream is not None:
            # v2 STREAM cursor: resume is by file window, not batch
            # index — validate that the current filelist still extends
            # the cursor's consumption order (completed files then the
            # open window, quarantined files excluded on both sides)
            if (getattr(dataset, "adopt_stream_cursor", None) is None
                    or not getattr(dataset, "windowed", False)):
                reason = ("cursor belongs to a windowed stream but this "
                          "dataset is not a windowed QueueDataset "
                          "(FLAGS.stream_window_files)")
            else:
                from paddlebox_tpu.data.dataset import chain_digest
                quar = set(cur.get("quarantined_files", []))
                fold = stream.get("files_folded") or {}
                nfold = int(fold.get("count", 0) or 0)
                expect = [str(f) for f in
                          list(stream.get("files_completed", []))
                          + list(stream.get("window_files", []))
                          if str(f) not in quar]
                avail = [f for f in dataset.filelist if f not in quar]
                if nfold and (len(avail) < nfold or chain_digest(
                        "", avail[:nfold]) != fold.get("sha256")):
                    reason = ("stream folded-history fingerprint "
                              "mismatch — the filelist's leading files "
                              "no longer reproduce the cursor's "
                              "compacted consumption prefix")
                elif avail[nfold:nfold + len(expect)] != expect:
                    reason = ("stream file prefix changed — the "
                              "filelist no longer extends the cursor's "
                              "consumption order")
        elif not getattr(dataset, "supports_cursor_resume", False):
            reason = ("dataset batch order is not deterministic "
                      "(supports_cursor_resume is False)")
        else:
            fp = dataset.filelist_fingerprint()
            quar = sorted(p for p, _ in dataset.quarantined_files)
            if (cur.get("fingerprint") != fp
                    or sorted(cur.get("quarantined_files", [])) != quar):
                reason = "fingerprint/quarantine changed"
        if reason is not None:
            boundary = checkpoint.latest_boundary_step()
            if boundary is None:
                # no pass-boundary state exists: replaying a "full"
                # pass from mid-pass state would double-train the
                # consumed prefix — unrecoverable automatically
                raise RuntimeError(
                    f"mid-pass cursor cannot be resumed ({reason}) and "
                    "no pass-boundary checkpoint exists to roll back "
                    "to — restart from scratch or restore the original "
                    "file list / deterministic load settings")
            log.warning(
                "mid-pass cursor at step %s cannot be resumed (%s) — "
                "rolling back to pass-boundary step %s",
                self.global_step, reason, boundary)
            checkpoint.restore(self, step=boundary)
            return None
        if stream is not None:
            fold = stream.get("files_folded") or {}
            nfold = int(fold.get("count", 0) or 0)
            completed = [str(f) for f in stream.get("files_completed",
                                                    [])]
            dsc = getattr(dataset, "files_completed", None)
            # with a folded history the cursor names only the tail —
            # the folded prefix was fingerprint-checked above, so the
            # dataset sits at the cursor iff lengths line up and the
            # named tail matches
            if (not stream.get("window_files") and dsc is not None
                    and len(dsc) == nfold + len(completed)
                    and dsc[nfold:] == completed):
                # in-process continuation at a stream BOUNDARY: the
                # dataset already sits exactly where the cursor points
                # (the previous window's boundary save) — nothing to
                # adopt, and counting it as a "resume" would bury the
                # real replay events in per-window noise. Still consume
                # a leftover resume marker (a restart whose kill landed
                # before anything trained matches this branch too).
                from paddlebox_tpu.resilience import preemption
                preemption.clear_resume_marker(checkpoint.root)
                return None
            # skip completed files, replay the open window from its
            # start (at-least-once), and carry the quarantine decisions
            # forward; batch_index is forced to 0 — there is no batch
            # splice in a thread-interleaved stream
            dataset.adopt_stream_cursor(
                stream, quarantined=cur.get("quarantined_files", []))
            cur = dict(cur, batch_index=0)
        mr = checkpoint.load_metrics(step)
        if mr is not None:
            self.metrics = mr
        from paddlebox_tpu.resilience import preemption
        preemption.clear_resume_marker(checkpoint.root)
        from paddlebox_tpu.obs.hub import get_hub
        hub = get_hub()
        hub.counter("pbox_cursor_resumes_total",
                    "passes resumed mid-pass from a cursor").inc()
        if hub.active:
            fields = {}
            if stream is not None:
                fields = dict(
                    stream=True,
                    files_completed=nfold + len(
                        stream.get("files_completed", [])),
                    folded_files=nfold,
                    replay_files=len(stream.get("window_files", [])))
            hub.emit("cursor_resume",
                     global_step=int(self.global_step),
                     batch_index=int(cur.get("batch_index", 0)),
                     pass_seq=cur.get("pass_seq"), **fields)
        return cur

    def _reject_cursor_state(self, checkpoint) -> None:
        """Resident-mode guard: a trainer sitting on a MID-PASS cursor
        checkpoint cannot hand the pass to ``train_pass_resident`` (one
        device program — no mid-pass entry point); training a "full"
        pass from mid-pass state would double-train the consumed
        prefix. Roll back to the pass boundary, or refuse."""
        cur = checkpoint.load_cursor()
        if cur is None or int(cur.get("global_step", -1)) \
                != int(self.global_step):
            return
        boundary = checkpoint.latest_boundary_step()
        if boundary is None:
            raise RuntimeError(
                "trainer state is mid-pass (cursor checkpoint) but "
                "resident passes cannot resume mid-pass, and no "
                "pass-boundary checkpoint exists to roll back to — "
                "finish the pass in streaming mode first")
        log.warning(
            "mid-pass cursor at step %s cannot feed a resident pass — "
            "rolling back to pass-boundary step %s", self.global_step,
            boundary)
        checkpoint.restore(self, step=boundary)

    def run_pass(self, dataset: Dataset, checkpoint=None,
                 log_prefix: str = "", resident: bool = False,
                 max_retries: Optional[int] = None) -> Dict[str, float]:
        """``train_pass`` with bounded retry-from-last-checkpoint and
        cursor-aware recovery (docs/RESILIENCE.md §pass-level recovery,
        §Preemption & mid-pass resume).

        A pass that dies on a *recoverable* error (transient IO /
        injected fault) is retried up to ``FLAGS.pass_retry_limit``
        (override with ``max_retries``) times. With a ``checkpoint``
        (CheckpointManager), each retry first rolls the trainer back to
        the last consistent step — and when that step carries a mid-pass
        cursor matching this dataset, the retry REPLAYS ONLY the batches
        after it instead of the whole pass. The same applies on entry:
        a freshly-restored trainer sitting on a cursor checkpoint
        resumes the interrupted pass seamlessly. A ``NanInfError`` is
        only recoverable when a checkpoint can roll the poisoned state
        back — without one, retrying from live NaN state would just
        re-diverge, so it raises immediately. ``PreemptedError`` (a
        deliberate graceful shutdown) is never retried.

        Resident passes run as ONE device program and cannot stop at a
        batch boundary; the stop flag is honored at PASS granularity
        instead — checked before every attempt, so a preempted
        resident job exits (with an inter-pass checkpoint) before
        dispatching the next pass."""
        from paddlebox_tpu.resilience import faults, preemption
        from paddlebox_tpu.resilience.preemption import PreemptedError
        from paddlebox_tpu.resilience.retry import is_retryable
        limit = (FLAGS.pass_retry_limit if max_retries is None
                 else max_retries)
        attempt = 0
        start_cursor = None
        if checkpoint is not None:
            if resident:
                self._reject_cursor_state(checkpoint)
            else:
                # restart path: a launcher that restored to a mid-pass
                # checkpoint resumes the interrupted pass here
                start_cursor = self._adopt_cursor(checkpoint, dataset)
        while True:
            try:
                if preemption.stop_pending():
                    # graceful stop BETWEEN passes/attempts (the only
                    # stop point a resident pass has). Without an
                    # adopted cursor the state sits at a pass boundary
                    # — snapshot it; with one, the mid-pass checkpoint
                    # already on disk covers the state.
                    path = None
                    if checkpoint is not None:
                        if start_cursor is None:
                            # publish the boundary state (windowed
                            # streams carry their boundary cursor so
                            # the restart skips every consumed file;
                            # a step already on disk is reused)
                            path = self._stream_boundary_save(
                                dataset, checkpoint)
                        preemption.write_resume_marker(
                            checkpoint.root, step=int(self.global_step),
                            reason=preemption.stop_reason())
                    raise PreemptedError(
                        f"preempted ({preemption.stop_reason()}) "
                        f"before pass dispatch at step "
                        f"{self.global_step}",
                        step=int(self.global_step),
                        checkpoint_path=path)
                faults.inject("trainer.pass", attempt=attempt)
                if resident:
                    return self.train_pass_resident(dataset, log_prefix)
                return self.train_pass(dataset, log_prefix,
                                       checkpoint=checkpoint,
                                       start_cursor=start_cursor)
            except PreemptedError:
                raise  # deliberate shutdown — the launcher handles it
            except Exception as e:
                # NaN needs a real rollback TARGET, not just a manager:
                # with nothing saved yet, restore() is a no-op and every
                # retry would replay from the poisoned live state. And
                # the target must be a PASS BOUNDARY — a mid-pass cursor
                # checkpoint may itself hold the poison (params go NaN
                # one batch before the loss guard can see it)
                recoverable = (is_retryable(e)
                               or (isinstance(e, NanInfError)
                                   and checkpoint is not None
                                   and checkpoint.latest_boundary_step()
                                   is not None))
                if attempt >= limit or not recoverable:
                    raise
                attempt += 1
                from paddlebox_tpu.obs.hub import get_hub
                hub = get_hub()
                hub.counter("pbox_pass_retries_total",
                            "pass-level recovery retries").inc()
                if hub.active:
                    hub.emit("pass_retry", attempt=attempt, limit=limit,
                             error=repr(e),
                             global_step=self.global_step)
                if checkpoint is not None:
                    if isinstance(e, NanInfError):
                        # the black-box seam (obs/flightrec): a NaN
                        # rollback is a postmortem-worthy anomaly —
                        # dump the recent-event ring + instrument
                        # snapshot BEFORE the restore overwrites the
                        # poisoned state, and book the counter the
                        # nan_rollback alert rule watches
                        from paddlebox_tpu.obs import flightrec
                        hub.counter(
                            "pbox_nan_rollbacks_total",
                            "NaN/Inf passes rolled back to a clean "
                            "boundary").inc()
                        flightrec.trigger(
                            "nan_rollback", reason=repr(e),
                            global_step=self.global_step,
                            attempt=attempt, limit=limit)
                        # mid-pass snapshots are suspect (see above):
                        # roll all the way back to the clean boundary.
                        # A STREAM boundary still carries its stream
                        # cursor — adopt it so the dataset's
                        # completed-file view matches the restored
                        # state (for batch cursors this is a no-op:
                        # boundary checkpoints have no cursor)
                        restored = checkpoint.restore(
                            self, step=checkpoint.latest_boundary_step())
                        start_cursor = self._adopt_cursor(
                            checkpoint, dataset, restored)
                    elif resident:
                        restored = checkpoint.restore(self)
                        self._reject_cursor_state(checkpoint)
                        start_cursor = None
                    else:
                        restored = checkpoint.restore(self)
                        start_cursor = self._adopt_cursor(checkpoint,
                                                          dataset,
                                                          restored)
                    log.warning(
                        "%spass failed (%r) — rolled back to step %s%s, "
                        "retry %d/%d", log_prefix, e, restored,
                        ("" if start_cursor is None else
                         f" (cursor: batch "
                         f"{start_cursor.get('batch_index')})"),
                        attempt, limit)
                else:
                    log.warning(
                        "%spass failed (%r) — no checkpoint manager, "
                        "retrying from current state (%d/%d)",
                        log_prefix, e, attempt, limit)

    # ---- continuous streaming ingest (docs/RESILIENCE.md §Streaming) ----
    def train_stream(self, dataset, checkpoint=None, *,
                     filelist_fn: Optional[Callable[[], Sequence]] = None,
                     max_windows: Optional[int] = None,
                     max_idle_polls: Optional[int] = None,
                     log_prefix: str = "") -> Dict[str, float]:
        """Always-on streaming loop: train arriving files through a
        windowed ``QueueDataset`` (``FLAGS.stream_window_files``), one
        window per pass, forever (or until the source dries up / a
        bound is hit).

        - **Arrivals**: ``filelist_fn()`` is polled for the current file
          list each iteration (new files append in poll order); with no
          ``filelist_fn`` the dataset's static filelist is drained and
          the loop ends. Empty polls emit ``stream_idle`` events and
          back off on the seeded ``RetryPolicy`` schedule
          (site ``stream.poll`` — deterministic per FLAGS.seed);
          arrivals reset the backoff. ``max_idle_polls`` bounds
          consecutive empty polls (None = poll forever).
        - **Checkpoints**: a stream-boundary checkpoint (v2 cursor:
          completed files, empty open window) publishes every
          ``FLAGS.stream_ckpt_every_windows`` completed windows, so a
          hard kill replays at most that many windows.
        - **Preemption** honors the full run_pass contract: SIGTERM
          mid-window raises ``PreemptedError`` after an emergency
          checkpoint whose stream cursor marks the open window; a
          restarted process (``CheckpointManager.restore`` then
          ``train_stream`` again) skips completed files and replays the
          open window AT-LEAST-ONCE — byte-identical to the
          uninterrupted run at the last common window boundary, modulo
          the documented replay window. Stops during the idle loop
          snapshot a boundary cursor the same way.
        - **Telemetry**: ``pbox_stream_{windows,files,replayed_files,
          idle_polls}_total`` counters, the ``pbox_stream_lag_files``
          backlog gauge (pending files not yet dispatched — the
          straggler watchdog's stalled-stream escalation signal), and
          ``stream_window``/``stream_idle`` events.
        """
        from paddlebox_tpu.obs.hub import get_hub
        if not getattr(dataset, "windowed", False):
            raise ValueError(
                "train_stream needs a windowed QueueDataset — set "
                "FLAGS.stream_window_files > 0 (the unbounded "
                "unwindowed stream cannot checkpoint/resume)")
        known: List[str] = [str(f) for f in dataset.filelist]
        # resume: seed the dataset's stream position and the known-file
        # order from the newest stream cursor, so the first window pass
        # reconstructs the cursor's consumption order exactly
        if checkpoint is not None and not dataset.files_completed:
            cur = checkpoint.load_cursor()
            stream = (cur or {}).get("stream")
            if isinstance(stream, dict):
                if int(cur.get("global_step", -1)) \
                        != int(self.global_step):
                    raise RuntimeError(
                        f"stream cursor at step "
                        f"{cur.get('global_step')} does not match "
                        f"trainer step {self.global_step} — restore "
                        "the checkpoint first "
                        "(CheckpointManager.restore) or point at a "
                        "fresh checkpoint root")
                dataset.adopt_stream_cursor(
                    stream,
                    quarantined=cur.get("quarantined_files", []))
                # the dataset expanded any folded (compacted) history
                # back to names from its filelist — read the prefix
                # from it, not from the cursor's (tail-only) block
                prefix = (list(dataset.files_completed)
                          + [str(f) for f in
                             stream.get("window_files", [])])
                seen = set(prefix)
                known = prefix + [f for f in known if f not in seen]
                if not stream.get("window_files"):
                    # a fresh process resuming at a window BOUNDARY
                    # (e.g. after SIGKILL): _adopt_cursor will treat
                    # the now-positioned dataset as an in-process
                    # continuation and stay silent, so this seam is
                    # the only place the restart-resume is visible —
                    # record it (mid-window cursors keep their single
                    # replay event from _adopt_cursor)
                    hub = get_hub()
                    hub.counter(
                        "pbox_cursor_resumes_total",
                        "passes resumed mid-pass from a cursor").inc()
                    if hub.active:
                        hub.emit(
                            "cursor_resume", stream=True,
                            global_step=int(self.global_step),
                            batch_index=0, replay_files=0,
                            files_completed=len(
                                dataset.files_completed))
        hub = get_hub()
        totals = {"windows": 0, "files": 0, "batches": 0,
                  "examples": 0, "replayed_files": 0, "idle_polls": 0}
        try:
            self._stream_loop(dataset, checkpoint, filelist_fn,
                              max_windows, max_idle_polls, log_prefix,
                              known, totals, hub)
        finally:
            # each window pass narrowed the filelist to its consumption
            # order — restore the full known list on EVERY exit
            # (preemption included) so a later train_stream call or
            # pending_files() probe still sees the whole stream
            dataset.set_filelist(known)
        log.info("%sstream done: %d windows, %d files (%d replayed), "
                 "%d batches", log_prefix, totals["windows"],
                 totals["files"], totals["replayed_files"],
                 totals["batches"])
        return totals

    def _stream_loop(self, dataset, checkpoint, filelist_fn,
                     max_windows, max_idle_polls, log_prefix,
                     known: List[str], totals: Dict[str, float],
                     hub) -> None:
        from paddlebox_tpu.resilience import preemption
        from paddlebox_tpu.resilience.retry import RetryPolicy
        wsize = FLAGS.stream_window_files
        since_ckpt = 0
        idle_run = 0
        backoff = iter(())  # armed lazily; reset on every arrival
        while True:
            if max_windows is not None \
                    and totals["windows"] >= max_windows:
                break
            if preemption.stop_pending():
                # idle/between-window stop: run_pass would catch it too,
                # but the poll loop must honor it without pending work —
                # and the snapshot must carry the stream boundary cursor
                self._stream_stop(dataset, checkpoint)
            if filelist_fn is not None:
                have = set(known)
                known.extend(str(f) for f in filelist_fn()
                             if str(f) not in have)
            dataset.set_filelist(known)
            pending = dataset.pending_files()
            hub.gauge("pbox_stream_lag_files",
                      "stream backlog: pending files not yet "
                      "dispatched into a window").set(
                          max(0, len(pending) - wsize))
            if not pending:
                if filelist_fn is None:
                    break
                idle_run += 1
                totals["idle_polls"] += 1
                if max_idle_polls is not None \
                        and idle_run > max_idle_polls:
                    break
                delay = next(backoff, None)
                if delay is None:
                    # (re)arm the seeded schedule; cap attempts high —
                    # the schedule plateaus at retry_max_delay_sec
                    backoff = RetryPolicy.from_flags(
                        site="stream.poll",
                        max_attempts=1 << 20).delays()
                    delay = next(backoff)
                hub.counter("pbox_stream_idle_polls_total",
                            "filelist polls that found no new files"
                            ).inc()
                if hub.active:
                    hub.emit("stream_idle", idle_polls=idle_run,
                             backoff_sec=round(delay, 4),
                             known_files=len(known))
                self._stream_sleep(delay)
                continue
            idle_run = 0
            backoff = iter(())
            window = pending[:wsize]
            # the pass's filelist is exactly the consumption order the
            # cursor records: completed files then this window (files
            # quarantined earlier are excluded from both)
            dataset.set_filelist(dataset.files_completed + window)
            widx = totals["windows"]
            rep0 = int(getattr(dataset, "files_replayed", 0))
            out = self.run_pass(dataset, checkpoint=checkpoint,
                                log_prefix=f"{log_prefix}stream "
                                           f"w{widx}: ")
            # files_replayed is cumulative on the dataset — book the
            # per-window delta so a resumed dataset's history doesn't
            # bleed into this call's totals/events
            replayed = int(getattr(dataset, "files_replayed", 0)) - rep0
            # files CONSUMED, not dispatched: a window file quarantined
            # during this pass never trained, so it must not inflate
            # the throughput totals
            # or desync pbox_stream_files_total from files_completed
            quarantined = {p for p, _ in
                           getattr(dataset, "quarantined_files", [])}
            consumed = [f for f in window if f not in quarantined]
            totals["windows"] += 1
            totals["files"] += len(consumed)
            totals["batches"] += int(out.get("batches", 0))
            totals["examples"] += int(out.get("examples", 0))
            totals["replayed_files"] += replayed
            totals.update({k: out[k] for k in ("auc", "last_loss")
                           if k in out})
            since_ckpt += 1
            hub.counter("pbox_stream_windows_total",
                        "stream windows fully trained").inc()
            hub.counter("pbox_stream_files_total",
                        "files consumed by stream windows").inc(
                            len(consumed))
            if hub.active:
                hub.emit("stream_window", window=widx,
                         files=len(consumed),
                         batches=int(out.get("batches", 0)),
                         lag_files=max(0, len(pending) - len(window)),
                         replayed_files=replayed,
                         global_step=int(self.global_step))
            if self.on_window_complete is not None:
                # the online daemon's boundary work (shrink scheduling,
                # /healthz bookkeeping) — between passes by
                # construction, and BEFORE the save decision so a
                # shrink cycle's stream_save_now/stream_force_base
                # requests take effect at THIS boundary (no training
                # lands between the shrink and its base save)
                self.on_window_complete(int(widx), dataset)
            if self.stream_membership is not None:
                decision = self.stream_membership()
                if decision:
                    # scale event at a COMPLETED boundary: persist the
                    # boundary (checkpoint + stream cursor) and hand
                    # control back — the launcher re-shards to the new
                    # world and resumes from this cursor. No data
                    # rollback: only completed-window state is saved.
                    if checkpoint is not None:
                        self._stream_boundary_save(dataset, checkpoint)
                    totals["membership"] = decision
                    log.warning("stream stop at window %d boundary for "
                                "membership change: %s", widx, decision)
                    return
            if checkpoint is not None and (
                    since_ckpt >= max(1, FLAGS.stream_ckpt_every_windows)
                    or self.stream_save_now):
                self._stream_boundary_save(dataset, checkpoint)
                since_ckpt = 0
                self.stream_save_now = False

    def _stream_boundary_save(self, dataset, checkpoint) -> str:
        """Publish a boundary checkpoint: for a windowed stream it
        carries the stream cursor (completed files, empty open window);
        for any other dataset ``_boundary_cursor`` is None and this is
        a plain cursor-free boundary save. A no-op when this step is
        already on disk (e.g. the window pass published a boundary
        after a mid-pass save or a cursor resume — a re-save would
        refuse as a delta over a base)."""
        if checkpoint.latest_step() == int(self.global_step):
            # NOTE: a pending stream_force_base stays pending through
            # this dedup — the post-shrink state is then captured by
            # the next boundary that actually saves (deterministic
            # either way: a restore replays the shrink at the same
            # windows_completed index)
            return checkpoint._dir(int(self.global_step))
        cursor = self._boundary_cursor(dataset)
        # clear_touched=True only with a stream cursor: a cursor-free
        # boundary save must stay kwarg-free so duck-typed tables whose
        # save surface predates the kwarg (sharded/tiered/multi_mf)
        # keep working on the generic graceful-stop path
        path = checkpoint.save(
            self,
            delta=checkpoint.has_base() and not self.stream_force_base,
            cursor=cursor,
            clear_touched=True if cursor is not None else None,
            metrics=self.metrics if len(self.metrics) else None)
        self.stream_force_base = False
        if cursor is not None:
            # this boundary checkpoint now records every completed file
            # BY NAME — fold them into the compact count+fingerprint
            # form so later cursors stay O(files since this boundary)
            fold = getattr(dataset, "fold_completed_history", None)
            if fold is not None:
                fold()
        return path

    def _stream_stop(self, dataset, checkpoint) -> None:
        """Graceful stop from the stream loop (idle poll / between
        windows): snapshot a stream-boundary checkpoint, write the
        resume marker, raise — the run_pass preemption contract."""
        from paddlebox_tpu.resilience import preemption
        path = None
        if checkpoint is not None:
            path = self._stream_boundary_save(dataset, checkpoint)
            preemption.write_resume_marker(
                checkpoint.root, step=int(self.global_step),
                reason=preemption.stop_reason())
        raise preemption.PreemptedError(
            f"preempted ({preemption.stop_reason()}) in the stream "
            f"loop at step {self.global_step}",
            step=int(self.global_step), checkpoint_path=path)

    @staticmethod
    def _stream_sleep(sec: float) -> None:
        """Stop-aware sleep: wakes early when a graceful stop arrives so
        the grace window is not burned idling."""
        from paddlebox_tpu.resilience import preemption
        deadline = time.monotonic() + sec
        while True:
            if preemption.stop_pending():
                return
            left = deadline - time.monotonic()
            if left <= 0:
                return
            time.sleep(min(0.05, left))

    def _emit_pass(self, kind: str, out: Dict[str, float], examples: int,
                   stage_timers: bool = False) -> None:
        """Per-pass telemetry record (obs/hub.emit_pass_event); returns
        immediately when no sink is attached."""
        from paddlebox_tpu.obs.hub import emit_pass_event, get_hub
        if not get_hub().active:
            return
        self._pass_seq += 1
        emit_pass_event(
            kind, dict(out, global_step=self.global_step,
                       pass_seq=self._pass_seq),
            stage_timers=self.stage_timers if stage_timers else None,
            table=self.table, examples=examples,
            # the quality monitor (obs/quality) diffs the AUC bucket
            # tables per pass for its calibration windows; a bare
            # reference costs nothing when quality is off
            auc_state=getattr(self.state, "auc", None))

    def _feed_registry_resident(self, rp, preds) -> None:
        """Post-pass metric registry feed (the per-batch AddAucMonitor
        hook, replayed from resident predictions + the dataset's
        columnar side channels)."""
        sd = rp.side
        bs = sd["batch_size"]
        r = sd["num_records"]
        preds_h = np.asarray(preds)               # ONE D2H fetch
        for i in range(rp.num_batches):
            a, b = i * bs, min((i + 1) * bs, r)
            m = b - a  # ≥ 1: nb is ceil(r/bs) by construction
            ins_w = (sd["show"][a:b] > 0).astype(np.float32)
            self.metrics.add_batch(
                preds_h[i, :m], sd["label"][a:b], ins_w,
                uid=None if sd["uid"] is None else sd["uid"][a:b],
                rank=None if sd["rank"] is None else sd["rank"][a:b],
                cmatch=(None if sd["cmatch"] is None
                        else sd["cmatch"][a:b]))

    def train_pass_resident(self, pass_or_dataset,
                            log_prefix: str = "") -> Dict[str, float]:
        """One pass in device-resident mode (train/device_pass.py): the
        pass's batches are staged to HBM in bulk and the whole loop runs
        on device via lax.fori_loop — zero per-batch host→device hops.
        Accepts a Dataset (built+uploaded inline) or a prebuilt
        ResidentPass (e.g. from PassPreloader double-buffering).

        Per-sample dumps need host visibility of every batch, which this
        mode gives up by design — with a dump configured, fall back to
        the streaming pass (for a prebuilt ResidentPass that is
        impossible, so raise instead of silently writing no dump)."""
        from paddlebox_tpu.obs import trace
        from paddlebox_tpu.train.device_pass import ResidentPass
        if self._dump_cfg is not None:
            if isinstance(pass_or_dataset, ResidentPass):
                raise ValueError(
                    "dump is configured (set_dump) but a prebuilt "
                    "ResidentPass has no host-side batches to dump — "
                    "pass the Dataset, or set_dump(None)")
            log.warning("dump configured: falling back to streaming "
                        "train_pass for this pass")
            return self.train_pass(pass_or_dataset, log_prefix)
        prebuilt = isinstance(pass_or_dataset, ResidentPass)
        seq = (pass_or_dataset.pass_seq if prebuilt else None) \
            or trace.next_pass_seq()
        # the pass boundary, spanned where the work happens (obs/trace;
        # docs/OBSERVABILITY.md §Tracing): pass.train is the parent of
        # consume (upload, dispatch, device_wait), mark_trained, finish
        with trace.span("pass.train", pass_seq=seq) as sp:
            out, rp = self._train_pass_resident(pass_or_dataset, seq,
                                                log_prefix)
            sp.attrs.update(records=rp.num_records,
                            batches=rp.num_batches)
        return out

    def _train_pass_resident(self, pass_or_dataset, seq: int,
                             log_prefix: str):
        """The body of ``train_pass_resident`` → (result, the pass)."""
        from paddlebox_tpu.obs import trace
        from paddlebox_tpu.train.device_pass import (ResidentPass,
                                                     ResidentPassRunner)
        want_metrics = len(self.metrics) > 0
        timer = Timer()
        timer.start()
        self.stage_timers.reset()
        st = self.stage_timers
        if isinstance(pass_or_dataset, ResidentPass):
            rp = pass_or_dataset
        else:
            with st.stage("build"):
                rp = ResidentPass.build(pass_or_dataset, self.table,
                                        pass_seq=seq)
        trivial = rp.segs is None
        wire = getattr(rp, "wire", "dedup")
        key = (rp.key_capacity, trivial, wire, rp.chunk_bits)
        runner = self._resident_runners.get(key)
        if runner is None:
            runner = ResidentPassRunner(
                self.step_fn, self.table.capacity, trivial, wire=wire,
                num_slots=self.step_fn.num_slots,
                chunk_bits=getattr(rp, "chunk_bits", None))
            self._resident_runners[key] = runner
        # "step" covers dispatch + device completion here (the resident
        # loop is one XLA program; the block is the honest device time).
        # The consume span links back to the pass's build span on the
        # preloader lane (obs/trace — the cross-thread flow arrow)
        with trace.span("pass.consume",
                        link_from=getattr(rp, "_trace_span_id", 0)), \
                st.stage("step"):
            self.state, preds = runner.run_pass(
                self.state, rp, self._rng,
                collect_preds=want_metrics and rp.side is not None)
            with trace.span("pass.device_wait"):
                jax.block_until_ready(self.state.step)
        rp.mark_trained_rows(self.table)
        if want_metrics:
            if rp.side is None:
                log.warning(
                    "registry metrics need columnar side channels — "
                    "this pass was built from a non-columnar dataset; "
                    "use train_pass for metric variants here")
            else:
                with st.stage("metrics"):
                    self._feed_registry_resident(rp, preds)
        self.global_step += rp.num_batches
        timer.pause()
        if getattr(self.step_fn, "step_scalars", ()):
            return self._finish_sequence_pass(rp, runner, timer), rp
        with trace.span("pass.finish") as sp:
            self.sync_table()
            res = auc_compute(self.state.auc)
            out = res.as_dict()
            # how much of the unique axis the pushes visited: read with
            # the AUC, after the device is done (no sync of its own)
            slots, slots_full = runner.push_slots(rp)
            sp.attrs.update(push_slots=slots, push_slots_full=slots_full)
            out.update(push_slots=slots, push_slots_full=slots_full,
                       batches=rp.num_batches,
                       elapsed_sec=timer.elapsed_sec(),
                       examples_per_sec=rp.num_records /
                       max(timer.elapsed_sec(), 1e-9))
            if FLAGS.check_nan_inf and math.isnan(out.get("auc", 0.0)):
                raise NanInfError(f"nan metrics after resident pass "
                                  f"at step {self.global_step}")
            log.info("%sresident pass done: %d batches, %.0f ex/s, "
                     "auc=%.4f", log_prefix, rp.num_batches,
                     out["examples_per_sec"], res.auc)
            self._emit_pass("train_pass_resident", out, rp.num_records,
                            stage_timers=True)
        return out, rp

    def _finish_sequence_pass(self, rp, runner, timer) -> Dict[str, Any]:
        """``pass.finish`` of a sequence model's pass: no AUC; the
        per-step scalars the pass program handed out come off the device
        here, once: each step's loss, and what the model names
        (``model.step_scalars``: name -> ``sum`` or ``mean`` over the
        pass's steps), which go on the span and into the result under
        the model's own names."""
        from paddlebox_tpu.obs import trace
        with trace.span("pass.finish") as sp:
            self.sync_table()
            per_step = np.concatenate(
                [np.asarray(jax.device_get(a)) for a in rp.step_scalars])
            col = {k: per_step[:, i].astype(np.float64)
                   for i, k in enumerate(self.step_fn.step_scalars)}
            slots, slots_full = runner.push_slots(rp)
            counters = dict(
                push_slots=slots, push_slots_full=slots_full,
                tokens=rp.num_records,
                documents=(rp.side or {}).get("documents"))
            for k, fold in getattr(self.step_fn.model, "step_scalars",
                                   {}).items():
                counters[k] = float(getattr(np, fold)(col[k]))
            sp.attrs.update(counters)
            out = dict(counters, loss=float(col["loss"].mean()),
                       losses=col["loss"].tolist(),
                       batches=rp.num_batches,
                       elapsed_sec=timer.elapsed_sec(),
                       examples_per_sec=rp.num_records /
                       max(timer.elapsed_sec(), 1e-9))
            if FLAGS.check_nan_inf and math.isnan(out["loss"]):
                raise NanInfError(f"nan loss after resident pass at step "
                                  f"{self.global_step}")
            log.info("resident pass done: %d steps, %.0f tokens/s, "
                     "loss=%.4f", rp.num_batches, out["examples_per_sec"],
                     out["loss"])
            self._emit_pass("train_pass_resident", out, rp.num_records,
                            stage_timers=True)
        return out

    def train_passes_resident(self, datasets: Iterable[Dataset],
                              depth: Optional[int] = None,
                              floats_dtype=np.float32,
                              checkpoint=None,
                              log_prefix: str = "") -> list:
        """Drive device-resident passes through the depth-N preload
        pipeline (train/device_pass.PassPreloader,
        FLAGS.preload_depth): builds for passes k+1..k+depth run on the
        pipeline worker while pass k trains, so the prologue build
        leaves the pass critical path (docs/PERFORMANCE.md §Deep pass
        pipeline). Returns the per-pass result dicts.

        Preemption-safe at PASS granularity: the stop flag is checked
        before every dispatch; on a stop the preloader DRAINS first (no
        orphan preload H2D contending with the checkpoint's D2H), a
        boundary checkpoint is written when a manager is given, and
        ``PreemptedError`` raises — the run_pass contract."""
        from paddlebox_tpu.resilience import preemption
        from paddlebox_tpu.resilience.preemption import PreemptedError
        from paddlebox_tpu.train.device_pass import PassPreloader
        pre = PassPreloader(iter(datasets), self.table,
                            floats_dtype=floats_dtype, depth=depth)
        pre.start_next()
        results = []
        try:
            while True:
                rp = pre.wait()
                # a stop with an empty queue also lands here (the
                # worker aborts its build and wait() returns None) —
                # it must still raise, not return as if complete
                if rp is None and not preemption.stop_pending():
                    break
                if preemption.stop_pending():
                    pre.drain()
                    if rp is not None and getattr(rp, "dev", None) \
                            is not None:
                        # the popped pass left the queue before drain()
                        # could settle it — wait its wire out too
                        jax.block_until_ready(
                            list(jax.tree.leaves(rp.dev)))
                    path = None
                    if checkpoint is not None:
                        path = checkpoint.save(
                            self, delta=checkpoint.has_base())
                        preemption.write_resume_marker(
                            checkpoint.root, step=int(self.global_step),
                            reason=preemption.stop_reason())
                    raise PreemptedError(
                        f"preempted ({preemption.stop_reason()}) before "
                        f"resident pass dispatch at step "
                        f"{self.global_step}",
                        step=int(self.global_step), checkpoint_path=path)
                pre.start_next()
                results.append(
                    self.train_pass_resident(rp, log_prefix=log_prefix))
        finally:
            pre.drain()
        return results

    def eval_pass(self, dataset: Dataset,
                  log_prefix: str = "") -> Dict[str, float]:
        """Forward-only pass: AUC on frozen params/table, no updates, no
        index growth (reference test-phase / infer semantics)."""
        auc = init_auc_state()
        nb = 0
        timer = Timer()
        timer.start()
        self.stage_timers.reset()
        it = self._prefetch_iter(dataset.batches(),
                                 prepare=self.table.prepare_eval)
        st = self.stage_timers
        for batch, dev in it:
            with st.stage("step"):
                auc, pred = self.step_fn.eval(self.state.table,
                                              self.state.params, auc, dev)
            if len(self.metrics):
                # test-phase metric feed (same hook as train_pass)
                with st.stage("metrics"):
                    self.metrics.add_batch(
                        pred, batch.label,
                        (batch.show > 0).astype(np.float32),
                        uid=batch.uid, rank=batch.rank, cmatch=batch.cmatch)
            nb += 1
        timer.pause()
        res = auc_compute(auc)
        out = res.as_dict()
        out.update(batches=nb, elapsed_sec=timer.elapsed_sec(),
                   examples_per_sec=res.ins_num / max(timer.elapsed_sec(),
                                                      1e-9))
        log.info("%seval pass: %d batches, auc=%.4f", log_prefix, nb,
                 res.auc)
        self._emit_pass("eval_pass", out, int(res.ins_num),
                        stage_timers=True)
        return out

    def sync_table(self) -> None:
        """Write the jit-updated table state back to the EmbeddingTable
        facade (for save/shrink/load host ops)."""
        self.table.state = self.state.table

    def fence_table(self) -> None:
        """Drain the table's async end_pass epilogue (ps/epilogue) and
        surface the first write-back failure; no-op for tables without
        one. NOT called at pass boundaries — that would re-serialize
        the overlap; checkpoint capture and host-tier reads fence
        themselves."""
        fence = getattr(self.table, "fence", None)
        if fence is not None:
            fence()

    def restore_state(self, params, opt_state, auc, step: int) -> None:
        """Rebind dense + metric state after a checkpoint restore (the
        table was already loaded); CheckpointManager's trainer hook."""
        self.state = StepState(table=self.table.state, params=params,
                               opt_state=opt_state, auc=auc,
                               step=jnp.asarray(step, jnp.int32))
        self.global_step = step

    def adopt_table(self) -> None:
        """Point the jit state at the table facade's (re)built state —
        used by the pass lifecycle after begin_pass swaps the working set."""
        self.state = self.state._replace(table=self.table.state)

    def reset_metrics(self) -> None:
        self.state = self.state._replace(auc=init_auc_state())

    # ---- checkpoint glue (dense + sparse) ----
    def save(self, prefix: str) -> None:
        import pickle
        self.sync_table()
        # pass-window tables: drain the async end_pass epilogue so the
        # dump never races an in-flight write-back (CheckpointManager
        # fences the same way)
        self.fence_table()
        self.table.save_base(prefix + ".sparse.npz")
        with open(prefix + ".dense.pkl", "wb") as fh:
            pickle.dump(jax.device_get((self.state.params,
                                        self.state.opt_state)), fh)

    def load(self, prefix: str) -> None:
        import pickle
        self.table.load(prefix + ".sparse.npz")
        with open(prefix + ".dense.pkl", "rb") as fh:
            params, opt_state = pickle.load(fh)
        self.state = StepState(
            table=self.table.state,
            params=jax.device_put(params),
            opt_state=jax.device_put(opt_state),
            auc=self.state.auc, step=self.state.step)
