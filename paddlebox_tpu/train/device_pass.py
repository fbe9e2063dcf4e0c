"""Device-resident pass mode — the pass's batches live in HBM.

Reference architecture: BoxPS stages the PASS into device memory up front
(``BeginPass`` buffers the pass's embeddings into HBM, box_wrapper.cc:171;
``PreLoadIntoMemory``/``WaitFeedPassDone`` double-buffer pass k+1's data
against pass k's training, box_wrapper.h:1142-1156). The per-batch work in
the CUDA path is then only key-copy + PS lookup.

TPU-native redesign: the same pass-window contract, but the staged object
is the pass's BATCH DATA — per-key row ids + dense features for every
batch, uploaded in three bulk transfers — because on TPU the per-batch
host→device hop is the scarce resource (PCIe latency), not HBM.
The train loop then runs as a ``lax.fori_loop`` ON DEVICE: batch slicing,
key dedup (ops/device_unique.py), pull, fwd/bwd, push, dense update and
AUC all inside one XLA program, zero host round-trips per step. The host's
only per-pass jobs are row assignment (native hash index) and the bulk
upload — both overlappable with the previous pass via ``PassPreloader``.

Falls back gracefully: anything this mode can't express (per-step dump
hooks, dynamic NaN aborts mid-pass) still runs via Trainer.train_pass.
"""

from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import wait as futures_wait
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.config import FLAGS
from paddlebox_tpu.data.dataset import Dataset
from paddlebox_tpu.obs import trace
from paddlebox_tpu.ops.bitpack import (pack_delta, pack_delta_auto,
                                       pack_u12, pack_u16m, pack_u18,
                                       pack_u24, unpack_delta16,
                                       unpack_u12, unpack_u16m,
                                       unpack_u18, unpack_u24)
from paddlebox_tpu.ops.chunk_map import cmap_select
from paddlebox_tpu.ops.device_unique import dedup_rows
from paddlebox_tpu.ps.table import (dedup_slotted_first_seen, push_chunk,
                                    push_chunks)
from paddlebox_tpu.train.step import (dequantize_floats, pack_floats,
                                      quantize_floats, unpack_floats)
from paddlebox_tpu.utils.logging import get_logger

log = get_logger(__name__)


class PreloadBuildAborted(RuntimeError):
    """A background pass build observed the graceful-stop flag between
    stages and aborted (resilience/preemption): a 2 s build must not eat
    the SIGTERM grace window. Raised only on NON-main threads (an inline
    main-thread build keeps the run_pass stop protocol in charge); the
    preloader treats it as a clean end-of-stream, never an error."""


_PRELOAD_TLS = threading.local()  # .abort: callable set on worker threads


def poll_preload_abort() -> None:
    """Stop poll for background pass builds — called between build
    stages (front/dedup/pack) and periodically inside long loops.
    Honors both the process-wide graceful-stop flag and the owning
    preloader's stop() (via a worker thread-local). A no-op on the
    main thread and when no stop is pending."""
    abort = getattr(_PRELOAD_TLS, "abort", None)
    if abort is not None and abort():
        raise PreloadBuildAborted("pass build aborted (preloader stop)")
    if threading.current_thread() is threading.main_thread():
        return
    from paddlebox_tpu.resilience import preemption
    if preemption.stop_pending():
        raise PreloadBuildAborted(
            f"pass build aborted ({preemption.stop_reason()})")


def _num_keys(per_batch) -> int:
    """Keys of a pass's per-batch views (the build spans' ``keys``)."""
    return int(sum(len(b[0]) for b in per_batch))


_FLOAT_LANE: Optional[ThreadPoolExecutor] = None
_FLOAT_LANE_LOCK = threading.Lock()


def _float_lane() -> ThreadPoolExecutor:
    """The process's float lane: ONE persistent host thread (lane
    ``preload.floats``), made at the first build that forks. Persistent,
    not a thread a build: a new thread starts on a cold allocator arena,
    so every temporary of the encode is mapped and page-faulted anew —
    on the chip machine's host (no transparent hugepages) a thread a
    build read cell 1's encode at 0.29-0.30 s where this thread reads
    0.18 s, and held the training thread's pop for 5.8 ms where this
    one costs 0.9 (PERF.md section 6, PR 39). Builds of several
    preloaders share it: their float halves then queue, which costs
    overlap and nothing else."""
    global _FLOAT_LANE
    with _FLOAT_LANE_LOCK:
        if _FLOAT_LANE is None:
            _FLOAT_LANE = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="pbox-preload-floats",
                initializer=trace.set_lane,
                initargs=(trace.LANE_PRELOAD_FLOATS,))
        return _FLOAT_LANE


class _FloatHalf:
    """The float half of a streamed build: the encoded block, its
    ``qmeta`` and their two transfers. It shares nothing with the key
    half but the block's shape, so ``fork`` makes it on the float lane
    (``_float_lane``, span ``build.floats``) while the caller goes on
    with the keys; ``make`` makes it on the caller's thread. The key half
    asks ``join()`` for it only where it assembles the pass, so a forked
    build's wall is the longer half, not the sum."""

    def __init__(self) -> None:
        self.batch_size = 0   # floats.shape[1]: known before any encoding
        self.floats = self.qmeta = None
        self.issued: List = []   # floats_t, qm, as far as they were put
        self.sec = 0.0           # the half's own seconds
        self.wait_sec = 0.0      # what join() blocked
        self._fut: Optional[Future] = None

    def make(self, batch_size: int, encode) -> None:
        """``encode() -> (floats, qmeta)``, then the two puts."""
        self.batch_size = batch_size
        t0 = time.perf_counter()
        self.floats, self.qmeta = encode()
        self.issued.append(jax.device_put(self.floats))
        self.issued.append(jax.device_put(
            np.zeros((2, 0), np.float32) if self.qmeta is None
            else self.qmeta))
        self.sec = time.perf_counter() - t0

    def fork(self, batch_size: int, encode) -> None:
        """``make`` on the float lane. The span carries the caller's
        ``pass_seq`` and links to the caller's open span (the
        preloader's ``pass.build``); the lane does not poll for an
        abort: the key half joins it."""
        seq, parent = trace.current_pass_seq(), trace.current_span_id()

        def run() -> None:
            with trace.span("build.floats", pass_seq=seq,
                            link_from=parent):
                self.make(batch_size, encode)

        self.batch_size = batch_size
        self._fut = _float_lane().submit(run)

    def settle(self) -> List:
        """Wait the lane's work out without raising -> the transfers it
        issued (every exit of a build passes here: none leaves the lane
        at work on its pass)."""
        if self._fut is not None:
            futures_wait([self._fut])
        return self.issued

    def join(self):
        """-> (floats, qmeta, floats_t, qm); the lane's error is
        re-raised here, on the build's thread."""
        t0 = time.perf_counter()
        self.settle()
        self.wait_sec += time.perf_counter() - t0
        if self._fut is not None:
            self._fut.result()
        return (self.floats, self.qmeta) + tuple(self.issued)


class ResidentPass:
    """One pass's batches, packed host-side then staged to HBM.

    The pack ships HOST-DEDUPED pull indexes (the DedupKeysAndFillIdx
    step, done once per batch by the native hash index at build time):
    an on-device sort+searchsorted dedup was measured at ~50ms of a 68ms
    step on v5p — ~75% of the whole pass — while the host dedup rides the
    build thread that overlaps the previous pass's training.

    Arrays (nb = #batches, K = uniform per-batch key capacity, U =
    uniform per-batch unique capacity):
      uniq:   int32 [nb, U]      per-batch unique table rows (ascending
              real rows first; padding = DISTINCT out-of-bounds ids, the
              fill_oob_pads contract — gathers clamp to the zero
              sentinel row, scatters drop)
      gidx:   int32 [nb, K]      per-key position in uniq; key padding →
              the first pad position (num_unique)
      floats: f32   [nb, B, D+3] [dense | label | show | clk]
      meta:   int32 [nb, 4]      [num_keys, pad_segment, num_unique,
              first_unique_row (the delta-wire base)]
      segs:   int32 [nb, K] | None   None when every batch has the trivial
              one-key-per-slot layout (segments derived on device)
    """

    def __init__(self, uniq: np.ndarray, gidx: np.ndarray,
                 floats: np.ndarray,
                 meta: np.ndarray, segs: Optional[np.ndarray],
                 num_records: int,
                 qmeta: Optional[np.ndarray] = None,
                 side: Optional[Dict] = None) -> None:
        self.uniq = uniq
        self.gidx = gidx
        self.floats = floats
        self.meta = meta
        self.segs = segs
        self.num_records = num_records
        self.qmeta = qmeta  # f32 [2, D] when floats is the q8 wire
        self.dev: Optional[Tuple[jax.Array, ...]] = None
        # "dedup": uniq/gidx are the host-deduped pull index (default).
        # "compact": built against a slot-arena table — uniq holds the
        # per-key GLOBAL rows, gidx the slot-LOCAL rows; the wire ships
        # the locals + the arena chunk map and the device rebuilds global
        # rows and dedups in-trace (ops/device_unique.py).
        self.wire = "dedup"
        self.chunk_bits: Optional[int] = None
        # the pass's DISTINCT table rows where the build knows them (the
        # compact wire's bulk assign), what mark_trained_rows flags;
        # None: the flags come from uniq
        self.trained_rows: Optional[np.ndarray] = None
        # columnar side channels for the post-pass metric feed (or None)
        self.side = side
        # per-stage build seconds (front/dedup/index_host/pack/h2d;
        # floats/floats_wait where the float half ran on its own lane),
        # set by
        # build_streamed — the preloader mirrors them into
        # pbox_preload_build_seconds_total{stage=...}
        self.build_stats: Optional[Dict[str, float]] = None
        # the pass's identifier on every span of every lane
        # (obs/trace.next_pass_seq), given by whoever makes the pass
        self.pass_seq: Optional[int] = None
        # trips of the counted push loops, one device scalar a program
        # the pass ran as (ResidentPassRunner.run_pass / push_slots)
        self.push_trips: List[jax.Array] = []
        # [steps, len(step.step_scalars)] float32 a program the pass ran
        # as, where the step hands out per-step scalars (a sequence
        # step's losses); on the device until the trainer reads them
        self.step_scalars: List[jax.Array] = []

    @property
    def num_batches(self) -> int:
        return self.gidx.shape[0]

    @property
    def key_capacity(self) -> int:
        return self.gidx.shape[1]

    @property
    def unique_capacity(self) -> int:
        return self.uniq.shape[1]

    @classmethod
    def build(cls, dataset: Dataset, table,
              floats_dtype=np.float32,
              pass_seq: Optional[int] = None) -> "ResidentPass":
        """Pack a dataset's batches; assigns table rows for every key and
        dedups per batch (the FeedPass key registration +
        DedupKeysAndFillIdx steps, both done by the native index).

        ``floats_dtype=jnp.bfloat16`` halves the float block on the wire
        (dense features, label/show/clk — the latter are small integers,
        exact in bf16); the step casts back to f32 on device.

        NOTE: table._touched is deliberately NOT set here — a preloaded
        pass hasn't trained yet, and a checkpoint save landing between
        build and training would clear the flags and lose the pass's
        updates from the next delta. The trainer marks the pass's rows
        touched AFTER the pass runs (mark_trained_rows)."""
        per_batch, floats, qmeta, trivial, nrec, side = cls._front(
            dataset, floats_dtype)
        dedup, u_pad, k_max = cls._dedup_phase(per_batch, table)
        host = cls._pack_chunk(per_batch, dedup, u_pad, k_max, trivial,
                               table.capacity)
        rp = cls(host[0], host[1], floats, host[2], host[3], nrec,
                 qmeta=qmeta, side=side)
        rp.pass_seq = (trace.next_pass_seq() if pass_seq is None
                       else pass_seq)
        return rp

    @classmethod
    def build_streamed(cls, dataset: Dataset, table,
                       floats_dtype=np.float32,
                       threads: int = 4,
                       block: bool = True) -> "ResidentPass":
        """Build with the upload IN FLIGHT. ``jax.device_put`` is async
        on this runtime (measured: the H2D transfer streams while the
        host packs; per-array forced fetches cost a ~0.25 s round-trip
        each). The float block is put before dedup/pack begin, so its
        transfer rides under the host build; the index blocks upload
        CHUNKED (FLAGS.preload_pack_chunk_batches): the wire format is
        chosen once from the dedup results (exactly the choice
        _encode_uniq/_encode_gidx would make on the whole pass), then
        each chunk of batches packs on the thread pool, encodes, and
        starts its H2D transfer while later chunks are still packing —
        pass wall ≈ host build with the tail chunk's transfer exposed,
        instead of build + full index transfer. The device stitches the
        chunks with one concatenate per wire leaf. The only blocking
        wait is one ``block_until_ready`` at the end. Wire bytes match
        upload() exactly; the returned pass is already staged (dev
        set).

        On a background (preloader) thread the build polls the
        graceful-stop flag between stages; an abort waits out the
        already-issued transfers (no orphan H2D competing with the
        emergency checkpoint) before raising PreloadBuildAborted.

        TWO LANES: a columnar feed with a float block
        (``desc.seq_len == 0``) encodes and puts that block on a second
        host thread (``_FloatHalf.fork``: ``build.floats`` on the
        process's float lane, ``preload.floats``) while this thread cuts
        the key views, dedups, walks the index and packs; the halves meet
        where the pass is assembled, so the build's wall is the longer
        half. The
        batch-walking fronts cut keys and floats in one walk, and a
        sequence feed has no block to encode: both stay on this thread.

        Per-stage seconds land in ``rp.build_stats``
        (front/dedup/index_host/pack/h2d; on two lanes
        ``front`` times the key views alone, ``floats`` is the float
        lane's own seconds and ``floats_wait`` what this thread waited
        for it where they meet — docs/PERFORMANCE.md telemetry)."""
        stats: Dict[str, float] = {}
        col = getattr(dataset, "columnar", None)
        forks = col is not None and not getattr(dataset.desc, "seq_len", 0)
        half = _FloatHalf()
        issued: List = []
        try:
            t0 = time.perf_counter()
            if forks:
                desc = dataset.desc
                nb = cls._columnar_batches(desc, col)
                half.fork(desc.batch_size, lambda: cls._columnar_floats(
                    desc, col, nb, floats_dtype))
            with trace.span("build.front") as sp:
                if forks:
                    per_batch, trivial = cls._columnar_keys(desc, col, nb)
                    nrec, side = cls._columnar_side(desc, col)
                else:
                    per_batch, floats, qmeta, trivial, nrec, side = \
                        cls._front(dataset, floats_dtype)
                sp.attrs["keys"] = _num_keys(per_batch)
            stats["front"] = time.perf_counter() - t0
            if not forks:
                half.make(floats.shape[1], lambda: (floats, qmeta))
            rp = cls._build_streamed_tail(
                per_batch, half, trivial, nrec, side, table, threads,
                block, stats, issued)
        except PreloadBuildAborted:
            # drain the transfers this build already issued, the float
            # lane's among them: an orphan H2D in flight would contend
            # with the emergency checkpoint's D2H during the grace window
            jax.block_until_ready(
                list(jax.tree.leaves(issued + half.settle())))
            raise
        finally:
            half.settle()
        if forks:
            stats["floats"] = half.sec
            stats["floats_wait"] = half.wait_sec
        rp.build_stats = stats
        return rp

    @classmethod
    def _build_streamed_tail(cls, per_batch, half: _FloatHalf, trivial,
                             nrec, side, table,
                             threads: int, block: bool,
                             stats: Dict[str, float],
                             issued: List) -> "ResidentPass":
        if getattr(table.index, "arena_enabled", False):
            rp = cls._compact_tail(per_batch, half, trivial, nrec, table,
                                   block=block, side=side, stats=stats)
            if rp is not None:
                return rp
            log.warning("compact wire unavailable for this pass "
                        "(foreign rows or width overflow); using dedup "
                        "wire")
        poll_preload_abort()
        t0 = time.perf_counter()
        with trace.span("build.dedup", keys=_num_keys(per_batch)):
            dedup, u_pad, k_max = cls._dedup_phase(
                per_batch, table, threads, stats=stats)
        t_dedup = time.perf_counter() - t0
        # the index stage (key→row assignment inside the dedup phase)
        # reports separately so the stall breakdown names the actual
        # bottleneck; keep the stages a partition of the build wall
        stats["dedup"] = max(0.0, t_dedup - stats.get("index_host", 0.0))
        poll_preload_abort()
        # wire formats decided ONCE from the dedup results — the exact
        # choice _encode_uniq/_encode_gidx make on the whole pass, so
        # per-chunk encodes are mutually consistent and byte-identical
        # to upload()
        # host pack with each chunk's transfer dispatched as it is
        # encoded (the pack and h2d stage seconds interleave inside)
        with trace.span("build.pack"):
            ufmt = cls._choose_uniq_fmt(dedup, u_pad, table.capacity)
            gfmt = cls._choose_gidx_fmt(per_batch, dedup, k_max)
            nb = len(per_batch)
            step = FLAGS.preload_pack_chunk_batches
            step = nb if step <= 0 else min(step, nb)
            t_pack = t_h2d = 0.0
            uniq_parts: List[tuple] = []
            gidx_parts: List[tuple] = []
            host_parts: List[tuple] = []
            with ThreadPoolExecutor(max_workers=threads) as pool:
                futs = [pool.submit(cls._pack_chunk,
                                    per_batch[a:a + step],
                                    dedup[a:a + step], u_pad, k_max,
                                    trivial, table.capacity)
                        for a in range(0, nb, step)]
                for f in futs:
                    t0 = time.perf_counter()
                    uniq_c, gidx_c, meta_c, segs_c = f.result()
                    t_pack += time.perf_counter() - t0
                    poll_preload_abort()
                    # host encode is pack work; only the device_put
                    # dispatch books as h2d (the stage split exists so
                    # a starved pipeline names its slow stage correctly)
                    t0 = time.perf_counter()
                    ue = cls._encode_uniq_fmt(ufmt, uniq_c, meta_c)
                    ge = cls._encode_gidx_fmt(gfmt, gidx_c)
                    t_pack += time.perf_counter() - t0
                    t0 = time.perf_counter()
                    up = tuple(jax.device_put(a) for a in ue)
                    gp = tuple(jax.device_put(a) for a in ge)
                    issued.extend(up)
                    issued.extend(gp)
                    uniq_parts.append(up)
                    gidx_parts.append(gp)
                    host_parts.append((uniq_c, gidx_c, meta_c, segs_c))
                    t_h2d += time.perf_counter() - t0
            t0 = time.perf_counter()
            if len(host_parts) == 1:
                uniq, gidx, meta, segs = host_parts[0]
                uniq_t, gidx_t = uniq_parts[0], gidx_parts[0]
                t_pack += time.perf_counter() - t0
            else:
                uniq = np.concatenate([p[0] for p in host_parts])
                gidx = np.concatenate([p[1] for p in host_parts])
                meta = np.concatenate([p[2] for p in host_parts])
                segs = (None if trivial else
                        np.concatenate([p[3] for p in host_parts]))
                t_pack += time.perf_counter() - t0
                # stitch the staged chunks device-side: one concatenate
                # per wire leaf, dispatched against the in-flight
                # transfers (device work → the h2d stage, like the puts
                # it chases)
                t0 = time.perf_counter()
                uniq_t = tuple(
                    jnp.concatenate([p[j] for p in uniq_parts], axis=0)
                    for j in range(len(uniq_parts[0])))
                gidx_t = tuple(
                    jnp.concatenate([p[j] for p in gidx_parts], axis=0)
                    for j in range(len(gidx_parts[0])))
                t_h2d += time.perf_counter() - t0
            t0 = time.perf_counter()
            segs_enc = (None if segs is None else
                        cls._encode_segs_or_fallback(segs, meta,
                                                     half.batch_size))
            t_pack += time.perf_counter() - t0
        floats, qmeta, floats_t, qm = half.join()
        with trace.span("build.upload"):
            t0 = time.perf_counter()
            segs_t = ((jax.device_put(np.zeros((1, 1), np.int32)),)
                      if segs_enc is None else
                      tuple(jax.device_put(a) for a in segs_enc))
            rp = cls(uniq, gidx, floats, meta, segs, nrec, qmeta=qmeta,
                     side=side)
            rp.dev = (uniq_t, gidx_t, floats_t, jax.device_put(meta),
                      segs_t, qm)
            issued.extend(jax.tree.leaves(rp.dev))
            if block:
                jax.block_until_ready(list(jax.tree.leaves(rp.dev)))
            # block=False: transfers are ISSUED (device_put is
            # eager/async) and the consuming execution will wait on
            # them — the caller's thread is free to start the NEXT
            # pass's host build while this pass's bytes are still on
            # the wire (PassPreloader does this, overlapping host build
            # k+2 with transfer k+1 and training k)
        stats["h2d"] = t_h2d + (time.perf_counter() - t0)
        stats["pack"] = t_pack
        return rp

    @classmethod
    def _encode_segs_or_fallback(cls, segs, meta, batch_size: int):
        enc = cls._encode_segs_slotwire(segs, meta, batch_size)
        return enc if enc is not None else cls._encode_gidx(segs)

    @classmethod
    def _choose_uniq_fmt(cls, dedup, u_pad: int, cap: int) -> str:
        """The whole-pass uniq wire decision, computed from the dedup
        results BEFORE packing (so chunks can encode+upload as they
        complete): exactly _encode_uniq's preference order — u8 deltas,
        u16 deltas, 16+8-bit halves, raw int32. Exception counts equal
        pack_delta's (per-row gaps over the real ascending prefix), and
        the u24 bound covers the fill_oob_pads tail (max pad id =
        cap + (u_pad - u))."""
        exc8 = exc16 = 0
        vmax = 0
        for uniq_s, _ in dedup:
            u = len(uniq_s)
            d = np.diff(uniq_s.astype(np.int64, copy=False))
            exc8 = max(exc8, int((d >= (1 << 8)).sum()))
            exc16 = max(exc16, int((d >= (1 << 16)).sum()))
            if u:
                vmax = max(vmax, int(uniq_s[-1]))
            if u < u_pad:
                vmax = max(vmax, cap + (u_pad - u))
        if exc8 <= cls._EXC8:
            return "d8"
        if exc16 <= cls._EXC:
            return "d16"
        return "u24" if vmax < (1 << 24) else "raw"

    @staticmethod
    def _choose_gidx_fmt(per_batch, dedup, k_max: int) -> str:
        """_encode_gidx's decision from dedup stats: per-batch max gidx
        is u (the pad value) when the batch has key pads, else u - 1
        (ranks are dense in [0, u))."""
        gmax = 0
        for (keys, *_), (uniq_s, _) in zip(per_batch, dedup):
            u = len(uniq_s)
            gmax = max(gmax, u if len(keys) < k_max else u - 1)
        return ("u18" if gmax < (1 << 18) and k_max % 4 == 0
                else "raw")

    @classmethod
    def _encode_uniq_fmt(cls, fmt: str, uniq: np.ndarray,
                         meta: np.ndarray):
        """Encode a chunk in the pre-chosen whole-pass format (the
        chunked twin of _encode_uniq — same bytes, decided once)."""
        if fmt == "d8":
            out = pack_delta(uniq, meta[:, 2], cls._EXC8, bits=8)
        elif fmt == "d16":
            out = pack_delta(uniq, meta[:, 2], cls._EXC, bits=16)
        elif fmt == "u24":
            return pack_u24(uniq)
        else:
            return (uniq,)
        assert out is not None, "pre-chosen delta wire must fit"
        return out

    @staticmethod
    def _encode_gidx_fmt(fmt: str, gidx: np.ndarray):
        return pack_u18(gidx) if fmt == "u18" else (gidx,)

    @classmethod
    def _compact_tail(cls, per_batch, half: _FloatHalf, trivial: bool,
                      nrec: int, table,
                      block: bool = True,
                      side: Optional[Dict] = None,
                      stats: Optional[Dict[str, float]] = None
                      ) -> Optional["ResidentPass"]:
        """COMPACT wire for slot-arena tables: ship per-key slot-LOCAL
        rows (≈17 bits at CTR scale — at/near the wire's entropy floor)
        plus the tiny arena chunk map; the device rebuilds global rows
        ((chunk_map[slot, local>>CB] << CB) | low bits) and dedups
        in-trace (ops/device_unique.dedup_rows). Eliminates the whole
        per-batch uniq stream and the host sort/rank work; the trade is
        the device's decode + dedup, 0.77 ms a step of 212,992 keys on a
        TPU v5 lite (three sorts and a one-hot product; 5.5 ms before
        PR 36, when it paid four key-wide gathers and scatters). Returns
        None (caller falls back to the dedup wire) when any key's row
        lives outside its slot's arena or the local width overflows 24
        bits."""
        nb = len(per_batch)
        k_max = max(kc for _, _, kc, _, _ in per_batch)
        cap = table.capacity
        n_arena = int(table.arena_slots)
        if any(int(sk.max(initial=0)) >= n_arena
               for _, sk, _, _, _ in per_batch):
            return None  # slots beyond the arena → dedup wire
        locs = np.zeros((nb, k_max), np.int32)
        rows_g = np.full((nb, k_max), cap + 1, np.int32)
        meta = np.zeros((nb, 4), np.int32)
        segs = None if trivial else np.empty((nb, k_max), np.int32)
        t0 = time.perf_counter()
        with trace.span("build.dedup", keys=_num_keys(per_batch)) as sp:
            # whole-pass bulk assign over the pass's DISTINCT (key, slot)
            # pairs: the first-seen dedup runs outside host_lock, then
            # ONE lock round-trip walks the index with the distinct pairs
            # only (a repeat is a lookup, so rows are allocated exactly as
            # a walk of the whole stream allocates them) and the inverse
            # expands rows and locals back to the stream
            keys_u, slots_u, inv = dedup_slotted_first_seen(
                np.concatenate([k for k, *_ in per_batch]),
                np.concatenate([s for _, s, *_ in per_batch]
                               ).astype(np.uint16, copy=False))
            sp.attrs["distinct"] = len(keys_u)
            with table.host_lock:
                r_u, l_u = table.index.assign_slotted(keys_u, slots_u)
                table.slot_host[r_u] = slots_u
            if (l_u < 0).any():
                return None
            bounds = np.cumsum([0] + [len(k) for k, *_ in per_batch])
            for i, (keys, _, _, pad_seg, seg_arr) in enumerate(per_batch):
                nk = len(keys)
                # straight into the pass's arrays ("clip": take buffers
                # its output under the default mode; inv is in range by
                # construction)
                inv_i = inv[bounds[i]:bounds[i] + nk]
                np.take(l_u, inv_i, out=locs[i, :nk], mode="clip")
                np.take(r_u, inv_i, out=rows_g[i, :nk], mode="clip")
                meta[i] = (nk, pad_seg, 0, 0)
                if segs is not None:
                    segs[i, :nk] = seg_arr
                    segs[i, nk:] = pad_seg
        if stats is not None:  # key-assignment stage (the dedup twin)
            stats["dedup"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        with trace.span("build.pack"):
            bits = max(int(locs.max()).bit_length(), 1)
            if bits > 24:
                return None
            with table.host_lock:
                cs_map, cr_map = table.index.arena_export()
            n_slots = int(table.arena_slots)
            valid = cs_map < n_slots  # default (slotless) arena excluded
            stride = int(cr_map[valid].max()) + 1 if valid.any() else 1
            # bucket the stride (power-of-two ladder) so the chunk map's
            # shape — and therefore the compiled runner — stays stable as
            # slots grow new chunks across passes
            from paddlebox_tpu.ps.table import next_bucket
            stride = min(next_bucket(8, stride),
                         (cap >> int(table.arena_chunk_bits)) + 1)
            cmap = np.zeros((n_slots, stride), np.int32)
            cmap[cs_map[valid], cr_map[valid]] = \
                np.nonzero(valid)[0].astype(np.int32)
            loc_t = tuple(jax.device_put(a)
                          for a in cls._encode_locals(locs, bits))
            if segs is None:
                segs_t = (jax.device_put(np.zeros((1, 1), np.int32)),)
            else:
                enc = cls._encode_segs_slotwire(segs, meta,
                                                half.batch_size)
                segs_t = (tuple(jax.device_put(a) for a in enc)
                          if enc is not None else
                          tuple(jax.device_put(a)
                                for a in cls._encode_gidx(segs)))
            cmap_t, meta_t = jax.device_put(cmap), jax.device_put(meta)
        if stats is not None:  # encode + transfer dispatch
            stats["pack"] = time.perf_counter() - t0
        floats, qmeta, floats_t, qm = half.join()
        rp = cls(rows_g, locs, floats, meta, segs, nrec, qmeta=qmeta,
                 side=side)
        rp.wire = "compact"
        rp.chunk_bits = int(table.arena_chunk_bits)
        rp.trained_rows = r_u
        rp.dev = (loc_t, (cmap_t,), floats_t, meta_t, segs_t, qm)
        if block:
            with trace.span("build.upload"):
                jax.block_until_ready(list(jax.tree.leaves(rp.dev)))
        return rp

    @staticmethod
    def _encode_locals(locs: np.ndarray, bits: int):
        """Wire for slot-local rows, narrowest first: u12 byte-pairs
        (1.5 B/key — thousand-slot vocabularies are a few thousand
        entries, the shape whose wire is ~all locals), plain u16,
        16-bit lows + m-bit packed highs (ops/bitpack.pack_u16m), raw
        int32."""
        k = locs.shape[-1]
        if bits <= 12 and k % 2 == 0:
            return pack_u12(locs)
        if bits <= 16:
            return (locs.astype(np.uint16),)
        for m in (1, 2, 4, 8):
            if bits <= 16 + m and k % (8 // m) == 0:
                return pack_u16m(locs, m)
        return (locs,)

    @classmethod
    def _front(cls, dataset: Dataset, floats_dtype):
        """Shared front-end: slice the pass into per-batch key views and
        pack the float block. Returns (per_batch, floats, qmeta, trivial,
        nrec); per_batch entries are (keys, slot_of_key, key_capacity,
        pad_segment, segments-or-None)."""
        col = getattr(dataset, "columnar", None)
        if col is not None:
            return cls._front_columnar(dataset, col, floats_dtype)
        if (floats_dtype == "q8" and FLAGS.q8_streaming_front
                and getattr(dataset, "supports_reiteration", False)):
            # two-phase streaming front: per-column range stats
            # accumulate batch by batch, then a second walk casts each
            # batch straight to the u8 wire — the host never holds a
            # full-pass f32 float block just for the range stats
            # (FLAGS.q8_streaming_front=False restores the staged
            # whole-pass quantization and its winsorized range)
            return cls._front_q8_streaming(dataset)
        per_batch = []
        floats_l = []
        trivial = True
        nrec = 0
        # q8 without a re-iterable dataset stages the whole pass f32
        # for the range stats; other wires cast per batch so the host
        # never holds a full f32 copy
        batch_dtype = np.float32 if floats_dtype == "q8" else floats_dtype
        for b in dataset.batches():
            poll_preload_abort()
            nk = b.num_keys
            slot_of_key = (b.segments[:nk] % b.num_slots).astype(np.int16)
            per_batch.append((b.keys[:nk], slot_of_key, b.key_capacity,
                              b.pad_segment,
                              b.segments[:nk].astype(np.int32, copy=False)))
            floats_l.append(pack_floats(b.dense, b.label, b.show, b.clk,
                                        dtype=batch_dtype))
            nrec += int((b.show > 0).sum())
            trivial = trivial and getattr(b, "segments_trivial", False)
        if not per_batch:
            raise ValueError("empty pass")
        floats = np.stack(floats_l)
        qmeta = None
        if floats_dtype == "q8":
            floats, qmeta = cls._encode_floats(floats, floats_dtype)
        return per_batch, floats, qmeta, trivial, nrec, None

    @classmethod
    def _front_q8_streaming(cls, dataset: Dataset):
        """q8 front without the whole-pass f32 staging: phase 1 walks
        the batches collecting the key views + per-column min/max over
        REAL rows (show > 0, the quantize_floats ``valid`` contract) +
        the exact-u8 label/show/clk checks; phase 2 re-walks the same
        (deterministic, in-memory) batch stream and casts each batch
        straight into the u8 block with the pass-level qmeta. Peak host
        float memory is one batch f32 + the u8 block instead of the
        full pass in f32.

        Divergence from the staged path, by design: the winsorized
        range (quantize_floats' [0.1, 99.9]-percentile clip for
        outlier-dominated columns) needs the full value distribution,
        which streaming min/max cannot see — heavy-tailed columns keep
        the raw min/max range here. When the data doesn't fit the u8
        wire at all, phase 2 falls back to the bf16 cast, exactly like
        _encode_floats."""
        per_batch = []
        trivial = True
        nrec = 0
        lo = hi = None
        n_valid = 0
        first_row = None
        fits = True
        for b in dataset.batches():
            poll_preload_abort()
            nk = b.num_keys
            slot_of_key = (b.segments[:nk] % b.num_slots).astype(np.int16)
            per_batch.append((b.keys[:nk], slot_of_key, b.key_capacity,
                              b.pad_segment,
                              b.segments[:nk].astype(np.int32,
                                                     copy=False)))
            nrec += int((b.show > 0).sum())
            trivial = trivial and getattr(b, "segments_trivial", False)
            d = b.dense.astype(np.float32, copy=False)
            if fits:
                lsc = np.stack([b.label, b.show, b.clk], axis=1)
                if (not np.isfinite(d).all() or (lsc < 0).any()
                        or (lsc > 255).any()
                        or (lsc != np.rint(lsc)).any()):
                    fits = False
            if first_row is None and d.shape[0]:
                first_row = d[:1].copy()
            valid = b.show > 0
            if valid.any():
                stat = d[valid]
                n_valid += stat.shape[0]
                blo, bhi = stat.min(axis=0), stat.max(axis=0)
                lo = blo if lo is None else np.minimum(lo, blo)
                hi = bhi if hi is None else np.maximum(hi, bhi)
        if not per_batch:
            raise ValueError("empty pass")
        if n_valid == 0:  # quantize_floats' stat = d[:1] fallback
            lo = first_row.min(axis=0)
            hi = first_row.max(axis=0)
        if not fits:
            log.warning("q8 float wire: data out of range, using bf16")
            floats = np.stack([
                pack_floats(b.dense, b.label, b.show, b.clk,
                            dtype=jnp.bfloat16)
                for b in dataset.batches()])
            return per_batch, floats, None, trivial, nrec, None
        scale = ((hi - lo) / 255.0)
        scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
        lo = lo.astype(np.float32)
        qmeta = np.stack([scale, lo])
        floats_u8 = None
        for i, b in enumerate(dataset.batches()):
            poll_preload_abort()
            d = b.dense.astype(np.float32, copy=False)
            q = np.clip(np.rint((d - lo[None, :]) / scale[None, :]),
                        0, 255)
            block = np.concatenate(
                [q, np.stack([b.label, b.show, b.clk], axis=1)],
                axis=1).astype(np.uint8)
            if floats_u8 is None:
                floats_u8 = np.zeros((len(per_batch),) + block.shape,
                                     np.uint8)
            floats_u8[i] = block
        return per_batch, floats_u8, qmeta, trivial, nrec, None

    @classmethod
    def _front_columnar(cls, dataset: Dataset, col, floats_dtype):
        """Vectorized whole-pass front for columnar datasets: array slices
        + bulk reshapes — no SlotBatch objects, no per-record python
        (build must stay under the device pass time for the preload to
        fully overlap). The key views, then the float block, on one
        thread; ``build_streamed`` runs the same two halves side by
        side."""
        desc = dataset.desc
        nb = cls._columnar_batches(desc, col)
        per_batch, trivial = cls._columnar_keys(desc, col, nb)
        if getattr(desc, "seq_len", 0):
            return cls._front_sequences(desc, col, per_batch, trivial, nb)
        floats, qmeta = cls._columnar_floats(desc, col, nb, floats_dtype)
        return (per_batch, floats, qmeta, trivial) \
            + cls._columnar_side(desc, col)

    @staticmethod
    def _columnar_batches(desc, col) -> int:
        """Batches of a columnar pass (the tail batch counts)."""
        r = col.num_records
        if r == 0:
            raise ValueError("empty pass")
        return (r + desc.batch_size - 1) // desc.batch_size

    @staticmethod
    def _columnar_keys(desc, col, nb: int):
        """The key half of the columnar front -> (per_batch, trivial):
        reads ``col.keys``, ``col.key_slot`` and ``col.offsets`` only."""
        bs = desc.batch_size
        s = len(desc.sparse_slots)
        r = col.num_records
        offsets = col.offsets
        bounds = offsets[np.minimum(np.arange(nb + 1) * bs, r)]
        nk_arr = np.diff(bounds)
        # resident pass = ONE uniform shape: the fine ladder pads ≤ ~6%
        # instead of the streaming pow2 bucket's ≤ 100% (pure wire waste
        # on ragged passes whose max-K lands just past a pow2 rung)
        from paddlebox_tpu.ps.table import next_bucket_fine
        k_max = next_bucket_fine(desc.key_bucket_min, int(nk_arr.max()))
        counts = np.diff(offsets)
        # trivial layout = exactly one key per slot per record, slot-order:
        # segments are then derivable on device (DeviceBatch.segments)
        trivial = (col.key_slot.size == r * s and bool((counts == s).all())
                   and bool((col.key_slot.reshape(r, s)
                             == np.arange(s, dtype=np.int32)).all()))
        pad_seg = bs * s
        segs_global = None
        if not trivial:
            rec_of_key = np.repeat(np.arange(r, dtype=np.int64), counts)
            segs_global = ((rec_of_key % bs) * s
                           + col.key_slot).astype(np.int32)
        per_batch = []
        for i in range(nb):
            a, b = int(bounds[i]), int(bounds[i + 1])
            per_batch.append((
                col.keys[a:b], col.key_slot[a:b].astype(np.int16),
                k_max, pad_seg,
                None if trivial else segs_global[a:b]))
        return per_batch, trivial

    @classmethod
    def _columnar_floats(cls, desc, col, nb: int, floats_dtype):
        """The float half of the columnar front -> (floats, qmeta): pack
        the whole pass, zero-pad the tail batch, apply the float wire.
        Reads ``col.dense``, ``col.label``, ``col.show`` and ``col.clk``
        only."""
        bs, r = desc.batch_size, col.num_records
        floats_full = pack_floats(col.dense, col.label, col.show, col.clk)
        d3 = floats_full.shape[1]
        if nb * bs != r:
            padded = np.zeros((nb * bs, d3), np.float32)
            padded[:r] = floats_full
            floats_full = padded
        return cls._encode_floats(floats_full.reshape(nb, bs, d3),
                                  floats_dtype)

    @staticmethod
    def _columnar_side(desc, col):
        """-> (nrec, side): the pass's real records, and the side
        channels for the post-pass metric registry feed (record j of
        batch i == columnar row i*bs + j); references, not copies."""
        side = {"label": col.label, "show": col.show, "uid": col.uid,
                "rank": col.rank, "cmatch": col.cmatch,
                "batch_size": desc.batch_size,
                "num_records": col.num_records}
        return int((col.show > 0).sum()), side

    @staticmethod
    def _front_sequences(desc, col, per_batch, trivial: bool, nb: int):
        """The rest of the front for a SEQUENCE feed (``desc.seq_len``):
        a record is one position, its one key the token there and its
        label the id of the next; a batch is whole sequences. In place of
        the float block the pass carries the labels as int32 [nb, B, 1]
        (no dense values; show is 1 at every real position, and a tail
        batch's pad positions carry label -1)."""
        bs, r = desc.batch_size, col.num_records
        if bs % desc.seq_len or not trivial:
            raise ValueError(
                f"a sequence feed takes one key a record and batches of "
                f"whole sequences: batch {bs}, seq_len {desc.seq_len}, "
                f"one-key layout {trivial}")
        labels = np.full(nb * bs, -1, np.int32)
        labels[:r] = col.label
        side = {"num_records": r, "batch_size": bs,
                "documents": (None if desc.bos_key is None else
                              int((col.keys == desc.bos_key).sum()))}
        return (per_batch, labels.reshape(nb, bs, 1), None, trivial, r,
                side)

    @staticmethod
    def _encode_floats(floats: np.ndarray, floats_dtype):
        """Apply the requested float wire to a packed f32 block
        [nb, B, D+3]: "q8" → per-column affine uint8 over the whole pass
        (train/step.quantize_floats; range stats over real rows only —
        show > 0 — so zero-filled batch padding doesn't dilute
        precision; falls back to bf16 when the data doesn't fit), else a
        plain dtype cast."""
        if floats_dtype == "q8":
            nb, b, d3 = floats.shape
            flat = floats.reshape(nb * b, d3)
            q = quantize_floats(flat[:, :-3], flat[:, -3], flat[:, -2],
                                flat[:, -1], valid=flat[:, -2] > 0)
            if q is not None:
                block, qmeta = q
                return block.reshape(nb, b, d3), qmeta
            log.warning("q8 float wire: data out of range, using bf16")
            floats_dtype = jnp.bfloat16
        return floats.astype(floats_dtype, copy=False), None

    @classmethod
    def _dedup_phase(cls, per_batch, table, threads: int = 4,
                     stats: Optional[Dict[str, float]] = None):
        """Pass-level dedup + row assignment (the FeedPass registration +
        DedupKeysAndFillIdx steps). Returns
        ([(uniq_sorted, gidx)] per batch, u_pad, k_max). When ``stats``
        is given, the seconds the table spent under host_lock
        (EmbeddingTable.last_assign_seconds) land in
        ``stats["index_host"]``.

        Concatenate every batch's keys, ONE first-seen dedup + assign
        round-trip under host_lock (EmbeddingTable.bulk_assign_unique —
        the dedup itself runs outside the lock), then the per-batch
        sort/rank splits fan out over a thread pool (numpy releases the
        GIL). New-row allocation order is first-seen over the pass,
        matching a serial batch walk of the native (first-occurrence)
        index row for row."""
        keys_all = np.concatenate([k for k, *_ in per_batch])
        slots_all = np.concatenate([s for _, s, *_ in per_batch])
        rows_u, inv = table.bulk_assign_unique(keys_all, slots_all)
        if stats is not None:
            stats["index_host"] = table.last_assign_seconds["index_host"]
        rows_of_key = rows_u[inv]
        bounds = np.cumsum([0] + [len(k) for k, *_ in per_batch])
        poll_preload_abort()

        def batch_dedup(a, b):
            u, g = np.unique(rows_of_key[a:b], return_inverse=True)
            return (u.astype(np.int32, copy=False),
                    g.astype(np.int32, copy=False))

        with ThreadPoolExecutor(max_workers=threads) as pool:
            dedup = list(pool.map(batch_dedup, bounds[:-1], bounds[1:]))
        u_max = max(len(u) + 1 for u, _ in dedup)
        from paddlebox_tpu.ps.table import next_bucket_fine
        u_pad = next_bucket_fine(table.unique_bucket_min, u_max)
        k_max = max(kc for _, _, kc, _, _ in per_batch)
        return dedup, u_pad, k_max

    @classmethod
    def _pack_chunk(cls, per_batch, dedup, u_pad: int, k_max: int,
                    trivial: bool, cap: int):
        """Pack a run of batches into uniform host arrays
        (uniq, gidx, meta, segs-or-None) — SORTED unique rows so the wire
        ships byte-cut deltas and the table scatter gets nondecreasing
        line indices."""
        from paddlebox_tpu.ps.table import fill_oob_pads
        nb = len(per_batch)
        uniq = np.empty((nb, u_pad), np.int32)
        gidx = np.empty((nb, k_max), np.int32)
        meta = np.empty((nb, 4), np.int32)
        segs = None if trivial else np.empty((nb, k_max), np.int32)
        for i, ((keys, _, _, pad_seg, seg_arr),
                (uniq_s, gidx_i)) in enumerate(zip(per_batch, dedup)):
            nk, u = len(keys), len(uniq_s)
            uniq[i, :u] = uniq_s
            fill_oob_pads(uniq[i], u, cap)
            gidx[i, :nk] = gidx_i
            gidx[i, nk:] = u  # key pads → first OOB pad position
            meta[i] = (nk, pad_seg, u, uniq[i, 0])
            if segs is not None:
                segs[i, :nk] = seg_arr
                segs[i, nk:] = pad_seg
        return uniq, gidx, meta, segs

    def upload(self, materialize: bool = False) -> None:
        """Stage to HBM, bit-packing the index arrays for the wire (H2D
        bandwidth is the scarce resource — ops/bitpack.py): uniq rides as
        16+8-bit halves when rows fit 24 bits, gidx as 16-bit lows plus
        packed 2-bit highs when positions fit 18 bits; the step
        reassembles in-register.

        ``materialize=True`` forces the bytes onto the device NOW (a tiny
        fetch per array): plain ``jnp.asarray`` can defer the copy, and
        the deferred transfer would otherwise serialize into
        the first training step that consumes the pass — the preloader
        materializes from its thread so the transfer rides alongside the
        previous pass's compute."""
        if self.dev is None:
            uniq = tuple(jnp.asarray(a) for a in
                         self._encode_uniq(self.uniq, self.meta))
            gidx = tuple(jnp.asarray(a) for a in
                         self._encode_gidx(self.gidx))
            if self.segs is None:
                segs = (jnp.zeros((1, 1), jnp.int32),)
            else:
                enc = self._encode_segs_slotwire(
                    self.segs, self.meta,
                    self.floats.shape[1])
                segs = tuple(jnp.asarray(a) for a in
                             (enc if enc is not None
                              else self._encode_gidx(self.segs)))
            qm = (jnp.zeros((2, 0), jnp.float32) if self.qmeta is None
                  else jnp.asarray(self.qmeta))
            self.dev = (uniq, gidx, jnp.asarray(self.floats),
                        jnp.asarray(self.meta), segs, qm)
        if materialize:
            # one blocking wait; per-leaf fetches cost ~0.25 s each
            jax.block_until_ready(list(jax.tree.leaves(self.dev)))

    _EXC = 32    # per-batch budget of >=2^16 delta gaps in the u16 wire
    _EXC8 = 64   # per-batch budget of >=2^8 gaps in the u8 wire

    @classmethod
    def _encode_uniq(cls, uniq: np.ndarray, meta: np.ndarray):
        """Wire encoding for the (ascending) per-batch unique rows, in
        preference order: u8 DELTAS + sparse gap exceptions (1 B/value —
        the common case once the table is warm, mean row gap is
        rows_assigned/u), u16 deltas (2 B), 16+8-bit halves (3 B), raw
        int32. The device reconstructs with one cumsum (_make_view).
        Hand-built passes that violate the delta wire's preconditions
        (unsorted rows, old 3-column meta without the base) fall through
        to the order-agnostic encodings."""
        if meta.shape[1] >= 4 and bool((meta[:, 3] == uniq[:, 0]).all()):
            delta = pack_delta_auto(uniq, meta[:, 2], cls._EXC8, cls._EXC)
            if delta is not None:
                return delta
        if int(uniq.max()) < (1 << 24):
            return pack_u24(uniq)
        return (uniq,)

    @staticmethod
    def _encode_gidx(gidx: np.ndarray):
        if (int(gidx.max(initial=0)) < (1 << 18)
                and gidx.shape[1] % 4 == 0):
            return pack_u18(gidx)
        return (gidx,)

    @staticmethod
    def _encode_segs_slotwire(segs: np.ndarray, meta: np.ndarray,
                              batch_size: int):
        """Segment wire for non-trivial layouts, narrowest first.

        GRID wire: when keys are ordered by (record, slot) — the
        BatchBuilder layout — the whole segment stream collapses to
        per-(record, slot) key COUNTS, one u8 [B, S] grid: ~S B/record
        instead of ~1 B/key (ragged at ~5 keys/slot: 130 → 26 B/record).
        The device rebuilds segments with one grid cumsum + boundary-
        mark scatter + key cumsum (the same scatter+cumsum identity as
        the record decode — no searchsorted).

        SLOT wire (fallback): per-key SLOT ids (u8) + per-record key
        COUNTS (u16) — needs only record-grouping, not slot order.

        Preconditions for either (else None → the u18 wire): S ≤ 255,
        pad_segment == B·S, keys record-grouped; GRID additionally needs
        nondecreasing slots within each record and per-cell counts ≤
        255. Pads decode for free in both (indices saturate at B·S)."""
        nb, k = segs.shape
        b = batch_size
        s = int(meta[0, 1]) // b          # pad_segment == bs * S
        if s <= 0 or s > 255 or int(meta[0, 1]) != b * s:
            return None
        rec = segs // s
        # GRID only when it is actually the smaller wire: b*s bytes vs
        # the SLOT wire's k + 2b per batch (sparse many-slot batches —
        # avg keys/record below S — would otherwise ship MORE bytes)
        grid_ok = b * s < k + 2 * b
        grid = (np.zeros((nb, b * s), np.int64) if grid_ok else None)
        counts = np.zeros((nb, b), np.int64)
        for i in range(nb):
            nk = int(meta[i, 0])
            r = rec[i, :nk]
            if nk and (np.diff(r) < 0).any():
                return None               # keys not record-grouped
            if nk and int(r.max()) >= b:
                return None
            if segs[i, nk:].size and (segs[i, nk:] != b * s).any():
                return None               # pads must be the discard bin
            # GRID additionally needs the composite segment id itself
            # to be nondecreasing (slot order within each record)
            if grid_ok and nk and (np.diff(segs[i, :nk]) < 0).any():
                grid_ok = False
            if grid_ok:
                grid[i] = np.bincount(segs[i, :nk], minlength=b * s)
                counts[i] = grid[i].reshape(b, s).sum(axis=1)
            else:
                counts[i] = np.bincount(r, minlength=b)
        if grid_ok and int(grid.max()) <= 255:
            return (grid.reshape(nb, b, s).astype(np.uint8),)
        # (counts are complete either way: grid-path batches derived
        # them from their grid row before any fallback flip)
        if int(counts.max()) > 65535:
            return None
        # numpy out, like every sibling encoder — transfer timing stays
        # with the caller
        return (segs % s).astype(np.uint8), counts.astype(np.uint16)

    def nbytes(self) -> int:
        """Wire bytes (after upload packing; host estimate before)."""
        if self.dev is not None:
            return sum(a.nbytes for a in jax.tree.leaves(self.dev))
        n = (self.uniq.nbytes + self.gidx.nbytes
             + self.floats.nbytes + self.meta.nbytes)
        return n + (self.segs.nbytes if self.segs is not None else 0)

    def mark_trained_rows(self, table) -> None:
        """Flag this pass's rows as touched-since-last-save — called by
        the trainer AFTER the pass runs, so delta saves include them
        regardless of when a checkpoint landed relative to the preload.
        The build's distinct rows where it kept them (no pad, no repeat);
        else a duplicate-tolerant boolean scatter of uniq after dropping
        the OOB pad ids (save paths only read rows the index owns).
        ``rows`` on the span counts what is scattered."""
        with trace.span("pass.mark_trained", pass_seq=self.pass_seq) as sp:
            rows = self.trained_rows
            if rows is None:
                rows = self.uniq.ravel()
                rows = rows[rows <= table.capacity]
            sp.attrs["rows"] = len(rows)
            with table.host_lock:
                table._touched[rows] = True


class _BatchView:
    """Duck-typed DeviceBatch built inside the trace from pass slices."""

    def __init__(self, unique_rows, gather_idx, key_valid, segments,
                 dense, label, show, clk,
                 segments_trivial=False, num_unique=None) -> None:
        self.unique_rows = unique_rows
        # the distinct count on the device, where the unique axis was
        # built there at key-count width (compact wire); None where a
        # host cut the axis to the count's bucket (dedup wire)
        self.num_unique = num_unique
        self.gather_idx = gather_idx
        self.key_valid = key_valid
        self.segments = segments
        self.dense = dense
        self.label = label
        self.show = show
        self.clk = clk
        self.segments_trivial = segments_trivial

    @property
    def pool_segments(self):
        return None if self.segments_trivial else self.segments


class ResidentPassRunner:
    """jits `chunk` steps of a resident pass as ONE device program
    (lax.fori_loop over the staged batches)."""

    def __init__(self, step, capacity: int, trivial_segments: bool,
                 chunk: int = 0, wire: str = "dedup",
                 num_slots: Optional[int] = None,
                 chunk_bits: Optional[int] = None) -> None:
        self.step = step            # TrainStep
        self.capacity = capacity
        self.trivial = trivial_segments
        self.chunk = chunk
        self.wire = wire            # "dedup" | "compact"
        self.num_slots = num_slots  # compact: derive slot = pos % S
        self.chunk_bits = chunk_bits
        self._jit: Dict[int, object] = {}  # n_steps → compiled runner

    @staticmethod
    def _decode_segs(segs, meta=None, k_pad=None):
        """segments arrive raw, as a u18-packed pair (ops/bitpack), as
        the GRID wire (u8 [B, S] per-cell key counts), as the SLOT wire
        (u8 slots + u16 per-record counts — see _encode_segs_slotwire),
        or as a bare array (hand-built passes / direct test calls). The
        kinds are distinguished statically by leaf count/dtype/rank
        (u18 lows are uint16; the GRID leaf is the only 2-D uint8).
        Both count wires decode with the scatter+cumsum identity —
        out[p] = #{cells whose cumulative count <= p} == the
        searchsorted(cum, arange, "right") this replaced: a binary
        search per output slot against one scatter and one prefix sum."""

        def cum_decode(counts_flat, k):
            # empty cells stack duplicate boundary marks, hence .add;
            # positions past the total saturate at the cell count
            cum = jnp.cumsum(counts_flat)
            marks = jnp.zeros(k, jnp.int32).at[cum].add(1, mode="drop")
            return jnp.cumsum(marks)

        if isinstance(segs, tuple):
            if (len(segs) == 1 and segs[0].dtype == jnp.uint8
                    and segs[0].ndim == 2):
                # GRID wire: segment id = owning (record, slot) cell,
                # saturating at B*S == pad_segment for pads
                if k_pad is None:
                    raise ValueError(
                        "GRID segment wire needs k_pad (the padded key "
                        "count) — pass it when calling _decode_segs "
                        "directly")
                return cum_decode(segs[0].reshape(-1).astype(jnp.int32),
                                  k_pad)
            if len(segs) == 2 and segs[0].dtype == jnp.uint8:
                slot = segs[0].astype(jnp.int32)          # [K]
                counts = segs[1].astype(jnp.int32)        # [B]
                k = slot.shape[0]
                s = meta[1] // counts.shape[0]            # pad_seg // B
                # pads: rec saturates at B and slot pads are 0, so the
                # reconstruction lands exactly on pad_segment == B*S
                return cum_decode(counts, k) * s + slot
            if len(segs) == 2:
                return unpack_u16m(segs[0], segs[1], 2)
            return segs[0]
        return segs

    def _make_view(self, uniq_t, gidx_t, floats, meta,
                   segs, qmeta) -> _BatchView:
        if self.wire == "compact":
            return self._make_view_compact(uniq_t, gidx_t[0], floats,
                                           meta, segs, qmeta)
        with jax.named_scope(trace.SCOPE_DECODE):
            if len(uniq_t) == 3:
                # u16-delta wire (ops/bitpack.unpack_delta16); the pad
                # region is derived (fill_oob_pads pattern: distinct,
                # > cap)
                u_pad = uniq_t[0].shape[0]
                upos = jnp.arange(u_pad, dtype=jnp.int32)
                uniq = jnp.where(upos < meta[2],
                                 unpack_delta16(*uniq_t, base=meta[3]),
                                 self.capacity + 1 + upos)
            elif len(uniq_t) == 2:
                uniq = unpack_u24(*uniq_t)
            else:
                uniq = uniq_t[0]
            gidx = (unpack_u18(*gidx_t) if len(gidx_t) == 2
                    else gidx_t[0])
            k = gidx.shape[0]
            num_keys, pad_seg = meta[0], meta[1]
            pos = jnp.arange(k, dtype=jnp.int32)
            if self.trivial:
                segments = jnp.where(pos < num_keys, pos, pad_seg)
            else:
                segments = self._decode_segs(segs, meta, k_pad=k)
            key_valid = (pos < num_keys).astype(jnp.float32)
            dense, label, show, clk = self._decode_floats(floats, qmeta)
        return _BatchView(
            uniq, gidx, key_valid, segments,
            dense=dense, label=label, show=show, clk=clk,
            segments_trivial=self.trivial)

    @staticmethod
    def _decode_floats(floats, qmeta):
        if floats.dtype == jnp.uint8:  # q8 wire (quantize_floats)
            return dequantize_floats(floats, qmeta)
        if floats.dtype == jnp.int32:  # a sequence pass: labels are ids
            label = floats[:, 0]
            show = (label >= 0).astype(jnp.float32)
            return (jnp.zeros((floats.shape[0], 0), jnp.float32), label,
                    show, jnp.zeros_like(show))
        return unpack_floats(floats)

    def _make_view_compact(self, loc_t, cmap, floats, meta, segs,
                           qmeta) -> _BatchView:
        """Decode the compact wire: slot-local rows → global rows via the
        arena chunk map, then in-trace dedup (DedupKeysAndFillIdx on the
        chip — ops/device_unique.py)."""
        with jax.named_scope(trace.SCOPE_DECODE):
            if len(loc_t) == 2:
                k = loc_t[0].shape[-1]
                m = 8 * loc_t[1].shape[-1] // k
                local = unpack_u16m(loc_t[0], loc_t[1], m)
            elif loc_t[0].dtype == jnp.uint8:   # u12 byte-pair wire
                local = unpack_u12(loc_t[0])
            else:
                local = loc_t[0].astype(jnp.int32)
            k = local.shape[-1]
            num_keys, pad_seg = meta[0], meta[1]
            pos = jnp.arange(k, dtype=jnp.int32)
            s = self.num_slots
            if self.trivial:
                segments = jnp.where(pos < num_keys, pos, pad_seg)
            else:
                segments = self._decode_segs(segs, meta, k_pad=k)
            cb = self.chunk_bits
            if self.trivial and k % s == 0:
                # key p is slot p % S's: the keys are a [K/S, S] grid
                # whose column s reads row s of the chunk map alone
                chunk = cmap_select(
                    cmap, (local >> cb).reshape(-1, s),
                    self.capacity >> cb).reshape(-1)
            else:
                slot = (pos if self.trivial else segments) % s
                chunk = cmap.reshape(-1)[slot * cmap.shape[1]
                                         + (local >> cb)]
            rows = (chunk << cb) | (local & ((1 << cb) - 1))
            rows = jnp.where(pos < num_keys, rows, self.capacity)
            key_valid = (pos < num_keys).astype(jnp.float32)
            dense, label, show, clk = self._decode_floats(floats, qmeta)
        with jax.named_scope(trace.SCOPE_DEDUP):
            uniq, gidx, num_unique = dedup_rows(rows, self.capacity)
        return _BatchView(
            uniq, gidx, key_valid, segments,
            dense=dense, label=label, show=show, clk=clk,
            segments_trivial=self.trivial, num_unique=num_unique)

    def _run(self, n_steps: int, collect: bool = False):
        key = (n_steps, collect)
        # scalars the step hands out for every step of the pass (a
        # sequence step's loss and expert loads); none for a click step,
        # whose program is then what it was
        scalars = getattr(self.step, "step_scalars", ())
        if key not in self._jit:
            def run(state, uniq_t, gidx_t, floats_p, meta_p,
                    segs_p, qmeta, start, rng):
                def body(i, carry):
                    state, rng, preds, pushed = carry[:4]
                    # compact wire: gidx slot carries the PASS-global
                    # arena chunk map, not per-batch data — don't index
                    # (slicing the staged pass is the decode's)
                    with jax.named_scope(trace.SCOPE_DECODE):
                        gi = (gidx_t if self.wire == "compact"
                              else tuple(a[i] for a in gidx_t))
                        # one shared index: the packed pair's leading
                        # dims are equal; the modulo only serves the
                        # [1, 1] dummy of the trivial layout
                        si = i % segs_p[0].shape[0]
                        sg = tuple(a[si] for a in segs_p)
                        ui = tuple(a[i] for a in uniq_t)
                        fi, mi = floats_p[i], meta_p[i]
                    view = self._make_view(ui, gi, fi, mi, sg, qmeta)
                    # 1-based like Trainer.train_pass's fold of the
                    # pre-incremented global_step
                    rng_i = jax.random.fold_in(rng, state.step + 1)
                    state, stats = self.step._step(state, view, rng_i)
                    if collect:
                        # per-batch predictions stay resident for the
                        # metric registry feed (AddAucMonitor role)
                        preds = jax.lax.dynamic_update_index_in_dim(
                            preds, stats["pred"], i - start, 0)
                    # trips of the step's counted push; an uncounted
                    # push covers its whole unique axis
                    pushed = pushed + stats.get(
                        "push_chunks",
                        push_chunks(view.unique_rows.shape[0], None))
                    if not scalars:
                        return state, rng, preds, pushed
                    row = jnp.stack([stats[k].astype(jnp.float32)
                                     for k in scalars])
                    return (state, rng, preds, pushed,
                            jax.lax.dynamic_update_index_in_dim(
                                carry[4], row, i - start, 0))

                preds0 = (jnp.zeros((n_steps, floats_p.shape[1]),
                                    jnp.float32) if collect
                          else jnp.zeros((), jnp.float32))
                init = (state, rng, preds0, jnp.zeros((), jnp.int32))
                if scalars:
                    init += (jnp.zeros((n_steps, len(scalars)),
                                       jnp.float32),)
                state, _, preds, pushed, *per_step = jax.lax.fori_loop(
                    start, start + n_steps, body, init)
                return (state, preds, pushed, *per_step)

            self._jit[key] = jax.jit(run, donate_argnums=(0,))
        return self._jit[key]

    def run_pass(self, state, rp: ResidentPass, rng: jax.Array,
                 chunk: Optional[int] = None, collect_preds: bool = False):
        """Run every batch of the staged pass → (state, preds or None);
        ``collect_preds`` returns [nb, B] per-batch device predictions
        (the post-pass metric registry feed). The trips the counted
        pushes made stay on the device, on the pass, for
        ``push_slots``."""
        with trace.span("pass.upload", pass_seq=rp.pass_seq,
                        staged=rp.dev is not None):
            rp.upload()
        nb = rp.num_batches
        c = chunk if chunk is not None else (self.chunk or nb)
        i = 0
        chunks = []
        rp.push_trips = []
        rp.step_scalars = []
        with trace.span("pass.dispatch", pass_seq=rp.pass_seq,
                        chunks=-(-nb // c)):
            while i < nb:
                n = min(c, nb - i)
                state, preds, pushed, *per_step = self._run(
                    n, collect_preds)(
                    state, *rp.dev, jnp.asarray(i, jnp.int32), rng)
                rp.push_trips.append(pushed)
                rp.step_scalars.extend(per_step)
                if collect_preds:
                    chunks.append(preds)
                i += n
        if not collect_preds:
            return state, None
        return state, (chunks[0] if len(chunks) == 1
                       else jnp.concatenate(chunks, axis=0))

    @staticmethod
    def push_slots(rp: ResidentPass) -> Tuple[int, int]:
        """→ (slots of the unique axis the pass's gathers and pushes
        visited, slots of that axis x steps) of a pass that has run: the
        trips its programs counted x ``push_chunk``, held to the second
        (a last trip that overlaps counts whole). They differ only where
        the steps carry their distinct count (``apply_push``'s
        ``num_unique``: the compact wire). Reading pulls the counters
        off the device, so call it once the pass is done."""
        full = rp.num_batches * rp.unique_capacity
        trips = sum(int(t) for t in rp.push_trips)
        return min(trips * push_chunk(rp.unique_capacity), full), full


class PassPreloader:
    """Depth-N pass pipeline — preload_into_memory /
    wait_feed_pass_done (box_wrapper.h:1142-1156) for resident passes:
    ONE persistent worker thread builds + uploads passes ahead of
    training through a bounded queue of ``depth`` passes
    (FLAGS.preload_depth, default 2). Pass k+2's build starts the
    moment k+1's finishes — no join-per-consume, so a slow build no
    longer serializes into the next pass boundary (the depth-1
    alternating-stall pattern).

    With the tiered tables' ASYNC EPILOGUE (ps/epilogue,
    FLAGS.async_end_pass) the steady-state pipeline is FOUR-deep: pass
    k-1's end_pass write-back drains on the epilogue worker, pass k
    trains on device, pass k+1 sits staged in HBM, and this worker
    builds pass k+2 — the pass boundary costs one reconcile+scatter,
    with the prologue build, the H2D wire and the epilogue D2H all off
    the critical path. The epilogue's fence rules keep it safe: a plan
    build here only assigns value-less PENDING rows (plan_scope — legal
    for several queued future passes at once; the window must hold the
    union of the open pass's and every queued pass's working set), and
    the overlapped ``stage`` fetch drains in-flight write-backs before
    reading the host tier (HostStore.read_barrier).

    HBM budget guard: after each build the staged wire bytes
    (``rp.nbytes()``) are measured and the EFFECTIVE depth clamps to
    ``max(1, budget // bytes_per_pass)`` (FLAGS.preload_hbm_budget_mb)
    — an oversized pass degrades the pipeline to double-buffering,
    loudly, instead of stacking passes until HBM OOMs. The clamp is
    monotone (never re-raises) so one giant pass bounds the rest of
    the run conservatively.

    Preemption: the worker polls the graceful-stop flag before every
    build, and the builders poll it between stages
    (poll_preload_abort) — on request_stop the pipeline stops building
    within one stage, already-staged passes stay consumable, and
    ``drain()`` joins the worker so no orphan H2D is in flight at
    emergency-checkpoint time.

    A build failure is held and re-raised by the ``wait()`` that would
    have returned that pass — passes built BEFORE the failure are
    served first (they are valid), and every wait() after the raise
    returns None."""

    def __init__(self, datasets: Iterator[Dataset], table=None,
                 floats_dtype=np.float32, build_fn=None,
                 block_transfers: bool = False,
                 depth: Optional[int] = None,
                 hbm_budget_bytes: Optional[int] = None) -> None:
        """``build_fn(dataset) -> pass`` overrides the default single-chip
        ResidentPass builder — e.g.
        ``build_fn=sharded_trainer.build_resident_pass`` pipelines mesh
        passes the same way. ``depth`` overrides FLAGS.preload_depth;
        ``hbm_budget_bytes`` overrides FLAGS.preload_hbm_budget_mb."""
        if table is None and build_fn is None:
            raise ValueError("need a table or a build_fn")
        self._it = iter(datasets)
        self._table = table
        self._floats_dtype = floats_dtype
        self._build_fn = build_fn
        self._block = block_transfers
        depth = FLAGS.preload_depth if depth is None else depth
        # depth=0 → MANUAL mode: the worker builds one pass per
        # start_next() credit instead of free-running (the depth-1
        # era's strict kick-per-pass protocol)
        self._manual = depth == 0
        self._credits = 0
        self.depth = max(1, depth)
        self._budget = (FLAGS.preload_hbm_budget_mb * (1 << 20)
                        if hbm_budget_bytes is None else hbm_budget_bytes)
        self._cv = threading.Condition()
        self._q: collections.deque = collections.deque()
        self._building = False
        self._exhausted = False   # source iterator drained
        self._stopped = False     # stop()/abort — no further builds
        self._err: Optional[BaseException] = None
        self._worker: Optional[threading.Thread] = None
        self._effective_depth = self.depth
        self.depth_clamped = False
        # cumulative per-stage build seconds + build count (bench)
        self.build_stage_sec: Dict[str, float] = {}
        self.builds = 0
        self.build_sec_total = 0.0
        self.wait_sec_total = 0.0

    # ---- worker --------------------------------------------------------
    def _build(self, ds: Dataset):
        if self._build_fn is not None:
            rp = self._build_fn(ds)
            # forced materialization moves the pass's bytes NOW, riding
            # alongside the open pass's compute (see
            # ResidentPass.upload); a lazy upload would instead
            # serialize into that pass's first step
            with trace.span("build.upload"):
                rp.upload(materialize=True)
            return rp
        # build+upload overlapped; transfers stay IN FLIGHT
        # (block=False) so this thread can start the next pass's host
        # build immediately — the training step consuming the pass
        # waits on its own args
        return ResidentPass.build_streamed(
            ds, self._table, floats_dtype=self._floats_dtype,
            block=self._block)

    def _run(self) -> None:
        from paddlebox_tpu.resilience import preemption
        # lets the builders' stage polls see THIS preloader's stop()
        # (poll_preload_abort) so an in-flight build aborts promptly
        _PRELOAD_TLS.abort = lambda: self._stopped
        trace.set_lane(trace.LANE_PRELOAD)
        while True:
            with self._cv:
                while not self._stopped and (
                        len(self._q) + (1 if self._building else 0)
                        >= self._effective_depth
                        or (self._manual and self._credits <= 0)):
                    self._cv.wait()
                if self._stopped:
                    return
                if self._manual:
                    self._credits -= 1
                self._building = True
            rp = None
            try:
                if preemption.stop_pending():
                    raise PreloadBuildAborted(
                        f"preload stopped ({preemption.stop_reason()})")
                ds = next(self._it, None)
                if ds is None:
                    with self._cv:
                        self._building = False
                        self._exhausted = True
                        self._cv.notify_all()
                    return
                t0 = time.perf_counter()
                # the pass trace's build span on the preload.worker
                # lane; its id rides the pass so the main-thread
                # consume span can link back (the build→consume flow
                # arrow — obs/trace, docs/OBSERVABILITY.md §Tracing),
                # and the pass carries the identifier drawn here onto
                # every later span of it
                seq = trace.next_pass_seq()
                with trace.span("pass.build", pass_seq=seq) as _sp:
                    rp = self._build(ds)
                    # a build on two lanes: what its key half waited
                    # for the float half where they meet
                    wait = (getattr(rp, "build_stats", None)
                            or {}).get("floats_wait")
                    if wait is not None:
                        _sp.attrs["floats_wait_ms"] = wait * 1e3
                try:
                    rp.pass_seq = seq
                    rp._trace_span_id = _sp.span_id
                except AttributeError:
                    pass  # slotted pass objects skip the link
                self._note_built(rp, time.perf_counter() - t0)
            except PreloadBuildAborted as e:
                log.warning("pass preload pipeline stopped: %s", e)
                with self._cv:
                    self._building = False
                    self._stopped = True
                    self._cv.notify_all()
                return
            except BaseException as e:  # held for the consuming wait()
                with self._cv:
                    self._building = False
                    self._err = e
                    self._cv.notify_all()
                return
            with self._cv:
                self._building = False
                dropped = self._stopped
                if not dropped:
                    self._q.append(rp)
                depth = len(self._q)
                self._cv.notify_all()
            if dropped:
                # drained mid-build: wait out the pass's issued
                # transfers before dropping it, so drain() really means
                # "no preload H2D in flight"
                dev = getattr(rp, "dev", None)
                if dev is not None:
                    jax.block_until_ready(list(jax.tree.leaves(dev)))
                return
            self._mirror_queue(depth)

    def _note_built(self, rp, build_sec: float) -> None:
        """Accounting + the HBM budget clamp, off the queue lock."""
        self.builds += 1
        self.build_sec_total += build_sec
        stages = getattr(rp, "build_stats", None)
        hub = self._hub()
        if stages:
            for stage, sec in stages.items():
                self.build_stage_sec[stage] = \
                    self.build_stage_sec.get(stage, 0.0) + sec
                if hub is not None:
                    hub.counter(
                        "pbox_preload_build_seconds_total",
                        "pass preload build seconds by stage"
                        ).inc(sec, stage=stage)
        if hub is not None:
            hub.counter("pbox_preload_builds_total",
                        "passes built by the preload pipeline").inc()
        if self._budget <= 0:
            return
        try:
            nbytes = int(rp.nbytes())
        except Exception:
            return  # passes without a wire-bytes estimate stay unguarded
        if nbytes <= 0:
            return
        fit = max(1, int(self._budget // nbytes))
        with self._cv:
            if fit >= self._effective_depth:
                return
            self._effective_depth = fit
            self.depth_clamped = True
        log.warning(
            "preload HBM budget: a staged pass is ~%.1f MB but the "
            "budget is %.1f MB — clamping preload depth %d -> %d "
            "(raise FLAGS.preload_hbm_budget_mb to restore the deeper "
            "pipeline)", nbytes / 1e6, self._budget / 1e6, self.depth,
            fit)
        if self._hub() is not None:
            self._hub().counter(
                "pbox_preload_depth_clamps_total",
                "preload depth reductions forced by the HBM budget"
                ).inc()

    # ---- consumer ------------------------------------------------------
    def start_next(self) -> bool:
        """Ensure the pipeline worker is running. Returns False only
        when the source is KNOWN exhausted and nothing remains to hand
        out — i.e. the next ``wait()`` would return None. (Compat shim
        for the depth-1 era's kick-per-pass protocol: extra calls are
        free, and lockstep start_next/wait loops keep working.)"""
        with self._cv:
            if self._manual:
                self._credits += 1
                self._cv.notify_all()
        if self._worker is None:
            self._worker = threading.Thread(
                target=self._run, daemon=True, name="pbox-preload")
            self._worker.start()
        with self._cv:
            return not (self._exhausted and not self._q
                        and not self._building and self._err is None)

    def wait(self) -> Optional[ResidentPass]:
        """Block until the next pipelined pass is staged
        (WaitFeedPassDone) and pop it; None at end-of-stream (or after
        ``stop()``/a raised build failure). The blocked seconds are the
        pipeline's prologue stall — exported as
        ``pbox_preload_wait_seconds_total`` so a starved pipeline
        (build slower than train) is visible next to the epilogue's
        fence-wait counter (docs/PERFORMANCE.md).

        With ``FLAGS.pipeline_wait_timeout_sec > 0`` a wait during
        which no build completes for that long raises
        ``PipelineHangError`` (ps/epilogue) naming the preload stage —
        a wedged build worker becomes a loud failure instead of an
        indefinite stall."""
        from paddlebox_tpu.ps.epilogue import hang_timeout, \
            wait_with_deadline
        if self._worker is None:
            return None
        t0 = time.perf_counter()
        err = None
        # the blocked part, always spanned (also when nothing blocked:
        # the boundary's readers want a wait for every pass)
        with trace.span("pass.wait") as sp, self._cv:
            wait_with_deadline(
                self._cv,
                done=lambda: bool(self._q) or self._exhausted
                or self._stopped or self._err is not None,
                progress=lambda: self.builds,
                stage="preload.build",
                message=lambda: (
                    f"pass preload wait hung: stage 'preload.build' "
                    f"made no progress for {hang_timeout():.1f}s — 0 "
                    f"staged pass(es) queued (building="
                    f"{self._building}, builds_done={self.builds}, "
                    f"effective_depth={self._effective_depth}, "
                    f"worker_alive="
                    f"{self._worker.is_alive()})"))
            waited = time.perf_counter() - t0
            if self._q:
                rp = self._q.popleft()
            else:
                rp = None
                if self._err is not None:
                    # the failure surfaces exactly where the broken
                    # pass would have been consumed; later waits → None
                    err, self._err = self._err, None
                    self._stopped = True
            depth = len(self._q)
            self._cv.notify_all()  # a build slot just freed
            sp.attrs["depth"] = depth
            sp.pass_seq = getattr(rp, "pass_seq", None)
        self.wait_sec_total += waited
        hub = self._hub()
        if hub is not None:
            if waited > 1e-4:
                hub.counter("pbox_preload_wait_seconds_total",
                            "seconds the trainer blocked on pass preload"
                            ).inc(waited)
                # critical-path attribution: the blocked wait is the
                # consuming pass's build-starvation stall (obs/trace —
                # rides the next pass event's critical_path block)
                trace.note_pass_part("build_wait", waited)
            hub.gauge("pbox_preload_queue_depth",
                      "staged passes queued ahead of training"
                      ).set(depth)
        if err is not None:
            raise err
        if rp is not None:
            rp.upload()  # no-op unless a build_fn skipped it
        return rp

    # ---- shutdown ------------------------------------------------------
    def stop(self) -> None:
        """Stop building: no new builds start; an in-flight build
        aborts at its next stage poll. Already-staged passes remain
        consumable via wait()."""
        with self._cv:
            self._stopped = True
            self._cv.notify_all()

    def drain(self, timeout: Optional[float] = None) -> None:
        """stop() + join the worker, then settle the staged passes'
        transfers — after this returns, no preload H2D is in flight
        (the graceful-shutdown hook: call before the emergency
        checkpoint's D2H so they don't contend for the wire)."""
        self.stop()
        w = self._worker
        if w is not None and w.is_alive():
            w.join(timeout)
        # queued passes were built with block=False, so their wire may
        # still be in flight even though the build finished; they stay
        # consumable — we only wait the transfers out
        with self._cv:
            staged = list(self._q)
        for rp in staged:
            dev = getattr(rp, "dev", None)
            if dev is not None:
                jax.block_until_ready(list(jax.tree.leaves(dev)))

    @property
    def staged(self) -> int:
        """Passes currently staged (built, unconsumed)."""
        with self._cv:
            return len(self._q)

    def _mirror_queue(self, depth: int) -> None:
        hub = self._hub()
        if hub is not None:
            hub.gauge("pbox_preload_queue_depth",
                      "staged passes queued ahead of training"
                      ).set(depth)

    @staticmethod
    def _hub():
        from paddlebox_tpu.obs.hub import get_hub
        hub = get_hub()
        return hub if hub.active else None


class PassPipeline:
    """ONE pass-pipeline abstraction — build → stage → consume →
    epilogue — shared by resident and tiered modes (ISSUE 9; ROADMAP's
    cross-cutting unification).

    Every pass mode decomposes into the same four phases:

      build    host pack of the pass (routing plans / dedup / wire
               encode) — ``build_fn`` (e.g. ``ResidentPass.build_streamed``
               or ``ShardedTrainer.build_resident_pass``)
      stage    moving the pass's bytes to where training reads them:
               the chunked H2D wire upload, plus — for pass-WINDOW
               tables — the host-tier feed-pass fetch (``table.stage``)
      consume  ``begin_pass`` reconcile (window tables) + the resident
               train loop over the staged pass
      epilogue ``end_pass`` write-back on the PassEpilogue lane, which
               also carries async capacity eviction and SSD watermark
               demotion (ps/tiered.py, ps/epilogue.py)

    For a plain resident table (``window_table=None``) this is exactly
    the depth-N ``PassPreloader``: build+stage ride the persistent
    worker, consume is the training loop, the epilogue is empty. For a
    pass-window table (``TieredShardedEmbeddingTable`` /
    ``MultihostTieredShardedTable``) each build is followed ON THE
    WORKER by the host-tier stage fetch, QUEUED in pass order
    (``table.stage(queue=True)``) — so by the time ``wait()`` hands a
    pass out, its plan is baked (plan_scope pending rows), its wire is
    in HBM, its host values are fetched, and its spilled rows are
    promoted (``prefetch_promote`` inside the build): ``begin_pass()``
    is reconcile-only, and ``end_pass()`` submits a write-back whose
    lane slot also evicts ahead for the NEXT queued stage
    (``_evict_ahead``). Plan builds stay serialized per ``plan_scope``
    on the single worker; the window capacity contract is the union
    over the open pass and every queued pass (ps/tiered.py module
    docstring).

    Driver shape (the bench / trainers):

        pipe = PassPipeline(datasets, build_fn=tr.build_resident_pass,
                            window_table=table, trainer=tr)
        pipe.start_next()
        while (rp := pipe.wait()) is not None:
            pipe.begin_pass()                  # reconcile-only
            pipe.start_next()
            tr.train_pass_resident(rp)
            pipe.end_pass()                    # submit; lane drains
        pipe.drain()

    ``depth=0`` gives the manual kick-per-pass sequential control (the
    no-overlap oracle for the pipeline gates)."""

    def __init__(self, datasets: Iterator, build_fn,
                 window_table=None, trainer=None,
                 depth: Optional[int] = None,
                 keys_of=None) -> None:
        import contextlib
        self.table = window_table
        self.trainer = trainer
        self._keys_of = keys_of or (lambda ds: ds.pass_keys())
        # fence-wait attribution baseline: the table's counters are
        # CUMULATIVE over its lifetime, and a fresh pipeline over a
        # long-lived table must not book historical fence waits into
        # its first pass's critical_path block
        self._fence_wait_mark = 0.0
        if window_table is not None:
            eps = getattr(window_table, "endpass_stats", None)
            if eps is not None:
                self._fence_wait_mark = float(
                    eps().get("critical_fence_wait_sec", 0.0))
        # key sets of built-and-staged passes, in build order — consumed
        # by begin_pass() to validate the head queued stage
        self._key_q: collections.deque = collections.deque()
        self._lock = threading.Lock()
        if window_table is None:
            build = build_fn
        else:
            def build(ds):
                keys = self._keys_of(ds)
                scope = getattr(window_table, "plan_scope", None)
                cm = (scope() if scope is not None
                      else contextlib.nullcontext())
                pin = getattr(window_table, "pin_working_set", None)
                # the OUTER plan_scope brackets build AND stage: an
                # abort (preemption/stop) or fetch failure between them
                # rolls the pass's pending plan rows back — a dead
                # build must not pin window capacity (the
                # rollback-under-abort contract,
                # tests/test_tiered_sharded.py)
                with cm:
                    # pin the working set for the WHOLE build+stage
                    # span: the plan bakes row ids for resident keys
                    # too, so eviction must not touch them from the
                    # first row lookup on (the pin hands over to the
                    # queued stage when stage() completes)
                    if pin is not None:
                        pin(keys)
                    try:
                        t0 = time.perf_counter()
                        rp = build_fn(ds)
                        t_build = time.perf_counter() - t0
                        poll_preload_abort()
                        # host fetch ON this worker, queued in pass
                        # order — by the time wait() hands the pass out
                        # its stage is complete and begin_pass is
                        # reconcile-only
                        t0 = time.perf_counter()
                        window_table.stage(keys, background=False,
                                           queue=True)
                        t_stage = time.perf_counter() - t0
                    except BaseException:
                        if pin is not None:
                            window_table.unpin_working_set()
                        raise
                # per-stage worker seconds for the preloader's
                # build_stage_sec mirror (builders that already report
                # stages — build_streamed — keep their finer split)
                stats = dict(getattr(rp, "build_stats", None) or {})
                stats.setdefault("build", t_build)
                stats["stage_fetch"] = t_stage
                try:
                    rp.build_stats = stats
                except AttributeError:
                    pass  # slotted pass objects skip the attribution
                with self._lock:
                    self._key_q.append(keys)
                return rp
        self.pre = PassPreloader(iter(datasets), build_fn=build,
                                 depth=depth)

    # ---- prologue (build + stage on the worker) ----------------------
    def start_next(self) -> bool:
        return self.pre.start_next()

    def wait(self):
        """Next staged pass (build + H2D wire + host fetch complete),
        or None at end-of-stream; the blocked seconds are the
        pipeline's prologue stall (PassPreloader.wait)."""
        return self.pre.wait()

    # ---- consume / epilogue (pass-window tables) ---------------------
    def begin_pass(self) -> int:
        """Consume the head queued stage: reconcile the staged working
        set into the HBM window (steady state: no fetch wait, no inline
        eviction — both already rode background lanes) and point the
        trainer's jit state at it."""
        if self.table is None:
            return 0
        with self._lock:
            if not self._key_q:
                raise RuntimeError("begin_pass with no staged pass — "
                                   "call wait() first")
            keys = self._key_q[0]
        # pop only AFTER the table accepted the pass: a raising
        # begin_pass leaves both queues ALIGNED — the table restores a
        # consumed stage to its queue head on failure (ps/tiered), so
        # drain() still releases every pin and the error surfaces
        # consistently (a partially-promoted pass must not be blindly
        # retried; see the table-side note)
        n = self.table.begin_pass(keys)
        with self._lock:
            if self._key_q and self._key_q[0] is keys:
                self._key_q.popleft()
        # boundary attribution for the upcoming pass event
        # (obs/trace critical_path): the begin-stall pieces the table
        # just measured (~0 in steady state — the point of the pipeline)
        lp = getattr(self.table, "last_pass_stats", None) or {}
        for stage, key in (("stage_wait", "stage_wait_sec"),
                           ("evict_scatter", "evict_scatter_sec"),
                           ("evict_emergency", "evict_emergency_sec"),
                           ("ssd_promote", "ssd_promote_wait_sec")):
            trace.note_pass_part(stage, float(lp.get(key, 0.0) or 0.0))
        if self.trainer is not None:
            self.trainer.adopt_table()
        return n

    def end_pass(self) -> int:
        """Close the open pass: write-back submits to the epilogue lane
        (async), which also runs the next queued stage's capacity
        eviction and any SSD watermark demotion. The submit cost and
        the main-thread fence wait it exposed are reported into the
        NEXT pass event's critical_path block (they stall the next
        boundary, not the pass that already emitted its event)."""
        if self.table is None:
            return 0
        if self.trainer is not None:
            self.trainer.sync_table()
        t0 = time.perf_counter()
        n = self.table.end_pass()
        trace.note_pass_part("end_submit", time.perf_counter() - t0)
        eps = getattr(self.table, "endpass_stats", None)
        if eps is not None:
            cur = float(eps().get("critical_fence_wait_sec", 0.0))
            mark, self._fence_wait_mark = self._fence_wait_mark, cur
            trace.note_pass_part("fence_wait", cur - mark)
        return n

    # ---- shutdown ----------------------------------------------------
    def drain(self, timeout: Optional[float] = None) -> None:
        """Stop building, join the worker, settle in-flight transfers,
        and DISCARD queued stages that will never begin (releasing
        their plan-pending pins — ps/tiered.discard_queued_stages)."""
        self.pre.drain(timeout)
        if self.table is not None:
            discard = getattr(self.table, "discard_queued_stages", None)
            if discard is not None:
                discard()
        with self._lock:
            self._key_q.clear()

    # ---- accounting pass-throughs (bench / telemetry) ----------------
    @property
    def depth(self) -> int:
        return self.pre.depth

    @property
    def builds(self) -> int:
        return self.pre.builds

    @property
    def build_sec_total(self) -> float:
        return self.pre.build_sec_total

    @property
    def wait_sec_total(self) -> float:
        return self.pre.wait_sec_total

    @property
    def build_stage_sec(self) -> Dict[str, float]:
        return self.pre.build_stage_sec

    @property
    def depth_clamped(self) -> bool:
        return self.pre.depth_clamped
