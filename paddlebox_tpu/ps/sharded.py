"""Mesh-sharded embedding table: the HeterComm redesign for TPU.

Reference: paddle/fluid/framework/fleet/heter_ps/heter_comm_inl.h — the
table is sharded by ``key % num_devices`` (calc_shard_index_kernel,
heter_comm_kernel.cu:91); pull sorts/splits keys per shard
(split_input_to_shard :1117), P2P-copies keys to the owner GPU
(walk_to_dest :273), gathers on the owner, walks values back
(walk_to_src :428) and restores order with dedup (pull_merge_sparse
:1329-1472); push merges grads (merge_grad cub sort+reduce) and applies the
optimizer on the owner.

TPU-native redesign: all P2P walks become TWO ``lax.all_to_all`` ops over
the mesh axis inside one jit step (ICI-routed, overlappable by XLA), and all
sort/dedup/index work happens on HOST during batch prep (overlapped with
device compute by the trainer's prefetch pipeline):

  host prep (per global batch):
    for each device d: unique keys of d's local batch, bucketed by owner
    shard s = key % N → request lists [N, A] (A = padded per-pair capacity);
    for each owner s: dedup of ALL requests it will serve → serve_rows [A2]
    and response index resp_idx [N, A] into it (so duplicate rows requested
    by several devices are served and grad-merged once).
  device step (per shard, under shard_map):
    serve_vals = gather(table, serve_rows)          # local HBM gather
    resp      = serve_vals[resp_idx]                # [N, A, D]
    recv      = all_to_all(resp)                    # values to requesters
    … model fwd/bwd on local batch …
    g_back    = all_to_all(g_recv)                  # grads to owners
    g_serve   = segment_sum(g_back, resp_idx)       # merge across requesters
    table     = apply_push(table, serve_rows, g_serve)

No RPC plane, no NCCL rings, no device-side sort: the only cross-chip
traffic is the two value-sized all-to-alls (+ the dense psum), exactly the
ICI-friendly schedule.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.config import FLAGS
from paddlebox_tpu.data.batch import SlotBatch
from paddlebox_tpu.parallel.mesh import stacked_zeros
from paddlebox_tpu.ps.sgd import SparseSGDConfig
from paddlebox_tpu.ps.table import (FIELD_COL, FIELDS, NUM_FIXED, HostKV,
                                    TableState, field_assign, field_slice,
                                    fill_oob_pads, init_table_state,
                                    next_bucket)
from paddlebox_tpu.utils.logging import get_logger

log = get_logger(__name__)


class ShardedPullIndex(NamedTuple):
    """Host-built routing plan for one global batch; leading dim = device.

    Shapes: N devices, A = per-(dst,src) request capacity, A2 = per-owner
    serve capacity, K = padded keys per local batch. ``req_need`` /
    ``serve_need`` are the UNPADDED maxima behind A/A2 — the resident
    builder re-buckets a whole pass with the fine ladder from them."""

    resp_idx: np.ndarray     # int32 [N_owner, N_dst, A] → slot in serve_rows
    serve_rows: np.ndarray   # int32 [N_owner, A2]; pads → sentinel row C
    serve_valid: np.ndarray  # f32   [N_owner, A2]
    serve_slot: np.ndarray   # f32   [N_owner, A2] slot id of the row's key
    gather_idx: np.ndarray   # int32 [N_dst, K] → index into recv [N*A]
    key_valid: np.ndarray    # f32   [N_dst, K]
    req_capacity: int        # A
    serve_capacity: int      # A2
    req_need: int = 0        # max real requests per (dst, owner)
    serve_need: int = 0      # max real serve rows per owner (+1 sentinel)
    # ---- chunked exchange layout (FLAGS.a2a_chunks > 1; ISSUE 11) ----
    # empty/None = the monolithic plan (exactly the pre-chunking bytes).
    # When set, the A axis is partitioned into per-slot-group sections
    # (sum(a2a_sections) == A) so chunk g's all_to_all ships only its
    # section, and the key stream is re-laid group-contiguous
    # (sum(key_sections) == gather_idx.shape[1]) with the group's
    # segments shipped as ``key_segments`` (the batch's own segment
    # stream is in the ORIGINAL key order and no longer applies).
    a2a_sections: Tuple[int, ...] = ()   # per-group A section widths
    key_sections: Tuple[int, ...] = ()   # per-group K section widths
    slot_sections: Tuple[int, ...] = ()  # per-group slot counts (contig)
    key_segments: Optional[np.ndarray] = None  # int32 [N_dst, sum(K_g)]


def plan_sections(idx: "ShardedPullIndex") -> Tuple:
    """The static chunk-schedule key of a plan: ``(a2a_sections,
    key_sections, slot_sections)`` for a grouped plan, ``()`` for a
    monolithic one. The device step compiles one executable per
    distinct value (train/sharded.ShardedTrainStep._step_fn_for)."""
    if getattr(idx, "a2a_sections", ()):
        return (tuple(idx.a2a_sections), tuple(idx.key_sections),
                tuple(idx.slot_sections))
    return ()


def section_offsets(sections) -> List[int]:
    """Start offset of each contiguous section (exclusive-prefix sum)
    of a grouped plan's static layout (train/sharded._device_step)."""
    off, t = [], 0
    for x in sections:
        off.append(t)
        t += x
    return off


def chunk_local_positions(gi, a_total: int, a_lo: int, ag: int):
    """Global exchange positions ``owner*A + j`` → chunk-local
    ``owner*A_g + (j - a_lo)`` for the section at [a_lo, a_lo+ag).
    Operator-only arithmetic: works on np AND traced jnp arrays — ONE
    definition of the remap for the step and the probe."""
    owner = gi // a_total
    return owner * ag + (gi - owner * a_total) - a_lo


def _bucket(n: int, bucket_min: int) -> int:
    return next_bucket(bucket_min, n)


class ShardedEmbeddingTable:
    """N-shard embedding store driven from a single host process.

    Key → owner shard ``key % N`` (heter_comm_kernel.cu:91); each shard has
    its own HostKV index and a [C+1]-row slice of the device table state,
    stacked on a leading mesh axis."""

    def __init__(self, num_shards: int, mf_dim: int = 8,
                 capacity_per_shard: Optional[int] = None,
                 cfg: Optional[SparseSGDConfig] = None,
                 req_bucket_min: int = 512,
                 serve_bucket_min: int = 1024) -> None:
        self.n = num_shards
        self.mf_dim = mf_dim
        self.capacity = capacity_per_shard or FLAGS.table_capacity_per_shard
        self.cfg = cfg or SparseSGDConfig()
        from paddlebox_tpu.ps.sgd import opt_ext_width
        self.opt_ext = opt_ext_width(self.cfg, mf_dim)
        self.indexes = [HostKV(self.capacity) for _ in range(num_shards)]
        self.req_bucket_min = req_bucket_min
        self.serve_bucket_min = serve_bucket_min
        # stacked state [N, L, 128] — sharded over the mesh axis
        single = init_table_state(self.capacity, mf_dim, ext=self.opt_ext)
        self.state = self._make_stacked_state(single, num_shards)
        self._touched = np.zeros((num_shards, self.capacity + 1), dtype=bool)
        # serializes host index/touched mutation across threads (resident
        # pass preloading vs save/shrink — same discipline as
        # EmbeddingTable.host_lock)
        self.host_lock = threading.Lock()
        # THREAD-LOCAL plan marker (tiered plan_scope): while the
        # CALLING thread builds a routing plan for a *future* pass, its
        # new-key assigns are recorded via _note_plan_assigned instead
        # of being marked touched — they have no values yet and train
        # only after their pass's begin_pass promotes the staged values.
        # Thread-local, not table-global: a concurrent streaming
        # prepare_global on another thread (training the OPEN pass)
        # must keep the normal assign semantics
        self._plan_tls = threading.local()

    @property
    def _plan_depth(self) -> int:
        return getattr(self._plan_tls, "depth", 0)

    # ------------------------------------------------------------------
    # device-resident key assignment (FLAGS.use_pallas_index): lazy
    # per-shard Pallas open-addressing mirrors of the host kvs — same
    # contract as EmbeddingTable._bulk_assign_device: the host kv stays
    # AUTHORITATIVE, any state the mirror cannot reproduce exactly
    # degrades that shard loudly and stickily back to the host path.
    def _dev_index_for(self, s: int):
        """Per-shard DeviceKeyIndex, lazily seeded from the shard's
        host kv (call under host_lock)."""
        if getattr(self, "_dev_indexes", None) is None:
            self._dev_indexes = [None] * self.n
        dev = self._dev_indexes[s]
        if dev is None:
            from paddlebox_tpu.ops.pallas_index import DeviceKeyIndex
            dev = DeviceKeyIndex(self.capacity)
            if not dev.seed_from_kv(self.indexes[s]):
                dev.degrade(f"shard {s}: host kv rows are not dense "
                            "(free-list holes) — cannot mirror")
            self._dev_indexes[s] = dev
        return dev

    def _reset_dev_indexes(self) -> None:
        """Forget every shard's device mirror after a host-side kv
        lifecycle mutation (load/shrink/merge/release/promote): the
        next flag-on prepare re-seeds from the kv, or degrades loudly
        if the allocation is no longer dense."""
        self._dev_indexes = None

    def _shard_rows_device(self, s: int, keys_s: np.ndarray,
                           assign: bool) -> Optional[np.ndarray]:
        """Device route for one owner-shard request list: probe the
        shard's device hash index instead of the host kv. Returns
        int32 rows (assign) or rows with miss→C (lookup), or None to
        fall back to the host kv."""
        dev = self._dev_index_for(s)
        if dev.degraded:
            return None
        if len(self.indexes[s]) != dev.next_row:
            dev.degrade(f"shard {s}: host kv diverged "
                        f"({len(self.indexes[s])} keys vs "
                        f"{dev.next_row} mirrored)")
            return None
        if not assign:
            rows = dev.lookup_rows(keys_s)
            return np.where(rows < 0, self.capacity,
                            rows).astype(np.int32)
        out = dev.assign_unique(keys_s)
        if out is None:
            dev.degrade(f"shard {s}: probe/capacity overflow "
                        f"({len(keys_s)} keys at {dev.next_row} rows, "
                        f"capacity {self.capacity})")
            return None
        rows_u, new_mask = out
        if new_mask.any():
            # mirror ONLY the new keys into the host kv; kv.assign
            # allocates in stream order, so a dense kv must reproduce
            # the device rows exactly — anything else means holes
            krows = self.indexes[s].assign(keys_s[new_mask])
            if not np.array_equal(
                    krows, rows_u[new_mask].astype(krows.dtype)):
                dev.degrade(f"shard {s}: host kv allocated different "
                            "rows than the device index (free-list "
                            "holes)")
                return None
        return rows_u.astype(np.int32, copy=False)

    def _shard_rows(self, s: int, keys_s: np.ndarray,
                    assign: bool) -> np.ndarray:
        """Resolve owner-local rows for one (dst, owner) request list
        (call under host_lock; ``keys_s`` sorted unique keys owned by
        shard ``s``). The single seam shared by the monolithic and
        grouped plans: plan-depth assigns stay host-side (plan rows
        need the pre-lookup miss mask and roll back on abort), the
        streaming assign / read-only lookup paths route through the
        per-shard device probe table behind FLAGS.use_pallas_index,
        with both decisions booked in pbox_kernel_dispatch_total."""
        C = self.capacity
        if assign and self._plan_depth:
            pre = self.indexes[s].lookup(keys_s)
            rows_s = self.indexes[s].assign(keys_s)
            if (pre < 0).any():
                self._note_plan_assigned(s, keys_s[pre < 0])
            # touched stays clear: plan rows train only after their
            # pass opens; mark_trained_rows flags them post-training
            if getattr(self, "_dev_indexes", None) is not None:
                # the mirror missed these assigns — re-seed on next use
                self._dev_indexes[s] = None
            return rows_s
        if FLAGS.use_pallas_index:
            from paddlebox_tpu.ops.pallas_index import (
                book_index_dispatch, device_impl)
            op = "assign" if assign else "lookup"
            rows_s = self._shard_rows_device(s, keys_s, assign)
            if rows_s is not None:
                if assign:
                    self._touched[s][rows_s] = True
                book_index_dispatch(op, device_impl())
                return rows_s
            book_index_dispatch(op, "host")
        if assign:
            rows_s = self.indexes[s].assign(keys_s)
            self._touched[s][rows_s] = True
        else:
            rows_s = self.indexes[s].lookup(keys_s)
            rows_s = np.where(rows_s < 0, C, rows_s).astype(rows_s.dtype)
        return rows_s

    def _make_stacked_state(self, single: TableState, n: int) -> TableState:
        """Subclass hook: build the stacked [N, L, 128] zero state, each
        shard born on its own device (parallel.mesh.stacked_zeros) —
        the multihost table stages it over the global mesh instead."""
        return single.with_packed(stacked_zeros(
            n, single.packed.shape, single.packed.dtype))

    # ------------------------------------------------------------------
    def prepare_global_eval(self, batches: List[SlotBatch],
                            req_capacity: Optional[int] = None,
                            serve_capacity: Optional[int] = None
                            ) -> ShardedPullIndex:
        """Read-only routing plan: unknown keys serve the zero sentinel
        row instead of allocating (inference; no index mutation). Only
        legal for pull-only steps — serve_rows may repeat the sentinel,
        which the push path's unique-scatter promise forbids."""
        return self.prepare_global(batches, req_capacity, serve_capacity,
                                   assign=False)

    def prepare_global(self, batches: List[SlotBatch],
                       req_capacity: Optional[int] = None,
                       serve_capacity: Optional[int] = None,
                       assign: bool = True,
                       groups: int = 1,
                       req_sections: Optional[Tuple[int, ...]] = None,
                       key_sections: Optional[Tuple[int, ...]] = None
                       ) -> ShardedPullIndex:
        """Build the routing plan for N per-device batches (one global
        batch). All batches must share K_pad/batch_size/num_slots.
        ``req_capacity``/``serve_capacity`` force the A/A2 buckets — the
        resident-pass builder uses this to give every batch in a pass
        identical shapes (gather_idx encodes positions as owner*A + j, so
        A must be uniform across the staged pass).

        ``groups > 1`` builds the CHUNKED exchange layout (ISSUE 11;
        FLAGS.a2a_chunks): the A axis is partitioned into contiguous
        per-slot-group sections so the device step can run one
        all_to_all per group overlapped with the previous group's
        pooling. Requires slot-qualified keys (every key's occurrences
        in ONE slot group); a violating batch falls back to the
        monolithic plan with a warning. ``req_sections``/
        ``key_sections`` force per-group section widths (the resident
        builder's uniform-shape contract, the grouped analogue of
        ``req_capacity``)."""
        if groups > 1:
            return self._prepare_global_grouped(
                batches, groups, serve_capacity=serve_capacity,
                assign=assign, req_sections=req_sections,
                key_sections=key_sections)
        n = self.n
        assert len(batches) == n, f"need {n} local batches, got {len(batches)}"
        k_pad = max(b.keys.shape[0] for b in batches)
        C = self.capacity

        # per device: unique local keys + their owner shard + owner-local row
        # + slot id (first occurrence) for the table's slot field
        dev_uniq: List[np.ndarray] = []
        dev_inv: List[np.ndarray] = []
        dev_uniq_slot: List[np.ndarray] = []
        for b in batches:
            uniq, first, inv = np.unique(
                b.keys[:b.num_keys], return_index=True, return_inverse=True)
            occ_slot = (b.segments[:b.num_keys] % b.num_slots).astype(np.float32)
            dev_uniq.append(uniq)
            dev_inv.append(inv)
            dev_uniq_slot.append(occ_slot[first])

        # request lists per (dst, owner)
        req_rows = [[None] * n for _ in range(n)]      # [dst][owner] → rows
        req_slots = [[None] * n for _ in range(n)]     # [dst][owner] → slots
        req_pos_of_uniq: List[np.ndarray] = []         # per dst: (owner, j)
        a_max = 1
        for d in range(n):
            uniq = dev_uniq[d]
            owners = (uniq % np.uint64(n)).astype(np.int64)
            pos = np.empty((len(uniq), 2), dtype=np.int64)
            for s in range(n):
                sel = np.nonzero(owners == s)[0]
                keys_s = uniq[sel]
                with self.host_lock:
                    rows_s = self._shard_rows(s, keys_s, assign)
                req_rows[d][s] = rows_s
                req_slots[d][s] = dev_uniq_slot[d][sel]
                pos[sel, 0] = s
                pos[sel, 1] = np.arange(len(sel))
                a_max = max(a_max, len(sel))
            req_pos_of_uniq.append(pos)
        A = _bucket(a_max, self.req_bucket_min)
        if req_capacity is not None:
            if req_capacity < a_max:
                raise ValueError(
                    f"forced req_capacity {req_capacity} < needed {a_max}")
            A = req_capacity

        # owner-side dedup: all (dst, j) requests to owner s → serve slots
        resp_idx = np.zeros((n, n, A), dtype=np.int32)
        serve_rows_l: List[np.ndarray] = []
        serve_slot_l: List[np.ndarray] = []
        a2_max = 1
        for s in range(n):
            all_rows = np.concatenate([req_rows[d][s] for d in range(n)])
            all_slots = np.concatenate([req_slots[d][s] for d in range(n)])
            su, sinv = (np.unique(all_rows, return_inverse=True)
                        if len(all_rows) else
                        (np.empty(0, np.int64), np.empty(0, np.int64)))
            serve_rows_l.append(su)
            slot_l = np.zeros(len(su), np.float32)
            slot_l[sinv] = all_slots  # any requester's slot id for the key
            serve_slot_l.append(slot_l)
            a2_max = max(a2_max, len(su) + 1)
            off = 0
            for d in range(n):
                cnt = len(req_rows[d][s])
                resp_idx[s, d, :cnt] = sinv[off:off + cnt]
                # pads: point at the sentinel serve slot (last)
                resp_idx[s, d, cnt:] = len(su)
                off += cnt
        A2 = _bucket(a2_max, self.serve_bucket_min)
        if serve_capacity is not None:
            if serve_capacity < a2_max:
                raise ValueError(
                    f"forced serve_capacity {serve_capacity} < {a2_max}")
            A2 = serve_capacity

        serve_rows = np.empty((n, A2), dtype=np.int32)
        serve_valid = np.zeros((n, A2), dtype=np.float32)
        serve_slot = np.zeros((n, A2), dtype=np.float32)
        for s in range(n):
            u = len(serve_rows_l[s])
            serve_rows[s, :u] = serve_rows_l[s]
            fill_oob_pads(serve_rows[s], u, C)
            serve_valid[s, :u] = 1.0
            serve_slot[s, :u] = serve_slot_l[s]
            # pad requests point at the sentinel slot (zero row)
            resp_idx[s][resp_idx[s] == u] = A2 - 1

        # dst-side gather: local key occurrence → position in recv [N*A]
        gather_idx = np.full((n, k_pad), n * A - 1, dtype=np.int32)
        key_valid = np.zeros((n, k_pad), dtype=np.float32)
        for d in range(n):
            b = batches[d]
            pos = req_pos_of_uniq[d]             # per-unique (owner, j)
            occ = dev_inv[d]                     # per occurrence → unique
            oi = pos[occ]                        # [nk, 2]
            gather_idx[d, :b.num_keys] = (oi[:, 0] * A + oi[:, 1]).astype(np.int32)
            key_valid[d, :b.num_keys] = 1.0
        return ShardedPullIndex(
            resp_idx=resp_idx, serve_rows=serve_rows, serve_valid=serve_valid,
            serve_slot=serve_slot, gather_idx=gather_idx,
            key_valid=key_valid, req_capacity=A, serve_capacity=A2,
            req_need=a_max, serve_need=a2_max)

    def _prepare_global_grouped(
            self, batches: List[SlotBatch], groups: int,
            serve_capacity: Optional[int] = None, assign: bool = True,
            req_sections: Optional[Tuple[int, ...]] = None,
            key_sections: Optional[Tuple[int, ...]] = None
            ) -> ShardedPullIndex:
        """Chunked-exchange plan (see prepare_global). Layout contract:

        - Rows ASSIGN in the monolithic order (sorted-unique per
          (dst, owner) pair) before any group re-layout, so new-key row
          ids — and therefore the whole table state — are bit-identical
          to an ``a2a_chunks=1`` run over the same stream.
        - The A axis is ``sum(a2a_sections)`` wide; pair (dst, owner)'s
          group-g requests sit at ``[a_lo[g], a_lo[g]+cnt)``. Every
          section keeps ≥ 1 trailing pad position (A_g ≥ need_g + 1) so
          the group's pad keys have an in-section zero read.
        - The key stream re-lays group-contiguous (key_sections), each
          section padded with keys that gather the section's last (pad)
          position and pool into the discard bin; the matching segment
          stream ships as ``key_segments``.
        - Serve side is UNCHANGED: one canonical per-owner dedup, so
          the push's merge_rows/apply_push segmentation — and the
          per-row grad summation order (src-major, one contribution per
          src) — match the monolithic plan exactly.

        The slot-qualified check is deliberately PER-DEVICE: a key that
        lands in different slot groups on different devices is still
        exact, because groups only shape each device's OWN request
        layout and key partition (each dst gathers from its own
        sections; pooling bins are per-(device-local) occurrence slot),
        while the serve side is group-agnostic — dedup is over row ids,
        and the slot last-writer is decided by the cross-device concat
        order, which the within-pair reorder preserves. Only a
        within-device conflict (one key, occurrences in two groups on
        the SAME batch) breaks the section layout, and that is exactly
        what the check rejects."""
        from paddlebox_tpu.ops.seqpool_cvm import slot_group_bounds
        n = self.n
        assert len(batches) == n, \
            f"need {n} local batches, got {len(batches)}"
        k_pad = max(b.keys.shape[0] for b in batches)
        C = self.capacity
        S = batches[0].num_slots
        bounds = slot_group_bounds(S, groups)
        c = len(bounds)
        if c <= 1:
            return self.prepare_global(batches, assign=assign,
                                       serve_capacity=serve_capacity)
        grp_of_slot = np.zeros(S, np.int64)
        for g, (lo, hi) in enumerate(bounds):
            grp_of_slot[lo:hi] = g

        # uniques + the slot-qualified check BEFORE any index mutation,
        # so the monolithic fallback is side-effect clean
        dev_uniq: List[np.ndarray] = []
        dev_inv: List[np.ndarray] = []
        dev_uniq_slot: List[np.ndarray] = []
        dev_key_grp: List[np.ndarray] = []
        for b in batches:
            uniq, first, inv = np.unique(
                b.keys[:b.num_keys], return_index=True,
                return_inverse=True)
            occ_slot = (b.segments[:b.num_keys]
                        % b.num_slots).astype(np.int64)
            occ_grp = grp_of_slot[occ_slot]
            key_grp = occ_grp[first]
            if (occ_grp != key_grp[inv]).any():
                log.warning(
                    "a2a_chunks=%d: a key's occurrences span slot "
                    "groups (keys are not slot-qualified) — falling "
                    "back to the monolithic exchange for this batch", c)
                return self.prepare_global(batches, assign=assign,
                                           serve_capacity=serve_capacity)
            dev_uniq.append(uniq)
            dev_inv.append(inv)
            dev_uniq_slot.append(occ_slot[first].astype(np.float32))
            dev_key_grp.append(key_grp)

        # request lists per (dst, owner): rows assigned in monolithic
        # order, then re-laid group-contiguous with per-group ranks
        req_rows = [[None] * n for _ in range(n)]
        req_slots = [[None] * n for _ in range(n)]
        req_grp = [[None] * n for _ in range(n)]
        need_g = np.zeros(c, np.int64)
        req_pos_of_uniq: List[np.ndarray] = []  # per dst: (owner, g, rank)
        for d in range(n):
            uniq = dev_uniq[d]
            owners = (uniq % np.uint64(n)).astype(np.int64)
            pos = np.empty((len(uniq), 3), dtype=np.int64)
            for s in range(n):
                sel = np.nonzero(owners == s)[0]
                keys_s = uniq[sel]
                with self.host_lock:
                    rows_s = self._shard_rows(s, keys_s, assign)
                grp_s = dev_key_grp[d][sel]
                order = np.argsort(grp_s, kind="stable")
                req_rows[d][s] = rows_s[order]
                req_slots[d][s] = dev_uniq_slot[d][sel][order]
                req_grp[d][s] = grp_s[order]
                ranks = np.empty(len(sel), np.int64)
                for g in range(c):
                    m = grp_s == g
                    cnt = int(m.sum())
                    ranks[m] = np.arange(cnt)
                    need_g[g] = max(need_g[g], cnt)
                pos[sel, 0] = s
                pos[sel, 1] = grp_s
                pos[sel, 2] = ranks
            req_pos_of_uniq.append(pos)
        if req_sections is not None:
            a_secs = tuple(int(x) for x in req_sections)
            for g in range(c):
                if a_secs[g] < int(need_g[g]) + 1:
                    raise ValueError(
                        f"forced req_sections[{g}]={a_secs[g]} < needed "
                        f"{int(need_g[g]) + 1}")
        else:
            bmin = max(1, self.req_bucket_min // c)
            a_secs = tuple(_bucket(int(need_g[g]) + 1, bmin)
                           for g in range(c))
        a_lo = np.concatenate([[0], np.cumsum(a_secs)]).astype(np.int64)
        A = int(a_lo[-1])

        # owner-side dedup: IDENTICAL to the monolithic plan (same rows,
        # same sorted-unique order); only resp positions move
        resp_idx = np.zeros((n, n, A), dtype=np.int32)
        serve_rows_l: List[np.ndarray] = []
        serve_slot_l: List[np.ndarray] = []
        a2_max = 1
        for s in range(n):
            all_rows = np.concatenate([req_rows[d][s] for d in range(n)])
            all_slots = np.concatenate([req_slots[d][s] for d in range(n)])
            su, sinv = (np.unique(all_rows, return_inverse=True)
                        if len(all_rows) else
                        (np.empty(0, np.int64), np.empty(0, np.int64)))
            serve_rows_l.append(su)
            slot_l = np.zeros(len(su), np.float32)
            slot_l[sinv] = all_slots
            serve_slot_l.append(slot_l)
            a2_max = max(a2_max, len(su) + 1)
            off = 0
            for d in range(n):
                cnt = len(req_rows[d][s])
                row = np.full(A, len(su), np.int64)
                if cnt:
                    jpos = a_lo[req_grp[d][s]] + \
                        np.concatenate([np.arange(int((req_grp[d][s] == g
                                                       ).sum()))
                                        for g in range(c)])
                    row[jpos] = sinv[off:off + cnt]
                resp_idx[s, d] = row
                off += cnt
        A2 = _bucket(a2_max, self.serve_bucket_min)
        if serve_capacity is not None:
            if serve_capacity < a2_max:
                raise ValueError(
                    f"forced serve_capacity {serve_capacity} < {a2_max}")
            A2 = serve_capacity

        serve_rows = np.empty((n, A2), dtype=np.int32)
        serve_valid = np.zeros((n, A2), dtype=np.float32)
        serve_slot = np.zeros((n, A2), dtype=np.float32)
        for s in range(n):
            u = len(serve_rows_l[s])
            serve_rows[s, :u] = serve_rows_l[s]
            fill_oob_pads(serve_rows[s], u, C)
            serve_valid[s, :u] = 1.0
            serve_slot[s, :u] = serve_slot_l[s]
            resp_idx[s][resp_idx[s] == u] = A2 - 1

        # dst-side gather: group-contiguous key sections
        k_need = np.zeros(c, np.int64)
        occ_grp_dev: List[np.ndarray] = []
        for d in range(n):
            og = dev_key_grp[d][dev_inv[d]]
            occ_grp_dev.append(og)
            for g in range(c):
                k_need[g] = max(k_need[g], int((og == g).sum()))
        if key_sections is not None:
            k_secs = tuple(int(x) for x in key_sections)
            for g in range(c):
                if k_secs[g] < int(k_need[g]):
                    raise ValueError(
                        f"forced key_sections[{g}]={k_secs[g]} < needed "
                        f"{int(k_need[g])}")
        else:
            # pow2 ladder from a FIXED min — never from the batch's
            # k_pad, whose per-batch wobble would mint gratuitously
            # distinct section tuples (and one jitted step executable
            # per tuple in streaming mode)
            k_secs = tuple(_bucket(max(1, int(k_need[g])), 8)
                           for g in range(c))
        k_lo = np.concatenate([[0], np.cumsum(k_secs)]).astype(np.int64)
        kp = int(k_lo[-1])
        gather_idx = np.empty((n, kp), dtype=np.int32)
        key_valid = np.zeros((n, kp), dtype=np.float32)
        key_segments = np.empty((n, kp), dtype=np.int32)
        for d, b in enumerate(batches):
            pos = req_pos_of_uniq[d]
            oi = pos[dev_inv[d]]                       # [nk, 3]
            gidx = (oi[:, 0] * A + a_lo[oi[:, 1]]
                    + oi[:, 2]).astype(np.int32)
            seg = b.segments[:b.num_keys]
            og = occ_grp_dev[d]
            for g in range(c):
                m = np.nonzero(og == g)[0]             # original order
                lo, kg = int(k_lo[g]), int(k_secs[g])
                # section pads gather the section's guaranteed-pad
                # exchange position (A_g ≥ need_g + 1 ⇒ the last j of
                # every pair's section serves the zero sentinel row)
                pad_flat = (n - 1) * A + int(a_lo[g]) + a_secs[g] - 1
                gather_idx[d, lo:lo + kg] = pad_flat
                gather_idx[d, lo:lo + len(m)] = gidx[m]
                key_valid[d, lo:lo + len(m)] = 1.0
                key_segments[d, lo:lo + kg] = b.pad_segment
                key_segments[d, lo:lo + len(m)] = seg[m]
        return ShardedPullIndex(
            resp_idx=resp_idx, serve_rows=serve_rows,
            serve_valid=serve_valid, serve_slot=serve_slot,
            gather_idx=gather_idx, key_valid=key_valid,
            req_capacity=A, serve_capacity=A2,
            req_need=int(need_g.max()) if c else 0, serve_need=a2_max,
            a2a_sections=a_secs, key_sections=k_secs,
            slot_sections=tuple(hi - lo for lo, hi in bounds),
            key_segments=key_segments)

    def _note_plan_assigned(self, s: int, new_keys: np.ndarray) -> None:
        """Hook (called under host_lock) for keys newly assigned during
        a plan build — the tiered table records them as value-less
        PENDING rows; the plain HBM-resident table needs nothing (fresh
        zero rows ARE its contract for unseen keys)."""

    # ---- host save/load mirrors EmbeddingTable, per shard ----
    def feature_count(self) -> int:
        return sum(len(ix) for ix in self.indexes)

    def obs_stats(self) -> Dict[str, float]:
        """Occupancy gauges for pass events (obs/hub.emit_pass_event):
        totals across shards plus the fullest shard's fill (the key%N
        split skews, and one full shard stalls the whole mesh).
        Subclasses with plan-pending rows (tiered) override to add
        ``pending``."""
        per_shard = [len(ix) for ix in self.indexes]
        used = sum(per_shard)
        cap = self.capacity * self.n
        return {"capacity": cap, "used": used,
                "fill_frac": round(used / max(cap, 1), 6),
                "max_shard_fill_frac": round(
                    max(per_shard) / max(self.capacity, 1), 6)}

    def _dump(self, path: str, row_filter) -> int:
        data = np.asarray(jax.device_get(self.state.data))
        mf_end = NUM_FIXED + self.mf_dim
        blobs = {}
        total = 0
        for s in range(self.n):
            with self.host_lock:
                keys, rows = self.indexes[s].items()
                keys, rows = row_filter(s, keys, rows)
                # clear only the SNAPSHOTTED rows, inside the lock — rows
                # touched concurrently (preload thread) keep their flag
                # for the next delta
                self._touched[s][rows] = False
            blobs[f"keys_{s}"] = keys
            sub = data[s][rows]
            for f in FIELDS:
                # embedx sliced to mf_dim explicitly — field_slice's tail
                # is unbounded and would duplicate opt_ext into embedx_w
                blobs[f"{f}_{s}"] = (sub[:, NUM_FIXED:mf_end]
                                     if f == "embedx_w"
                                     else field_slice(sub, f))
            if self.opt_ext:
                blobs[f"opt_ext_{s}"] = sub[:, mf_end:]
            total += len(keys)
        np.savez_compressed(path, n=self.n, **blobs)
        return total

    def save_base(self, path: str) -> int:
        """Full model dump (SaveBase, box_wrapper.cc:1383)."""
        return self._dump(path, lambda s, keys, rows: (keys, rows))

    def save_delta(self, path: str) -> int:
        """Rows touched since last save (SaveDelta "xbox delta",
        box_wrapper.cc:1406)."""
        def flt(s, keys, rows):
            m = self._touched[s][rows]
            return keys[m], rows[m]
        return self._dump(path, flt)

    def load(self, path: str, merge: bool = False) -> int:
        """Load a base/delta dump; merge=True applies on top of the live
        table, else the table (host index AND device rows) is reset first."""
        blob = np.load(path)
        if merge:
            data = np.asarray(jax.device_get(self.state.data)).copy()
        else:
            data = np.zeros(
                (self.n, self.capacity + 1,
                 NUM_FIXED + self.mf_dim + self.opt_ext),
                np.float32)
            self.indexes = [HostKV(self.capacity) for _ in range(self.n)]
            self._touched[:] = False
        total = 0
        mf_end = NUM_FIXED + self.mf_dim
        for s, (keys, fields) in enumerate(self._file_per_shard(blob)):
            rows = self.indexes[s].assign(keys)
            for f in FIELDS:
                field_assign(data[s], rows, f, fields[f])
            if self.opt_ext:
                if "opt_ext" in fields \
                        and fields["opt_ext"].shape[1] == self.opt_ext:
                    data[s][rows, mf_end:mf_end + self.opt_ext] = \
                        fields["opt_ext"]
                elif len(keys):
                    # keep the log honest: starting "fresh" must also hold
                    # under merge=True, where the loaded rows may carry live
                    # optimizer state from before the load
                    data[s][rows, mf_end:mf_end + self.opt_ext] = 0.0
                    log.warning("load: file has no matching opt_ext block "
                                "for shard %d; optimizer state starts "
                                "fresh", s)
            total += len(keys)
        self.state = TableState.from_logical(data, self.capacity,
                                             ext=self.opt_ext)
        self._reset_dev_indexes()
        return total

    # ---- lifecycle: shrink / merge (box_wrapper.h:638-640,801-815) ----
    def shrink(self, delete_threshold: Optional[float] = None,
               decay: Optional[float] = None) -> int:
        """ShrinkTable over every HBM shard: decay show/clk/delta_score,
        drop rows whose decayed score falls below threshold — the same
        accessor rules as EmbeddingTable.shrink (ps/table.py), applied
        shard-parallel on the stacked state."""
        thr = (FLAGS.shrink_delete_threshold
               if delete_threshold is None else delete_threshold)
        dk = FLAGS.show_click_decay_rate if decay is None else decay
        freed_total = 0
        with self.host_lock:
            data = np.asarray(jax.device_get(self.state.data)).copy()
            data[:, :, 0:3] *= dk
            for s in range(self.n):
                keys, rows = self.indexes[s].items()
                if len(keys) == 0:
                    continue
                show, clk = data[s][rows, 0], data[s][rows, 1]
                score = (self.cfg.nonclk_coeff * (show - clk)
                         + self.cfg.clk_coeff * clk)
                drop = score < thr
                freed = self.indexes[s].release(keys[drop])
                data[s][freed] = 0.0
                self._touched[s][freed] = False
                freed_total += len(freed)
            self._reset_dev_indexes()
            self.state = TableState.from_logical(data, self.capacity,
                                                 ext=self.opt_ext)
        log.info("sharded shrink: freed %d rows across %d shards",
                 freed_total, self.n)
        return freed_total

    def _file_per_shard(self, blob):
        """(keys, fields-dict) per owner shard from a save file — fast
        path when the file's shard count matches; otherwise (different
        mesh size, or a single-table EmbeddingTable/HostStore save) keys
        re-split by key % N."""
        want = list(FIELDS) + (["opt_ext"] if self.opt_ext else [])
        if "n" in blob and int(blob["n"]) == self.n \
                and all(f"keys_{s}" in blob for s in range(self.n)):
            for s in range(self.n):
                fields = {f: blob[f"{f}_{s}"] for f in want
                          if f"{f}_{s}" in blob}
                yield blob[f"keys_{s}"], fields
            return
        if "n" in blob:
            # tolerate files holding only SOME shards (a multihost
            # per-process save): concatenate what is present — the
            # key%N re-split below re-derives ownership either way
            fn = int(blob["n"])
            present = [s for s in range(fn) if f"keys_{s}" in blob]
            if present:
                keys = np.concatenate([blob[f"keys_{s}"]
                                       for s in present])
                fields = {f: np.concatenate([blob[f"{f}_{s}"]
                                             for s in present])
                          for f in want if f"{f}_{present[0]}" in blob}
            else:
                keys = np.zeros(0, np.uint64)
                fields = {}
        else:
            keys = blob["keys"]
            fields = {f: blob[f] for f in want if f in blob}
        owners = (np.ascontiguousarray(keys, np.uint64)
                  % np.uint64(self.n)).astype(np.int64)
        for s in range(self.n):
            m = owners == s
            yield keys[m], {f: v[m] for f, v in fields.items()}

    def merge_model(self, path: str) -> int:
        """MergeModel (box_wrapper.h:801-803) shard-parallel: keys present
        in both ACCUMULATE show/clk/delta_score and keep live weights /
        optimizer state; unseen keys insert wholesale. Accepts sharded
        saves (any shard count) and single-table saves (split by key%N)."""
        blob = np.load(path)
        mf_end = NUM_FIXED + self.mf_dim
        total = 0
        with self.host_lock:
            data = np.asarray(jax.device_get(self.state.data)).copy()
            for s, (keys, fields) in enumerate(self._file_per_shard(blob)):
                if len(keys) == 0:
                    continue
                existing = self.indexes[s].lookup(keys) >= 0
                rows_new = self.indexes[s].assign(keys[~existing])
                for f in FIELDS:
                    field_assign(data[s], rows_new, f, fields[f][~existing])
                if self.opt_ext and "opt_ext" in fields \
                        and fields["opt_ext"].shape[1] == self.opt_ext:
                    data[s][rows_new, mf_end:] = fields["opt_ext"][~existing]
                rows_old = self.indexes[s].lookup(keys[existing])
                for f in ("show", "clk", "delta_score"):
                    data[s][rows_old, FIELD_COL[f]] += fields[f][existing]
                rows_all = self.indexes[s].lookup(keys)
                self._touched[s][rows_all] = True
                total += len(keys)
            self._reset_dev_indexes()
            self.state = TableState.from_logical(data, self.capacity,
                                                 ext=self.opt_ext)
        log.info("sharded merge_model: %d rows from %s", total, path)
        return total

    def merge_models(self, paths, update_type: str = "stats") -> int:
        """MergeMultiModels (box_wrapper.h:812-815): "stats" accumulates
        per file (merge_model); "overwrite" applies each file as a delta
        (load(merge=True) — later files win)."""
        if update_type not in ("stats", "overwrite"):
            raise ValueError(f"unknown update_type {update_type!r}")
        total = 0
        for p in paths:
            total += (self.merge_model(p) if update_type == "stats"
                      else self.load(p, merge=True))
        return total
