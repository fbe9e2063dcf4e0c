"""HBM-resident embedding table — the BoxPS/HeterPS store, single shard.

Reference capabilities re-implemented (SURVEY.md §2.1-2.2):
- ``BoxWrapper::PullSparse/PushSparseGrad`` (fleet/box_wrapper.h:488,526)
  with key dedup (``DedupKeysAndFillIdx``, box_wrapper_impl.h:129);
- the HeterPS GPU hashtable value store (heter_ps/hashtable.h:113,
  feature_value.h:570 ``FeatureValue`` layout) with in-table optimizer
  application (optimizer.cuh.h);
- pass/save lifecycle hooks (BeginPass/EndPass/SaveBase/SaveDelta/
  ShrinkTable, box_wrapper.cc:171-186,1383-1415).

TPU-native redesign: XLA needs static shapes, so the device side is a
statically-sized SoA of ``[capacity+1]`` arrays (row ``capacity`` is a
permanent zero "sentinel" used for padding); the key→row mapping is a host
hash index updated during batch preparation (overlapped with device compute
by the trainer's prefetch pipeline). Per-batch key dedup happens on host
(np.unique == DedupKeysAndFillIdx), so the device step is three fused ops:
gather unique rows → model fwd/bwd → segment-sum grads + one scatter update.
No dynamic growth inside jit — the riskiest reference behavior (SSD-backed
dynamic hashtable) maps to host-index growth + static device capacity
(+ Phase-5 host backing store).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.config import FLAGS
from paddlebox_tpu.data.batch import SlotBatch
from paddlebox_tpu.ops.pallas_kernels import _book_dispatch, gather_rows
from paddlebox_tpu.ps.sgd import (RowState, SparseSGDConfig,
                                  opt_ext_width, sparse_update)
from paddlebox_tpu.utils.compile_cache import enable_compilation_cache
from paddlebox_tpu.utils.logging import get_logger

log = get_logger(__name__)


NUM_FIXED = 8  # scalar columns before the embedx block


def _f_pad(feat: int) -> int:
    """The padded logical row width: the smallest divisor of 128 ≥ feat,
    so that rows pack evenly into 128-lane storage lines; a row wider
    than one line takes whole lines (the next multiple of 128)."""
    for d in (1, 2, 4, 8, 16, 32, 64, 128):
        if d >= feat:
            return d
    return -(-feat // 128) * 128


def _lane_onehot(sub: jax.Array, rpl: int, dtype) -> jax.Array:
    """[..., 1]-hot row-in-line selector mask (THE lane-packing
    selector, shared by gather_full_rows / expand_pull / merge_rows /
    apply_push): 1.0 at each element's row slot within its 128-lane
    line, 0 elsewhere."""
    return (jnp.arange(rpl, dtype=jnp.int32)[None, :]
            == sub.astype(jnp.int32)[:, None]).astype(dtype)


def _lane_select(mask: jax.Array, values: jax.Array) -> jax.Array:
    """Masked lane select: ``where(mask, values, 0)`` with the [N, rpl]
    one-hot broadcast over the trailing feature axis. Semantically the
    ``mask * values`` reduce every lane-packing site used to do, but
    NaN-ISOLATING: ``0 * NaN`` is NaN, so one diverging row's NaN used
    to bleed into every healthy row sharing its 128-lane storage line
    (and, through the scatter-add transpose, into their updates) —
    ``where`` keeps a NaN confined to its own lane span, which is what
    lets telemetry localize a NaN to ONE key.
    Exact f32 either way (select, no arithmetic)."""
    return jnp.where(mask.astype(bool)[:, :, None], values, 0)


def pack_geometry(capacity: int, feat: int):
    """(rows_per_line, f_pad, n_lines) for a [capacity+1, feat] logical
    table stored as [n_lines, 128] lane-aligned lines. A WIDE row (feat >
    128, a token's vector) is ``f_pad // 128`` whole consecutive lines:
    rows_per_line is then 1 and row r starts at line ``r * (f_pad //
    128)``."""
    fp = _f_pad(feat)
    if fp > 128:
        return 1, fp, (capacity + 1) * (fp // 128)
    rpl = 128 // fp
    n_lines = (capacity + 1 + rpl - 1) // rpl
    return rpl, fp, n_lines


def _row_lines(rows: jax.Array, fp: int) -> jax.Array:
    """Line ids [n * lpr] of wide rows ``rows`` [n] (``lpr = fp // 128``
    consecutive lines a row), row-major."""
    lpr = fp // 128
    return (rows[:, None] * lpr
            + jnp.arange(lpr, dtype=rows.dtype)[None, :]).reshape(-1)


def unpack_host(packed: np.ndarray, capacity: int, feat: int) -> np.ndarray:
    """Packed [..., L, 128] → logical [..., C+1, F] (numpy; returns a
    copy only for the final column slice)."""
    rpl, fp, n_lines = pack_geometry(capacity, feat)
    lead = packed.shape[:-2]
    flat = packed.reshape(*lead, n_lines * 128 // fp, fp)
    return flat[..., :capacity + 1, :feat]


def pack_host(logical: np.ndarray, capacity: int, feat: int) -> np.ndarray:
    """Logical [..., C+1, F] → packed [..., L, 128] (numpy)."""
    rpl, fp, n_lines = pack_geometry(capacity, feat)
    lead = logical.shape[:-2]
    out = np.zeros((*lead, n_lines * 128 // fp, fp), logical.dtype)
    out[..., :capacity + 1, :feat] = logical
    return out.reshape(*lead, n_lines, 128)


@jax.tree_util.register_pytree_node_class
class TableState:
    """AoS feature-value store in PACKED line layout.

    Logical view: ``[..., C+1, 8+mf_dim]`` rows mirroring the reference's
    contiguous ``FeatureValue`` struct (feature_value.h:570) — cols 0..7
    = show, clk, delta_score, slot, embed_w, embed_g2sum, embedx_g2sum,
    mf_size; cols 8.. = embedx_w. Row C is the zero sentinel used by
    padding (pads that alias real storage lines read the zeroed padding
    columns instead — same zeros).

    Physical storage: ``packed [..., L, 128]`` with ``128 // f_pad``
    logical rows per 128-lane line (f_pad = feat rounded up to a divisor
    of 128). Why: XLA lays [C+1, 16] out COLUMN-major on TPU (minor dim
    must tile to 128 lanes without 8x padding), which makes every row
    gather/scatter touch 16 strided tiles — measured 2.2x slower than
    one contiguous line per row. The packed layout keeps rows lane-
    contiguous at zero memory waste; gathers fetch whole lines and
    extract in-register, pushes scatter-ADD masked line deltas: one
    [U, 128] delta line a unique slot, zero outside the row's lane span,
    so rows that share a line add into disjoint lanes (exact, in any
    order) and the scatter's line indices REPEAT — it is never a
    ``unique_indices`` scatter. Its cost is per slot of the unique axis,
    pad or real, not per line of the table (measured, PERF.md §6 PR 27):
    a unique axis that knows its distinct count lets the gather and the
    push stop there (``num_unique`` of gather_full_rows / apply_push).

    Why AoS and not per-field SoA: a TPU scatter/gather costs per INDEX,
    not per byte — nine per-field scatters were 9x the price of one
    row-matrix scatter. Host-side mirrors (HostStore) derive their
    layouts from FIELDS/TWO_D_FIELDS below; host code converts with
    pack_host/unpack_host (or the ``.data`` logical property)."""

    def __init__(self, packed: jax.Array, capacity: int, feat: int,
                 ext: int = 0) -> None:
        self.packed = packed
        self._capacity = int(capacity)
        self._feat = int(feat)
        # optimizer extension width appended after embedx_w
        # (ps/sgd.opt_ext_width): feat = NUM_FIXED + mf_dim + ext
        self._ext = int(ext)

    @classmethod
    def from_logical(cls, data, capacity: Optional[int] = None,
                     ext: int = 0) -> "TableState":
        """Build from a logical [..., C+1, F] matrix (host np or jnp)."""
        cap = data.shape[-2] - 1 if capacity is None else capacity
        feat = data.shape[-1]
        packed = pack_host(np.asarray(data), cap, feat)
        return cls(jnp.asarray(packed), cap, feat, ext)

    def tree_flatten(self):
        return (self.packed,), (self._capacity, self._feat, self._ext)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    def with_packed(self, packed: jax.Array) -> "TableState":
        return TableState(packed, self._capacity, self._feat, self._ext)

    @property
    def geometry(self):
        return pack_geometry(self._capacity, self._feat)

    @property
    def data(self) -> jax.Array:
        """LOGICAL [..., C+1, F] view (materialized — host/save paths and
        tests; the jit hot path uses gather_full_rows/apply_push on
        ``packed`` directly)."""
        rpl, fp, n_lines = self.geometry
        lead = self.packed.shape[:-2]
        flat = self.packed.reshape(*lead, n_lines * 128 // fp, fp)
        return flat[..., :self._capacity + 1, :self._feat]

    @property
    def show(self) -> jax.Array:
        return self.data[..., 0]

    @property
    def clk(self) -> jax.Array:
        return self.data[..., 1]

    @property
    def delta_score(self) -> jax.Array:
        return self.data[..., 2]

    @property
    def slot(self) -> jax.Array:
        return self.data[..., 3]

    @property
    def embed_w(self) -> jax.Array:
        return self.data[..., 4]

    @property
    def embed_g2sum(self) -> jax.Array:
        return self.data[..., 5]

    @property
    def embedx_g2sum(self) -> jax.Array:
        return self.data[..., 6]

    @property
    def mf_size(self) -> jax.Array:
        return self.data[..., 7]

    @property
    def embedx_w(self) -> jax.Array:
        return self.data[..., NUM_FIXED:NUM_FIXED + self.mf_dim]

    @property
    def opt_ext(self) -> jax.Array:
        return self.data[..., NUM_FIXED + self.mf_dim:]

    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def ext(self) -> int:
        return self._ext

    @property
    def mf_dim(self) -> int:
        return self._feat - NUM_FIXED - self._ext


# field-name → column mapping (host mirrors and save files use names)
FIELD_COL = {"show": 0, "clk": 1, "delta_score": 2, "slot": 3,
             "embed_w": 4, "embed_g2sum": 5, "embedx_g2sum": 6,
             "mf_size": 7}
FIELDS = tuple(FIELD_COL) + ("embedx_w",)
TWO_D_FIELDS = ("embedx_w",)  # [*, mf_dim] blocks; all others are scalar


def field_slice(data, name: str):
    """Column view of a field on a data matrix (numpy or jax)."""
    if name == "embedx_w":
        return data[..., NUM_FIXED:]
    return data[..., FIELD_COL[name]]


def field_assign(data: np.ndarray, rows: np.ndarray, name: str,
                 values: np.ndarray) -> None:
    """Write counterpart of field_slice: data[rows, <field cols>] = values.
    The single place that knows which fields are the embedx block (whose
    width follows the values — tables with an optimizer extension write
    mf-only blocks, field_slice round-trips write the full tail)."""
    if name == "embedx_w":
        data[rows, NUM_FIXED:NUM_FIXED + values.shape[-1]] = values
    else:
        data[rows, FIELD_COL[name]] = values


def next_bucket(minimum: int, need: int) -> int:
    """Power-of-two padding ladder: the smallest doubling of ``minimum``
    that is ≥ ``need`` (bounds distinct XLA compilations). THE bucket
    rule for unique-row capacities across all index builders."""
    cap = minimum
    while cap < need:
        cap *= 2
    return cap


def next_bucket_fine(minimum: int, need: int) -> int:
    """FINE bucket ladder for resident whole-pass shapes: round ``need``
    up to a step of ~1/16 its magnitude (pow2 steps, ≥512). A resident
    pass compiles one runner for its uniform shape either way, so the
    pow2 ladder's ≤100% padding is pure wire waste — this caps it at
    ~6% while steps stay coarse enough that successive passes of one
    workload almost always land on the same rung (bounded recompiles).
    Steps are multiples of 512, preserving the wire packers' alignment
    (pack_u18/pack_u16m need length % 4 == 0)."""
    if need <= minimum:
        return minimum  # exactly-tuned minimums stay padding-free
    step = max(512, 1 << max(need.bit_length() - 5, 0))
    return -(-need // step) * step


def _flatten_sharded_blob(blob):
    """Adapt a sharded-format save (``n`` + per-shard ``keys_s``/field_s
    blocks, written by ShardedEmbeddingTable._dump and the tiered table)
    to the single-table mapping ``load``/``merge_model`` consume."""
    if "n" not in blob:
        return blob
    fn = int(blob["n"])
    out = {"keys": np.concatenate([blob[f"keys_{s}"] for s in range(fn)])}
    for f in list(FIELDS) + ["opt_ext"]:
        if f"{f}_0" in blob:
            out[f] = np.concatenate([blob[f"{f}_{s}"] for s in range(fn)])
    return out


def store_fields_from_rows(sub: np.ndarray, mf_dim: int, opt_ext: int,
                           slot_override: Optional[np.ndarray] = None
                           ) -> Dict[str, np.ndarray]:
    """Logical rows [k, feat] → HostStore field dict — THE shared
    write-back assembly (tiered/pass-scoped end_pass + eviction).
    embedx is sliced to mf_dim explicitly: field_slice's tail is
    unbounded and would leak the opt_ext columns into the host store's
    (k, mf_dim) array. ``slot_override`` substitutes host slot metadata
    for tables that do not maintain the device slot column."""
    mf_end = NUM_FIXED + mf_dim
    vals = {f: (sub[:, NUM_FIXED:mf_end] if f == "embedx_w"
                else field_slice(sub, f)) for f in FIELDS}
    if slot_override is not None:
        vals["slot"] = slot_override
    if opt_ext:
        vals["opt_ext"] = sub[:, mf_end:]
    return vals


def rows_from_store_fields(vals: Dict[str, np.ndarray], mf_dim: int,
                           opt_ext: int) -> np.ndarray:
    """HostStore field dict → logical rows [k, feat] (the scatter input
    of delta staging) — inverse of store_fields_from_rows."""
    k = len(vals["show"])
    mf_end = NUM_FIXED + mf_dim
    out = np.zeros((k, mf_end + opt_ext), np.float32)
    idx = np.arange(k)
    for f in FIELDS:
        field_assign(out, idx, f, vals[f])
    if opt_ext:
        out[:, mf_end:] = vals["opt_ext"]
    return out


def promote_window_delta(index, touched: np.ndarray, capacity: int,
                         want_keys: np.ndarray, new_keys: np.ndarray,
                         gather_rows, writeback, on_freed=None,
                         pending: Optional[np.ndarray] = None,
                         protect: Optional[np.ndarray] = None):
    """THE shared per-window delta-promotion core (tiered shards and the
    single-chip PassScopedTable — box_wrapper.cc:129-186's incremental
    window, one place): reconcile the staged delta against the live
    window (keys that became resident since stage() keep their fresher
    rows), evict only under capacity pressure (clean rows first; dirty
    evictees go through ``writeback(keys, rows, gather_rows(rows))``),
    assign the remaining new keys as clean rows.

    ``pending`` (sorted uint64) lists keys whose rows were assigned by
    a ROUTING-PLAN build before their values staged (the overlapped
    preloader, ps/tiered.plan_scope): they look resident to the index
    but hold fresh ZERO rows, so the usual resident-is-fresher rule
    must NOT apply — their staged values win, and their (plan-baked)
    rows are pinned against eviction.

    ``protect`` lists additional keys PINNED against eviction: with the
    depth-N pass pipeline (ps/tiered stage queue) several FUTURE passes'
    working sets may be staged ahead of this begin — evicting a queued
    pass's resident row would invalidate the missing-split its stage
    already computed (the capacity contract is the union over open +
    queued passes; ps/tiered.py module docstring).

    Caller holds the host lock and scatters the staged values for the
    returned ``rows_new``. Returns (rows_new, still_missing_mask,
    stats) — ``stats["evict_sec"]`` is the wall spent in the eviction
    block (the begin-boundary's inline/emergency eviction cost; the
    async lane's eviction is accounted by the table).
    ``on_freed(rows)`` hooks per-row host metadata cleanup."""
    miss = index.lookup(new_keys) < 0
    still = miss
    if pending is not None and len(pending):
        still = miss | np.isin(new_keys, pending, assume_unique=False)
    ins_keys = new_keys[still]
    stats = dict(resident=len(want_keys) - len(ins_keys),
                 staged=len(ins_keys), evicted=0, evicted_writeback=0,
                 evict_sec=0.0)
    # capacity pressure counts only truly-missing keys: pending keys
    # already own rows, re-assigning them allocates nothing
    overflow = len(index) + int(miss.sum()) - capacity
    if overflow > 0:
        t0 = time.perf_counter()
        live_keys, live_rows = index.items()
        cand = ~np.isin(live_keys, want_keys)
        if pending is not None and len(pending):
            # plan-baked rows for a FUTURE pass: their row ids are
            # already encoded in that pass's staged wire — evicting
            # them would hand the rows to other keys
            cand &= ~np.isin(live_keys, pending)
        if protect is not None and len(protect):
            cand &= ~np.isin(live_keys, protect)
        ck, cr = live_keys[cand], live_rows[cand]
        t = touched[cr]
        order = np.argsort(t, kind="stable")[:overflow]
        ck, cr, t = ck[order], cr[order], t[order]
        if t.any():
            writeback(ck[t], cr[t], gather_rows(cr[t]))
            stats["evicted_writeback"] = int(t.sum())
        freed = index.release(ck)
        touched[freed] = False
        if on_freed is not None:
            on_freed(freed)
        stats["evicted"] = len(ck)
        stats["evict_sec"] = time.perf_counter() - t0
    rows_new = index.assign(ins_keys)
    touched[rows_new] = False  # freshly loaded = clean
    from paddlebox_tpu.obs.hub import get_hub
    hub = get_hub()
    if hub.active:  # per-pass window accounting → Prometheus counters
        for k, help_txt in (("staged", "rows fetched+scattered into the "
                             "HBM window"),
                            ("resident", "working-set rows already "
                             "resident at begin_pass"),
                            ("evicted", "rows evicted under capacity "
                             "pressure"),
                            ("evicted_writeback", "dirty evictions "
                             "written back to the host tier")):
            if stats[k]:
                hub.counter(f"pbox_table_{k}_rows_total",
                            help_txt).inc(stats[k])
    return rows_new, still, stats


_ROW_GATHER_FNS: Dict[tuple, object] = {}


def dispatch_packed_row_gather(state: "TableState", shard: Optional[int],
                               rows: np.ndarray) -> Tuple[jax.Array, int]:
    """Dispatch a ``[bucket, feat]`` logical-row gather straight off the
    packed lines (shard ``shard`` of a stacked [N, L, 128] state, or the
    single table with ``shard=None``) and return the un-fetched device
    array + the real row count (callers slice ``[:k]`` after
    ``device_get``).

    THE async-epilogue D2H primitive (ps/epilogue): end_pass must
    dispatch its gathers before returning (the dispatch pins the
    immutable buffers against a later donating jit step), so dispatch
    cost IS the end_pass critical path. Eager ops re-trace per call and
    touch the full packed buffer (~0.8 s/dispatch measured on the CPU
    bench at 4M rows); this is ONE jitted executable per table geometry
    — row indices pad to a pow2 bucket (pads read the zero sentinel
    row), so delta-sized passes reuse the compile."""
    rpl, fp, _ = state.geometry
    feat = state._feat
    k = len(rows)
    bucket = next_bucket(1024, max(k, 1))
    idx = np.full(bucket, state.capacity, np.int32)  # pads → sentinel
    idx[:k] = rows
    sharded = shard is not None
    key = (sharded, rpl, fp, feat)
    fn = _ROW_GATHER_FNS.get(key)
    if fn is None:
        cols = jnp.arange(feat, dtype=jnp.int32)

        if fp > 128:   # wide rows: whole lines, then the row's width
            def run(packed, *args):
                idx = args[-1]
                src = packed[args[0]] if sharded else packed
                return src[_row_lines(idx, fp)].reshape(-1, fp)[:, :feat]
        elif sharded:
            def run(packed, s, idx):
                lines = packed[s, idx // rpl]            # [K, 128]
                off = (idx % rpl * fp)[:, None] + cols[None, :]
                return jnp.take_along_axis(lines, off, axis=1)
        else:
            def run(packed, idx):
                lines = packed[idx // rpl]
                off = (idx % rpl * fp)[:, None] + cols[None, :]
                return jnp.take_along_axis(lines, off, axis=1)
        fn = jax.jit(run)
        _ROW_GATHER_FNS[key] = fn
    if sharded:
        out = fn(state.packed, jnp.asarray(shard, jnp.int32),
                 jnp.asarray(idx))
    else:
        out = fn(state.packed, jnp.asarray(idx))
    return out, k


def host_pull_block(vals: np.ndarray, mf_dim: int) -> np.ndarray:
    """[k, F] gathered logical rows → [k, 3+mf] pull values (show, clk,
    embed_w, mf_size-gated embedx) — THE host-side CopyForPull block
    assembly, shared by every host pull (EmbeddingTable.host_pull,
    MultiMfShardedTable.pull)."""
    mf_end = NUM_FIXED + mf_dim
    gate = vals[:, FIELD_COL["mf_size"]:FIELD_COL["mf_size"] + 1] > 0
    return np.concatenate(
        [vals[:, FIELD_COL["show"]:FIELD_COL["clk"] + 1],
         vals[:, FIELD_COL["embed_w"]:FIELD_COL["embed_w"] + 1],
         vals[:, NUM_FIXED:mf_end] * gate], axis=1)


def dedup_first_seen(keys: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Dedup ``keys`` in FIRST-SEEN order → (uniq, first_idx, inv).

    The bulk pass-assign front half (EmbeddingTable.bulk_assign_unique):
    dedup runs OUTSIDE host_lock, and first-seen order makes the single
    bulk ``index.assign`` allocate new rows in exactly the order a
    serial batch-by-batch walk of the native hash index would (the
    native assign_unique is first-occurrence by construction), so bulk
    and per-batch builds are row-for-row identical there.

    Routed through the native one-pass dedup (ps/kv.
    dedup_first_seen_native) when the library is available — the
    python formulation below walks the stream three times (unique +
    argsort + rank scatter); both produce bitwise-identical outputs
    (tests/test_pallas_index.py gates it), and the cut shows up in
    ``pbox_preload_build_seconds_total{stage=dedup}``."""
    from paddlebox_tpu.ps.kv import dedup_first_seen_native
    out = dedup_first_seen_native(keys)
    if out is not None:
        return out
    return _dedup_first_seen_py(keys)


def _dedup_first_seen_py(keys: np.ndarray
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The pure-python three-pass formulation (the oracle the native
    and device paths are gated against)."""
    uniq_s, first_s, inv_s = np.unique(keys, return_index=True,
                                       return_inverse=True)
    order = np.argsort(first_s, kind="stable")
    rank = np.empty(len(order), np.int64)
    rank[order] = np.arange(len(order), dtype=np.int64)
    return uniq_s[order], first_s[order], rank[inv_s]


def dedup_slotted_first_seen(keys: np.ndarray, slots: np.ndarray
                             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Dedup a pass's ``(key, slot)`` stream in FIRST-SEEN order →
    (uniq keys, their slots as uint16, inv int32).

    The compact-wire build's front half (train/device_pass.
    ResidentPass._compact_tail): it runs OUTSIDE host_lock, and the
    index is then walked with the distinct pairs only. A walk of the
    first occurrences in stream order allocates new rows exactly as a
    walk of the whole stream does (a repeat is a lookup), and a key seen
    again under another slot stays a pair of its own, so the index
    answers it as it would in the stream: its first row, local -1."""
    from paddlebox_tpu.ps.kv import dedup_slotted_first_seen_native
    out = dedup_slotted_first_seen_native(keys, slots)
    if out is not None:
        return out
    return _dedup_slotted_first_seen_py(keys, slots)


def _dedup_slotted_first_seen_py(keys: np.ndarray, slots: np.ndarray
                                 ) -> Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
    """The numpy formulation (no native library; the oracle the native
    route is gated against): sort by (slot, key), rank the groups by
    their first stream position."""
    keys = np.asarray(keys, np.uint64)
    slots = np.asarray(slots, np.uint16)
    order = np.lexsort((keys, slots))  # stable: a group's head is its
    ks, ss = keys[order], slots[order]  # first stream position
    head = np.ones(len(order), bool)
    head[1:] = (ks[1:] != ks[:-1]) | (ss[1:] != ss[:-1])
    first = order[head]
    by_first = np.argsort(first, kind="stable")
    rank = np.empty(len(first), np.int32)
    rank[by_first] = np.arange(len(first), dtype=np.int32)
    inv = np.empty(len(order), np.int32)
    inv[order] = rank[np.cumsum(head) - 1]
    first = first[by_first]
    return keys[first], slots[first], inv


def fill_oob_pads(unique_rows: np.ndarray, u: int, capacity: int) -> None:
    """Fill positions [u:] with DISTINCT out-of-bounds row ids (> capacity).

    This is the unique-scatter invariant shared by every host index
    builder: pads must never collide with real rows OR each other, so
    gathers through them clamp to the zero sentinel row and scatters
    drop them (apply_push promises no ``unique_indices``: it scatters
    lines, and rows sharing a line repeat one)."""
    n = len(unique_rows) - u
    unique_rows[u:] = capacity + np.arange(1, n + 1, dtype=np.int32)


class PullIndex(NamedTuple):
    """Host-built per-batch dedup index (DedupKeysAndFillIdx analogue)."""

    unique_rows: np.ndarray  # int32 [U_pad]; pads → sentinel row C
    gather_idx: np.ndarray   # int32 [K_pad]; pads → sentinel slot
    key_valid: np.ndarray    # f32   [K_pad]; 1.0 for real keys
    num_unique: int


# Host key→row index implementations live in ps/kv.py (native C++ fast path
# + python fallback). HostKV is the factory used across the tables.
from paddlebox_tpu.ps.kv import make_kv as HostKV  # noqa: N813


def init_table_state(capacity: int, mf_dim: int,
                     dtype=jnp.float32, ext: int = 0) -> TableState:
    # every entry (trainers, serving, bench, chip_smoke) builds a table
    # before its first compile — the one shared point that turns the
    # persistent compilation cache on
    enable_compilation_cache()
    feat = NUM_FIXED + mf_dim + ext
    _, _, n_lines = pack_geometry(capacity, feat)
    return TableState(jnp.zeros((n_lines, 128), dtype), capacity, feat,
                      ext)


#: slots one trip of the counted gather / push loops visits (below)
PUSH_CHUNK = 8192


def push_chunk(u: int) -> int:
    """Slots a trip of the counted loops visits on a unique axis of
    ``u`` slots (``gather_full_rows`` / ``apply_push`` given a
    ``num_unique``): a constant of the shapes, never of the data."""
    return min(PUSH_CHUNK, u)


def push_chunks(u: int, num_unique: Optional[jax.Array]):
    """Trips the counted loops make over ``[0, num_unique)``: int32
    ``ceil(num_unique / push_chunk(u))``, computed on the device; with
    no count, the trips that cover the whole axis (an int)."""
    c = push_chunk(u)
    if num_unique is None:
        return -(-u // c)
    return (num_unique.astype(jnp.int32) + (c - 1)) // c


def _chunk_start(i: jax.Array, c: int, u: int) -> jax.Array:
    """First slot of trip ``i``. Where ``c`` does not divide ``u`` the
    last trip is moved back to end at ``u`` and so overlaps the one
    before it: its slots below ``i * c`` were visited already."""
    return jnp.minimum(i * c, u - c)


def _extract_rows(state: TableState, rows: jax.Array) -> jax.Array:
    """Line gather + in-register extract of clamped row ids → [n, F]."""
    rpl, fp, _ = state.geometry
    n = rows.shape[0]
    if fp > 128:
        # a wide row is whole lines: nothing to extract in-register
        _book_dispatch("gather_rows", "xla")
        lines = state.packed[_row_lines(rows, fp)]
        return lines.reshape(n, fp)[:, :state._feat]
    if FLAGS.use_pallas_gather:
        _book_dispatch("gather_rows", "pallas")
        lines = gather_rows(state.packed, rows // rpl)
    else:
        _book_dispatch("gather_rows", "xla")
        lines = state.packed[rows // rpl]                 # [n, 128]
    grouped = lines.reshape(n, rpl, fp)
    onehot = _lane_onehot(rows % rpl, rpl, lines.dtype)   # [n, rpl]
    # elementwise mask+reduce, NOT einsum (default-precision dot_general
    # would round through bf16 on TPU); where-select, NOT multiply, so a
    # NaN row cannot bleed across its storage line (_lane_select)
    vals = _lane_select(onehot, grouped).sum(axis=1)
    return vals[:, :state._feat] if fp != state._feat else vals


def gather_full_rows(state: TableState, unique_rows: jax.Array,
                     num_unique: Optional[jax.Array] = None) -> jax.Array:
    """ONE line-gather of complete feature rows → [U, 8+mf_dim].

    Each logical row lives lane-contiguous inside one 128-wide storage
    line (see TableState); the gather fetches whole lines and a ONE-HOT
    mask + sum over the rows-per-line axis extracts the row's slice
    in-register. The earlier take_along_axis extract lowered to a SECOND
    per-index gather and cost as much as the line fetch itself — the
    mask extract is pure VPU work. Pad/OOB ids are clamped to
    the SENTINEL row before the line split so they read its zeros —
    clamping raw line indices instead would let a far-OOB id alias a
    real row when capacity % rows_per_line == rpl-1.

    ``num_unique`` (int32 scalar ON THE DEVICE, or None): the caller's
    promise that every slot at or after it is a pad. A TPU gather costs
    per index, pad or real, so with a count the gather runs over
    ``[0, num_unique)`` only, as ``push_chunks`` trips of ``push_chunk``
    slots (a ``fori_loop`` whose trip count is data), each writing its
    rows into a zero [U, F] buffer. The slots never visited stay zero,
    which is what a pad reads from the zero sentinel row: the result is
    bit-identical to the single gather's. ``None`` is the single gather,
    for every unique axis that a host already cut to the distinct
    count's bucket (the dedup wire, ``DeviceBatch``, the sharded
    steps)."""
    rows = jnp.minimum(unique_rows, state.capacity)
    if num_unique is None:
        return _extract_rows(state, rows)
    u = rows.shape[0]
    c = push_chunk(u)

    def body(i, buf):
        at = _chunk_start(i, c, u)
        # an overlapping last trip rewrites what the trip before wrote
        vals = _extract_rows(state, jax.lax.dynamic_slice(rows, (at,), (c,)))
        return jax.lax.dynamic_update_slice(buf, vals, (at, 0))

    return jax.lax.fori_loop(
        0, push_chunks(u, num_unique), body,
        jnp.zeros((u, state._feat), state.packed.dtype))


_SCATTER_CHUNK_FNS: Dict[tuple, object] = {}


def _scatter_chunk_fn(sharded: bool, rpl: int, fp: int, feat: int):
    """Jitted FIXED-SHAPE chunk scatter (one executable per geometry ×
    chunk size, reused across every pass boundary): rows arrive padded
    to the chunk with out-of-bounds line ids, ``mode="drop"`` discards
    them. The packed buffer is DONATED — the caller must treat the input
    state as consumed."""
    key = (sharded, rpl, fp, feat)
    fn = _SCATTER_CHUNK_FNS.get(key)
    if fn is not None:
        return fn
    cols_off = jnp.arange(feat, dtype=jnp.int32)

    if fp > 128:   # wide rows: whole lines, the pad columns zero as ever
        lpr = fp // 128

        def run(packed, *args):
            rows_c, vals_c = args[-2:]
            lines = _row_lines(jnp.minimum(rows_c, packed.shape[-2] // lpr),
                               fp)
            vals = jnp.pad(vals_c, ((0, 0), (0, fp - feat))).reshape(-1, 128)
            if sharded:
                return packed.at[jnp.repeat(args[0], lpr), lines].set(
                    vals, mode="drop")
            return packed.at[lines].set(vals, mode="drop")
    elif sharded:
        def run(packed, shard_c, rows_c, vals_c):
            lines = rows_c // rpl
            cols = (rows_c % rpl * fp)[:, None] + cols_off[None, :]
            return packed.at[shard_c[:, None], lines[:, None],
                             cols].set(vals_c, mode="drop")
    else:
        def run(packed, rows_c, vals_c):
            lines = rows_c // rpl
            cols = (rows_c % rpl * fp)[:, None] + cols_off[None, :]
            return packed.at[lines[:, None], cols].set(vals_c,
                                                       mode="drop")
    fn = jax.jit(run, donate_argnums=(0,))
    _SCATTER_CHUNK_FNS[key] = fn
    return fn


def scatter_logical_rows(state: TableState, shard_idx,
                         rows: np.ndarray,
                         values: np.ndarray,
                         chunk: Optional[int] = None) -> TableState:
    """Device scatter of logical rows into a packed state — stacked
    [N, L, 128] with ``shard_idx`` per row, or a single table [L, 128]
    with ``shard_idx=None``: row ``rows[k]`` (of shard ``shard_idx[k]``)
    becomes ``values[k]`` (logical width feat). The delta-staging
    primitive (tiered/pass-scoped begin_pass): wire cost is just
    ``values`` — the table itself never crosses the host↔device
    boundary. (shard, row) pairs must be unique; pad columns
    [feat:f_pad] of the line stay untouched (zero by the init/push
    invariants).

    The scatter runs in FIXED-SIZE chunks (``FLAGS.scatter_chunk_rows``)
    so XLA compiles ONE executable per table geometry instead of one per
    delta size — delta sizes vary every pass and each new size would
    pay a fresh compile at the pass boundary. Chunk pads are
    out-of-bounds line ids (dropped on
    device); values ship exact-size and are zero-padded on device, so no
    pad bytes ride the wire. The input state stays VALID (unchanged
    semantics for callers that keep references, e.g. trainers that
    adopted it): one explicit device copy feeds the first chunk and the
    chunks donate intermediates to each other — total table traffic is
    one copy regardless of chunk count."""
    rpl, fp, n_lines = state.geometry
    feat = state._feat
    n = len(rows)
    if n == 0:
        return state
    from paddlebox_tpu.config import FLAGS
    c = int(chunk or FLAGS.scatter_chunk_rows)
    sharded = shard_idx is not None
    rows = np.ascontiguousarray(rows, np.int32)
    if sharded:
        shard_idx = np.ascontiguousarray(shard_idx, np.int32)
        n_shards = state.packed.shape[0]
    vals_np = np.asarray(values)
    fn = _scatter_chunk_fn(sharded, rpl, fp, feat)
    # the chunk executable donates its input; feed it a copy so callers
    # (trainers that adopted this state) keep a live buffer
    packed = jnp.copy(state.packed)
    oob_row = n_lines * rpl  # line index == n_lines → dropped
    np_dtype = np.dtype(packed.dtype)
    for off in range(0, n, c):
        m = min(c, n - off)
        r_c = np.full(c, oob_row, np.int32)
        r_c[:m] = rows[off:off + m]
        if m == c:
            v_c = jnp.asarray(
                np.ascontiguousarray(vals_np[off:off + m], np_dtype))
        else:
            # tail chunk: pad on HOST — a device-side pad
            # (dynamic_update_slice) would compile per remainder size,
            # re-introducing a per-delta compile at the pass boundary;
            # the ≤1-chunk of zero pad bytes compresses on the wire
            v_full = np.zeros((c, feat), np_dtype)
            v_full[:m] = vals_np[off:off + m]
            v_c = jnp.asarray(v_full)
        if sharded:
            s_c = np.full(c, n_shards, np.int32)
            s_c[:m] = shard_idx[off:off + m]
            packed = fn(packed, jnp.asarray(s_c), jnp.asarray(r_c), v_c)
        else:
            packed = fn(packed, jnp.asarray(r_c), v_c)
    return state.with_packed(packed)


def warmup_begin_scatter(state: TableState, sharded: bool,
                         chunk: Optional[int] = None) -> TableState:
    """Compile the begin_pass chunk scatter AHEAD of the first pass
    boundary (a no-op scatter of one dropped row): with the persistent
    compilation cache enabled this also seeds the on-disk cache, so a
    cold process's first delta begin_pass deserializes instead of
    paying the ~20 s scatter compile. Returns the (unchanged-content)
    state."""
    rpl, _, n_lines = state.geometry
    oob = np.array([n_lines * rpl], np.int32)
    z = np.zeros((1, state._feat), np.float32)
    sh = np.array([state.packed.shape[0]], np.int32) if sharded else None
    return scatter_logical_rows(state, sh, oob, z, chunk=chunk)


def aot_warmup_scatter(shape, dtype, sharded: bool, rpl: int, fp: int,
                       feat: int, chunk: Optional[int] = None) -> float:
    """AOT-compile the pass-boundary chunk scatter from
    ``jax.ShapeDtypeStruct`` inputs — NO device buffers are allocated
    (the old warmup materialized a throwaway TABLE-SIZED zeros buffer,
    which could nondeterministically OOM a box whose HBM was already
    committed to the live table + staging). The AOT executable does NOT
    land in jit's dispatch cache, so the warmup's value rides the
    PERSISTENT cache (enabled at table construction,
    ``init_table_state``): the real begin_pass deserializes instead of
    paying the scatter compile. Returns compile seconds (telemetry)."""
    import time as _time
    from paddlebox_tpu.config import FLAGS as _F
    c = int(chunk or _F.scatter_chunk_rows)
    fn = _scatter_chunk_fn(sharded, rpl, fp, feat)
    sds = jax.ShapeDtypeStruct
    args = [sds(shape, dtype)]
    if sharded:
        args.append(sds((c,), jnp.int32))
    args += [sds((c,), jnp.int32), sds((c, feat), dtype)]
    t0 = _time.perf_counter()
    fn.lower(*args).compile()
    return _time.perf_counter() - t0


def start_scatter_warmup(state: TableState, sharded: bool) -> None:
    """Background-compile the pass-boundary chunk scatter at table
    construction (FLAGS.warmup_pass_scatter) via ``aot_warmup_scatter``:
    abstract ShapeDtypeStruct inputs mean the warmup costs ZERO device
    memory — same shapes → same executable in the (persistent) compile
    cache, and the live buffer is never donated behind the backs of
    trainers that already adopted it. Outcome is emitted as a
    ``scatter_warmup`` telemetry event either way (a silent warmup
    failure used to be invisible until the first pass boundary stalled
    ~20 s)."""
    from paddlebox_tpu.config import FLAGS
    if not FLAGS.warmup_pass_scatter:
        return

    rpl, fp, n_lines = state.geometry
    feat = state._feat
    shape = state.packed.shape
    dtype = state.packed.dtype

    def run() -> None:
        from paddlebox_tpu.obs.hub import get_hub
        hub = get_hub()
        try:
            secs = aot_warmup_scatter(shape, dtype, sharded, rpl, fp,
                                      feat)
            if hub.active:
                hub.counter("pbox_scatter_warmup_total",
                            "pass-scatter warmup attempts").inc(
                                outcome="ok")
                hub.emit("scatter_warmup", outcome="ok",
                         compile_sec=round(secs, 3),
                         sharded=sharded, feat=feat)
        except Exception as e:  # warmup only — training still works
            from paddlebox_tpu.utils.logging import get_logger
            get_logger(__name__).warning("pass-scatter warmup failed: %s",
                                         e)
            if hub.active:
                hub.counter("pbox_scatter_warmup_total",
                            "pass-scatter warmup attempts").inc(
                                outcome="failed")
                hub.emit("scatter_warmup", outcome="failed", error=str(e))

    threading.Thread(target=run, daemon=True).start()


def pull_values(rows_full: jax.Array,
                mf_dim: Optional[int] = None) -> jax.Array:
    """Pull-value view of gathered rows → [U, 3+mf_dim] laid out as
    [show, clk, embed_w, embedx…] (FeaturePullValue, feature_value.h:161).
    Non-materialized mf (mf_size==0) reads as zeros, as in CopyForPull.
    ``mf_dim`` must be passed for tables with an optimizer extension
    block (defaults to everything after the fixed columns)."""
    gate = (rows_full[:, 7] > 0).astype(rows_full.dtype)
    end = rows_full.shape[1] if mf_dim is None else NUM_FIXED + mf_dim
    mf = rows_full[:, NUM_FIXED:end] * gate[:, None]
    return jnp.concatenate(
        [rows_full[:, 0:2], rows_full[:, 4:5], mf], axis=1)


def pull_rows(state: TableState, unique_rows: jax.Array) -> jax.Array:
    """gather_full_rows + pull_values (kept for callers that don't reuse
    the full rows for the push)."""
    return pull_values(gather_full_rows(state, unique_rows), state.mf_dim)


def expand_pull(values_u: jax.Array, gather_idx: jax.Array) -> jax.Array:
    """[U, D] unique values → [K, D] per-key-occurrence values.

    LANE-PACKED formulation: the naive ``values_u[gather_idx]``
    row gather — and, worse, its autodiff transpose (the per-unique grad
    merge) — pay XLA's per-index cost on narrow strided rows. Packing
    the unique values into 128-lane lines (8 rows/line at D ≤ 16) makes
    the forward a line fetch + one-hot VPU extract and the TRANSPOSE a
    line-granular scatter-add of masked deltas (the apply_push trick,
    derived by autodiff for free). Exact f32 both ways. Falls back to
    the plain gather when the shapes don't line-align."""
    u, d = values_u.shape
    fp = _f_pad(d) if d <= 128 else 0
    rpl = 128 // fp if fp else 0
    if not fp or u % rpl:
        return values_u[gather_idx]
    padded = (values_u if fp == d else
              jnp.pad(values_u, ((0, 0), (0, fp - d))))
    packed = padded.reshape(u // rpl, 128)
    # clamp BEFORE the line split so out-of-range indices read row u-1,
    # exactly like the plain gather's clamp semantics (line-clamping
    # alone would read row u-rpl)
    gi = jnp.clip(gather_idx, 0, u - 1)
    lines = packed[gi // rpl]                          # [K, 128]
    grouped = lines.reshape(-1, rpl, fp)
    onehot = _lane_onehot(gi % rpl, rpl, lines.dtype)  # [K, rpl]
    # elementwise mask+reduce, NOT einsum: a dot_general would run at
    # default (bf16-pass) matmul precision on TPU and break the exact-
    # f32 contract of this op and its autodiff transpose; where-select,
    # NOT multiply, so a NaN unique row stays confined to its own keys
    # (_lane_select — the transpose derives the same select)
    vals = _lane_select(onehot, grouped).sum(axis=1)
    return vals[:, :d] if fp != d else vals


def merge_rows(values: jax.Array, idx: jax.Array,
               num_segments: int) -> jax.Array:
    """segment_sum of narrow rows in LANE-PACKED form: [M, D] values
    summed by ``idx`` into [num_segments, D]. A scatter-add into a
    [num, D<16] accumulator is random-access RMW on strided narrow rows
    (docs/DESIGN_NOTES.md §4a); this packs each
    contribution into its row's lane span of a 128-lane line delta and
    scatter-adds whole lines (disjoint-lane adds commute exactly, the
    apply_push trick). Exact f32; falls back to jax.ops.segment_sum when
    shapes don't line-align."""
    m, d = values.shape
    fp = _f_pad(d) if d <= 128 else 0
    rpl = 128 // fp if fp else 0
    # the line form wins in the RMW-bound regime (large accumulators);
    # into a small accumulator the plain scatter-add is already fast
    # and the [M, 128] delta materialization is pure overhead
    if not fp or num_segments % rpl or num_segments <= (1 << 17):
        return jax.ops.segment_sum(values, idx, num_segments=num_segments)
    v = (values if fp == d else
         jnp.pad(values, ((0, 0), (0, fp - d))))
    onehot = _lane_onehot(idx % rpl, rpl, v.dtype)      # [M, rpl]
    d_lines = _lane_select(onehot, v[:, None, :]).reshape(m, 128)
    out = jnp.zeros((num_segments // rpl, 128), v.dtype).at[
        idx // rpl].add(d_lines, mode="drop")
    out = out.reshape(num_segments, fp)
    return out[:, :d] if fp != d else out


def merge_push(key_grads: jax.Array, gather_idx: jax.Array,
               key_valid: jax.Array, slot_of_key: jax.Array,
               num_unique: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dedup-merge per-key-occurrence grads into per-unique-row grads —
    PushMergeCopy (box_wrapper.cu:417). Returns (unique_grads [U, D],
    touched [U] bool, slot_val [U]). NOTE: when grads come from autodiff
    through ``expand_pull`` they are ALREADY occurrence-merged; use
    ``push_stats`` for just touched/slot then."""
    g = jax.ops.segment_sum(key_grads * key_valid[:, None], gather_idx,
                            num_segments=num_unique)
    touched, slot_val = push_stats(gather_idx, key_valid, slot_of_key,
                                   num_unique)
    return g, touched, slot_val


def push_stats(gather_idx: jax.Array, key_valid: jax.Array,
               slot_of_key: jax.Array,
               num_unique: int) -> Tuple[jax.Array, jax.Array]:
    """Per-unique-row touched flag and mean slot id."""
    cnt = jax.ops.segment_sum(key_valid, gather_idx, num_segments=num_unique)
    slot_sum = jax.ops.segment_sum(slot_of_key * key_valid, gather_idx,
                                   num_segments=num_unique)
    touched = cnt > 0
    slot_val = jnp.where(touched, slot_sum / jnp.maximum(cnt, 1.0), 0.0)
    return touched, slot_val


def _push_wide_lines(packed: jax.Array, unique_rows: jax.Array,
                     delta: jax.Array, fp: int,
                     num_unique: Optional[jax.Array]) -> jax.Array:
    """``apply_push``'s scatter-add for WIDE rows (a row is ``fp // 128``
    whole lines, so no two slots share a line and there is no lane
    select): ``delta`` [U, fp] added at the rows' lines, the slots at or
    after ``num_unique`` left out by the same counted loop; then the
    sentinel row's lines are set to zero, as the narrow path does. Pad
    ids lie past the table and are dropped (clamped first, so that a far
    id times the lines a row cannot wrap)."""
    u = delta.shape[0]
    lpr = fp // 128
    n_rows = packed.shape[0] // lpr
    d_lines = delta.reshape(u * lpr, 128)
    rows = jnp.minimum(unique_rows, n_rows)
    cap = n_rows - 1

    def zero_sentinel(packed):
        return packed.at[cap * lpr:(cap + 1) * lpr].set(0.0)

    if num_unique is None:
        return zero_sentinel(
            packed.at[_row_lines(rows, fp)].add(d_lines, mode="drop"))
    c = push_chunk(u)

    def body(i, packed):
        at = _chunk_start(i, c, u)
        rows_c = jax.lax.dynamic_slice(rows, (at,), (c,))
        if u % c:
            # the overlapping last trip: drop what was added already
            rows_c = jnp.where(
                at + jnp.arange(c, dtype=jnp.int32) >= i * c, rows_c,
                n_rows)
        return packed.at[_row_lines(rows_c, fp)].add(
            jax.lax.dynamic_slice(d_lines, (at * lpr, 0), (c * lpr, 128)),
            mode="drop")

    return zero_sentinel(
        jax.lax.fori_loop(0, push_chunks(u, num_unique), body, packed))


def apply_push(
    state: TableState,
    unique_rows: jax.Array,   # int32 [U_pad]
    unique_grads: jax.Array,  # [U_pad, 3+mf_dim]: [g_show, g_clk, g_embed, g_embedx…]
    cfg: SparseSGDConfig,
    rng: jax.Array,
    rows_full: Optional[jax.Array] = None,  # [U_pad, F] from gather_full_rows
    touched: Optional[jax.Array] = None,    # bool [U_pad]; None → derived
    slot_val: Optional[jax.Array] = None,   # f32 [U_pad]; None → keep col
    num_unique: Optional[jax.Array] = None,  # int32 scalar: pads from here
) -> TableState:
    """In-table optimizer on merged grads — dy_mf_update_value
    (optimizer.cuh.h:80) + scatter write-back.

    The whole table write is ONE line-granular scatter-ADD of masked
    deltas (packed layout — see TableState): each updated row contributes
    ``new − old`` placed at its lane span inside a zero [U, 128] line
    delta. Line indices may REPEAT (several logical rows share a storage
    line) — their deltas occupy disjoint lanes, so the add commutes
    exactly; pad rows are masked to zero delta so in-bounds-aliasing pads
    write nothing. NOTE: ``old + (new − old)`` can differ from ``new`` by
    1 ulp — both train paths share this op, so path-parity is exact.

    ``unique_indices`` is NOT promised to the scatter and cannot be:
    the indices are LINES, and rows sharing a line repeat one.

    ``num_unique`` (int32 scalar on the device, or None) is
    ``gather_full_rows``'s promise: every slot at or after it is a pad.
    A TPU scatter costs per update, dropped or not, so with a count the
    scatter-add runs over ``[0, num_unique)`` only: ``push_chunks`` trips
    of ``push_chunk`` slots in a ``fori_loop`` that carries ``packed``
    (in place). The optimizer mathematics above it stays U-wide, so
    every row draws the random numbers its position drew before. The
    slots left out are pads: their delta is masked to zero (``touched``
    is false past the sentinel) and they are out of bounds or alias the
    table's last line, so leaving them out adds nothing that was added
    before but zeros: the table is bit-identical to the single
    scatter's (adds into a line's disjoint lanes commute exactly).
    Where ``push_chunk`` does not divide U the last trip overlaps the
    one before, and its slots already visited are sent out of bounds
    and dropped, so no delta is added twice.

    ``rows_full`` lets the caller reuse the rows gathered for the pull
    (gather_full_rows) instead of re-gathering here. ``touched`` defaults
    to the dup-free contract (every in-bounds row was hit); ``slot_val``
    None keeps the stored slot column — the single-process tables track
    slot as HOST metadata (EmbeddingTable.slot_host), so no device
    segment op is spent on it."""
    g = unique_grads
    if touched is None:
        # strictly < capacity: real rows are always below the sentinel.
        # The compact wire maps pad keys to row == capacity and dedup_rows
        # emits that as an in-bounds unique entry — the optimizer must
        # never run on it (lazy mf creation would seed it from RNG before
        # the trailing re-zero).
        touched = unique_rows < state.capacity
    if rows_full is None:
        rows_full = gather_full_rows(state, unique_rows, num_unique)
    mf_dim = state.mf_dim
    mf_end = NUM_FIXED + mf_dim
    rows = RowState(
        show=rows_full[:, 0], clk=rows_full[:, 1],
        delta_score=rows_full[:, 2],
        embed_w=rows_full[:, 4], embed_g2sum=rows_full[:, 5],
        embedx_w=rows_full[:, NUM_FIXED:mf_end],
        embedx_g2sum=rows_full[:, 6],
        mf_size=rows_full[:, 7],
        opt_ext=rows_full[:, mf_end:],
    )
    new = sparse_update(rows, g[:, 0], g[:, 1], g[:, 2], g[:, 3:3 + mf_dim],
                        touched, cfg, rng)
    if slot_val is None:
        slot_new = rows_full[:, 3]
    else:
        slot_new = jnp.where(touched, slot_val, rows_full[:, 3])
    new_mat = jnp.concatenate([
        new.show[:, None], new.clk[:, None], new.delta_score[:, None],
        slot_new[:, None], new.embed_w[:, None], new.embed_g2sum[:, None],
        new.embedx_g2sum[:, None], new.mf_size[:, None], new.embedx_w,
        new.opt_ext,
    ], axis=1)
    rpl, fp, _ = state.geometry
    u = new_mat.shape[0]
    # where, not multiply: an untouched row holding NaN would otherwise
    # turn its masked-out delta into NaN (0 * NaN) and poison the line
    delta = jnp.where(touched[:, None], new_mat - rows_full, 0)
    if fp != state._feat:
        delta = jnp.concatenate(
            [delta, jnp.zeros((u, fp - state._feat), delta.dtype)], axis=1)
    if fp > 128:
        return state.with_packed(_push_wide_lines(
            state.packed, unique_rows, delta, fp, num_unique))
    onehot = _lane_onehot(unique_rows % rpl, rpl, delta.dtype)
    d_lines = _lane_select(onehot, delta[:, None, :]).reshape(u, 128)
    if num_unique is None:
        packed = state.packed.at[unique_rows // rpl].add(d_lines,
                                                        mode="drop")
    else:
        c = push_chunk(u)
        n_lines = state.packed.shape[0]

        def body(i, packed):
            at = _chunk_start(i, c, u)
            line = jax.lax.dynamic_slice(unique_rows, (at,), (c,)) // rpl
            if u % c:
                # the overlapping last trip: drop what was added already
                line = jnp.where(
                    at + jnp.arange(c, dtype=jnp.int32) >= i * c,
                    line, n_lines)
            return packed.at[line].add(
                jax.lax.dynamic_slice(d_lines, (at, 0), (c, 128)),
                mode="drop")

        packed = jax.lax.fori_loop(0, push_chunks(u, num_unique), body,
                                   state.packed)
    # keep the sentinel row zero (defense in depth — pad deltas are
    # masked, but eval's miss collapse reads it)
    cap = state.capacity
    s0 = (cap % rpl) * fp
    packed = packed.at[cap // rpl, s0:s0 + fp].set(0.0)
    return state.with_packed(packed)


class EmbeddingTable:
    """Single-shard embedding PS facade (BoxWrapper role)."""

    def __init__(self, mf_dim: int = 8, capacity: Optional[int] = None,
                 cfg: Optional[SparseSGDConfig] = None, seed: int = 0,
                 unique_bucket_min: int = 1024,
                 arena_slots: Optional[int] = None,
                 arena_chunk_bits: int = 12) -> None:
        """``arena_slots``: enable the slot-arena row allocator (native
        kv_index Arena) for ``arena_slots`` feature slots — rows cluster
        into per-slot chunk extents so the resident-pass COMPACT wire can
        ship ~17-bit slot-local rows instead of dedup streams
        (train/device_pass.py). Purely an allocation policy: every other
        table path (save/load/shrink/streaming prepare) is unchanged and
        correct either way; keys that enter through slotless paths make
        the compact wire fall back to the dedup wire for passes touching
        them."""
        self.mf_dim = mf_dim
        self.capacity = capacity or FLAGS.table_capacity_per_shard
        self.cfg = cfg or SparseSGDConfig()
        self.opt_ext = opt_ext_width(self.cfg, mf_dim)
        self.index = HostKV(self.capacity)
        self.arena_slots = arena_slots
        self.arena_chunk_bits = arena_chunk_bits
        if arena_slots is not None:
            self.index.arena_enable(arena_chunk_bits, arena_slots)
        self.state = init_table_state(self.capacity, mf_dim,
                                      ext=self.opt_ext)
        self._rng = jax.random.PRNGKey(seed)
        self._push_count = 0
        self.unique_bucket_min = unique_bucket_min
        self._touched = np.zeros(self.capacity + 1, dtype=bool)
        # per-row slot id — HOST metadata (the FeatureValue slot field,
        # feature_value.h:570). Slot never changes for a key, and the host
        # sees every key at assign time, so no device work tracks it.
        self.slot_host = np.zeros(self.capacity + 1, dtype=np.int16)
        # serializes host-side index/touched mutation across threads
        # (prefetch prepare, ResidentPass.build preload, shrink/save/load)
        self.host_lock = threading.Lock()
        # device-resident key index (FLAGS.use_pallas_index seam):
        # created lazily on first flag-on bulk assign, dropped whenever
        # the host kv's allocation may stop being dense (load/merge/
        # shrink) — see _device_index
        self._dev_index = None
        # last bulk_assign_unique timing split, host-lock mirror work vs
        # device insert work — surfaced as the preloader's `index` build
        # stage (train/device_pass._dedup_phase)
        self.last_assign_seconds = {"index_host": 0.0, "index_device": 0.0}

    # ---- per-batch host prep (dedup + row assignment) ----
    def _build_index(self, batch: SlotBatch, rows: np.ndarray,
                     inv: np.ndarray) -> PullIndex:
        """Shared padding/bucketing tail of prepare/prepare_eval.

        Padding positions (u.., where padded KEYS also point) get the
        fill_oob_pads treatment, keeping unique_rows duplicate-free.
        (rows itself is dup-free: assign_unique returns distinct rows;
        lookup_unique collapses all misses into ONE sentinel entry.)"""
        u = len(rows)
        cap = next_bucket(self.unique_bucket_min, u + 1)
        unique_rows = np.empty(cap, dtype=np.int32)
        unique_rows[:u] = rows
        fill_oob_pads(unique_rows, u, self.capacity)
        k_pad = batch.keys.shape[0]
        gather_idx = np.full(k_pad, u, dtype=np.int32)  # pads → sentinel slot
        gather_idx[:batch.num_keys] = inv
        key_valid = np.zeros(k_pad, dtype=np.float32)
        key_valid[:batch.num_keys] = 1.0
        return PullIndex(unique_rows, gather_idx, key_valid, u)

    def host_pull(self, keys: np.ndarray,
                  data: Optional[np.ndarray] = None) -> np.ndarray:
        """[n] keys → [n, 3+mf] pull values on HOST (show, clk, embed_w,
        embedx…); unknown keys → zeros. Shared by the serving mirror and
        MultiMfEmbeddingTable.pull — THE host-side CopyForPull.
        ``data`` lets callers pass a cached logical mirror."""
        keys = np.ascontiguousarray(keys, np.uint64)
        rows, inv = self.index.lookup_unique(keys, self.capacity)
        if data is None:
            data = np.asarray(jax.device_get(self.state.data))
        vals = data[np.minimum(rows, self.capacity)]  # OOB pads clamp
        return host_pull_block(vals, self.mf_dim)[inv]

    def record_slots(self, rows: np.ndarray, inv: np.ndarray,
                     slot_of_key: np.ndarray) -> None:
        """Record each unique row's slot (first key occurrence wins via
        the reversed assignment). Caller holds host_lock."""
        self.slot_host[rows[inv[::-1]]] = slot_of_key[::-1]

    def bulk_assign_unique(self, keys: np.ndarray,
                           slot_of_key: np.ndarray
                           ) -> Tuple[np.ndarray, np.ndarray]:
        """Whole-PASS bulk row assignment (the resident-pass build's
        critical path): dedup the concatenated key stream outside
        ``host_lock`` (first-seen order — see dedup_first_seen), then
        ONE index round-trip under the lock instead of one per batch.
        Returns (rows of the first-seen uniques, inverse). Slot
        metadata records the key's PASS-level first-occurrence slot;
        the serial per-batch path nets out to the last batch's
        first occurrence instead — identical under the one-slot-per-key
        contract (CTR feasigns are slot-qualified,
        Dataset.pass_key_slots), which is the only input either path
        supports.

        Arena tables assign slotted so first-seen keys land in their
        slot's arena (same rationale as the per-batch dedup path:
        slotless assigns would poison the compact wire forever).

        ``FLAGS.use_pallas_index`` routes this through the device hash
        index (_bulk_assign_device): raw ids go to the chip, dedup and
        row assignment happen there, and the host kv is mirrored with
        ONLY the new keys — one O(new) append instead of the O(all)
        round trip. Any call the device route cannot serve exactly
        (probe/capacity overflow, kv divergence) falls back here,
        loudly, and books ``index.assign/host``."""
        keys = np.ascontiguousarray(keys, np.uint64)
        if FLAGS.use_pallas_index:
            dev = self._device_index()
            if not dev.degraded:
                out = self._bulk_assign_device(keys, slot_of_key, dev)
                if out is not None:
                    return out
            from paddlebox_tpu.ops.pallas_index import book_index_dispatch
            book_index_dispatch("assign", "host")
        uniq, first_idx, inv = dedup_first_seen(keys)
        slots_first = slot_of_key[first_idx]
        t1 = time.perf_counter()
        with self.host_lock:
            if getattr(self.index, "arena_enabled", False):
                rows, _ = self.index.assign_slotted(
                    uniq, slots_first.astype(np.uint16, copy=False))
            else:
                rows = self.index.assign(uniq)
            self.slot_host[rows] = slots_first.astype(np.int16,
                                                      copy=False)
        self.last_assign_seconds = {
            "index_host": time.perf_counter() - t1, "index_device": 0.0}
        return rows, inv

    # ---- device-resident key index (FLAGS.use_pallas_index) ----
    def _device_index(self):
        """Lazy DeviceKeyIndex for this table. On creation it seeds from
        the host kv (possible only while kv allocation is dense) and
        marks itself degraded — sticky, loud — when it can't mirror
        (arena-slotted allocation, free-list holes)."""
        dev = self._dev_index
        if dev is None:
            from paddlebox_tpu.ops.pallas_index import DeviceKeyIndex
            dev = DeviceKeyIndex(self.capacity)
            with self.host_lock:
                if getattr(self.index, "arena_enabled", False):
                    dev.degrade("arena-slotted row allocation has no "
                                "dense device mirror")
                elif not dev.seed_from_kv(self.index):
                    dev.degrade("host kv rows are not dense "
                                "(free-list holes) — cannot seed")
            self._dev_index = dev
        return dev

    def _reset_dev_index(self) -> None:
        """Drop the device index after a host-kv lifecycle mutation
        (load/merge/shrink/window eviction); the next flag-on bulk
        assign re-seeds from the kv, or degrades loudly if it can't."""
        self._dev_index = None

    def _bulk_assign_device(self, keys: np.ndarray,
                            slot_of_key: np.ndarray, dev
                            ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Device route of bulk_assign_unique: on-device first-seen
        dedup + hash insert, host kv mirrored with the NEW keys only.
        Returns None (after degrading ``dev``) whenever the result
        cannot be trusted bit-for-bit — the caller redoes the call on
        the host path, which is always authoritative."""
        from paddlebox_tpu.ops.pallas_index import (book_index_dispatch,
                                                    device_impl)
        t0 = time.perf_counter()
        pre_rows = dev.next_row
        out = dev.assign_raw(keys)
        t_dev = time.perf_counter() - t0
        if out is None:
            dev.degrade("probe/capacity overflow "
                        f"({len(keys)} keys at {pre_rows} rows, "
                        f"capacity {self.capacity})")
            return None
        uniq, first_idx, inv, rows_u, new_mask = out
        t1 = time.perf_counter()
        slots_first = slot_of_key[first_idx]
        with self.host_lock:
            if len(self.index) != pre_rows:
                dev.degrade(f"host kv diverged ({len(self.index)} keys "
                            f"vs {pre_rows} mirrored)")
                return None
            if new_mask.any():
                krows = self.index.assign(uniq[new_mask])
                if not np.array_equal(
                        krows, rows_u[new_mask].astype(np.int32)):
                    dev.degrade("host kv allocated different rows than "
                                "the device index (free-list holes)")
                    return None
            self.slot_host[rows_u] = slots_first.astype(np.int16,
                                                        copy=False)
        self.last_assign_seconds = {
            "index_host": time.perf_counter() - t1,
            "index_device": t_dev}
        book_index_dispatch("assign", device_impl())
        return (rows_u.astype(np.int32, copy=False),
                inv.astype(np.int64, copy=False))

    def prepare(self, batch: SlotBatch) -> PullIndex:
        valid = batch.keys[:batch.num_keys]
        with self.host_lock:
            rows, inv = self.index.assign_unique(valid)
            self._touched[rows] = True
            self.record_slots(
                rows, inv,
                (batch.segments[:batch.num_keys]
                 % batch.num_slots).astype(np.int16))
        return self._build_index(batch, rows, inv)

    def prepare_eval(self, batch: SlotBatch) -> PullIndex:
        """Read-only prepare: unknown keys map to the zero sentinel row
        instead of allocating (inference path — no index mutation)."""
        valid = batch.keys[:batch.num_keys]
        with self.host_lock:
            rows, inv = self.index.lookup_unique(valid, self.capacity)
        return self._build_index(batch, rows, inv)

    def next_rng(self) -> jax.Array:
        self._push_count += 1
        return jax.random.fold_in(self._rng, self._push_count)

    # ---- eager convenience (tests / small runs) ----
    def pull(self, idx: PullIndex) -> jax.Array:
        vals_u = pull_rows(self.state, jnp.asarray(idx.unique_rows))
        return expand_pull(vals_u, jnp.asarray(idx.gather_idx))

    def push(self, idx: PullIndex, key_grads: jax.Array,
             slot_of_key: Optional[jax.Array] = None) -> None:
        """Per-key-occurrence grads in → dedup-merge → optimizer apply.
        ``slot_of_key`` (per padded key) records the rows' slot ids into
        the host-side slot metadata (save files read slot from there)."""
        if slot_of_key is not None:
            sok = np.asarray(slot_of_key)
            kvm = np.asarray(idx.key_valid) > 0
            with self.host_lock:
                self.record_slots(idx.unique_rows, idx.gather_idx[kvm],
                                  sok[kvm].astype(np.int16))
        gi = jnp.asarray(idx.gather_idx)
        kv = jnp.asarray(idx.key_valid)
        # grad merge only (PushMergeCopy) — touched derives from the
        # dup-free _build_index contract inside apply_push, slot is host
        # metadata: no segment-stat scatters
        g = jax.ops.segment_sum(key_grads * kv[:, None], gi,
                                num_segments=idx.unique_rows.shape[0])
        self.state = apply_push(
            self.state, jnp.asarray(idx.unique_rows), g,
            self.cfg, self.next_rng())

    # ---- lifecycle: save / load / shrink (box_wrapper.cc:1383-1415) ----
    def _gather_host(self, rows: np.ndarray) -> Dict[str, np.ndarray]:
        """Per-field host dict (the save-file format stays field-named,
        independent of the device AoS layout). The slot field comes from
        host metadata — the device column is not maintained."""
        data = np.asarray(jax.device_get(self.state.data))
        sub = data[rows]
        mf_end = NUM_FIXED + self.mf_dim
        out = {f: (sub[:, NUM_FIXED:mf_end] if f == "embedx_w"
                   else field_slice(sub, f)) for f in FIELDS}
        out["slot"] = self.slot_host[rows].astype(np.float32)
        if self.opt_ext:
            out["opt_ext"] = sub[:, mf_end:]
        return out

    def save_base(self, path: str, clear_touched: bool = True) -> int:
        """Full model dump (day-level batch model). Returns rows saved.

        ``clear_touched=False`` = a MID-PASS snapshot (checkpoint resume
        cursor): the touched set is prepare-time bookkeeping, and with a
        prefetch pipeline running ahead a mid-pass clear would drop rows
        that are assigned but not yet pushed from every later delta.
        Only pass-boundary saves (pipeline drained) may clear."""
        with self.host_lock:
            keys, rows = self.index.items()
            if clear_touched:
                # clear only snapshotted rows under the lock (rows touched
                # by a concurrent preload keep their delta flag)
                self._touched[rows] = False
        data = self._gather_host(rows)
        np.savez_compressed(path, keys=keys, **data)
        return len(keys)

    def save_delta(self, path: str, clear_touched: bool = True) -> int:
        """Incremental dump of rows touched since last save ("xbox delta").

        With ``clear_touched=False`` (mid-pass cursor checkpoints) the
        flags survive, so successive in-pass deltas are CUMULATIVE over
        the pass — a superset each time, which keeps the chain correct
        while the prefetch pipeline's prepare-ahead makes any mid-pass
        flag clearing unsound (see save_base)."""
        with self.host_lock:
            keys, rows = self.index.items()
            mask = self._touched[rows]
            keys, rows = keys[mask], rows[mask]
            if clear_touched:
                self._touched[rows] = False
        data = self._gather_host(rows)
        np.savez_compressed(path, keys=keys, **data)
        return len(keys)

    def clear_touched_flags(self) -> None:
        """Post-commit half of a STAGED export (artifacts publish,
        BoxPSHelper.publish_*): a ``save_*(clear_touched=False)`` into
        the stage dir followed by this after the publish COMMITS is
        equivalent to the plain clearing save — but a publish failure
        in between loses no delta rows (the flags survive for the
        retry). Call only between passes."""
        with self.host_lock:
            self._touched[:] = False

    def rows_digest(self) -> str:
        """sha256 over the logical rows sorted by feasign — the
        read-only full-model fingerprint (row-assignment order cancels
        out; no touched flags change, so digesting is inert). The
        single-table sibling of ``HostStore.rows_digest`` /
        ``TieredShardedEmbeddingTable.rows_digest`` — serving gates
        compare served snapshots against it (scripts/serve_check.py)."""
        import hashlib
        with self.host_lock:
            keys, rows = self.index.items()
        order = np.argsort(keys)
        data = np.asarray(jax.device_get(self.state.data))
        h = hashlib.sha256()
        h.update(np.ascontiguousarray(keys[order]).tobytes())
        h.update(np.ascontiguousarray(data[rows[order]]).tobytes())
        return h.hexdigest()

    def _assign_file_rows(self, keys: np.ndarray,
                          slots_b: np.ndarray) -> np.ndarray:
        """Assign rows for a save-file's keys — slotted when the arena is
        on and the file's slots fit, so the compact wire stays available
        after a restore. Caller holds host_lock."""
        if (getattr(self.index, "arena_enabled", False)
                and (0 <= slots_b).all()
                and (slots_b < (self.arena_slots or 0)).all()):
            rows, _ = self.index.assign_slotted(
                keys, slots_b.astype(np.uint16))
        else:
            rows = self.index.assign(keys)
        self.slot_host[rows] = slots_b
        return rows

    def _insert_file_rows(self, data: np.ndarray, rows: np.ndarray,
                          blob, sel=slice(None)) -> None:
        """Write a save-file's field blocks (all but slot, which is host
        metadata) into the logical data matrix at ``rows``; ``sel``
        restricts to a subset of the file's rows (merge_model)."""
        mf_end = NUM_FIXED + self.mf_dim
        for f in FIELDS:
            if f == "slot":
                continue
            if f == "embedx_w":
                data[rows, NUM_FIXED:mf_end] = blob[f][sel]
            else:
                field_assign(data, rows, f, blob[f][sel])
        if self.opt_ext:
            if "opt_ext" in blob \
                    and blob["opt_ext"].shape[1] == self.opt_ext:
                data[rows, mf_end:mf_end + self.opt_ext] = \
                    blob["opt_ext"][sel]
            else:
                log.warning("load: file has no matching opt_ext block; "
                            "optimizer state starts fresh for loaded "
                            "rows")

    def load(self, path: str, merge: bool = False) -> int:
        """Load a save_base/save_delta file; merge=True keeps existing rows
        (delta apply), else resets the table first. Sharded-format saves
        (ShardedEmbeddingTable/tiered, any shard count) load too — their
        per-shard blocks concatenate into one table (the serving consumer
        of a pod-trained model)."""
        blob = _flatten_sharded_blob(np.load(path))
        keys = blob["keys"]
        with self.host_lock:
            if not merge:
                self.index = HostKV(self.capacity)
                if self.arena_slots is not None:
                    self.index.arena_enable(self.arena_chunk_bits,
                                            self.arena_slots)
                self.state = init_table_state(self.capacity, self.mf_dim,
                                              ext=self.opt_ext)
                self._touched[:] = False
                self.slot_host[:] = 0
            rows = self._assign_file_rows(keys,
                                          blob["slot"].astype(np.int16))
            self._reset_dev_index()
        data = np.asarray(jax.device_get(self.state.data)).copy()
        self._insert_file_rows(data, rows, blob)
        self.state = TableState.from_logical(data, self.capacity,
                                             ext=self.opt_ext)
        return len(keys)

    def merge_model(self, path: str) -> int:
        """MergeModel (box_wrapper.h:801-803, bound at box_helper_py.cc):
        fold another saved model's rows into the LIVE table — unlike
        ``load(merge=True)``, which OVERWRITES rows from a delta file,
        this MERGES statistics:

        - keys present in both: show/clk/delta_score ACCUMULATE (the
          other model's traffic counts add to ours); embedding weights
          and optimizer state keep the live values (the live model is
          the training continuation);
        - unseen keys: inserted wholesale (all fields from the file).

        Returns the number of rows merged."""
        blob = _flatten_sharded_blob(np.load(path))
        keys = blob["keys"]
        if len(keys) == 0:
            return 0
        slots_b = blob["slot"].astype(np.int16)
        with self.host_lock:
            existing = self.index.lookup(keys) >= 0
            rows_new = self._assign_file_rows(keys[~existing],
                                              slots_b[~existing])
            rows_all = self.index.lookup(keys)
            data = np.asarray(jax.device_get(self.state.data)).copy()
            # new rows: full insert (shared with load)
            self._insert_file_rows(data, rows_new, blob, sel=~existing)
            # existing rows: statistics accumulate
            rows_old = rows_all[existing]
            for f in ("show", "clk", "delta_score"):
                data[rows_old, FIELD_COL[f]] += blob[f][existing]
            self.state = TableState.from_logical(data, self.capacity,
                                                 ext=self.opt_ext)
            self._touched[rows_all] = True
            self._reset_dev_index()
        log.info("merge_model: %d rows (%d new, %d stat-merged) from %s",
                 len(keys), len(rows_new), int(existing.sum()), path)
        return len(keys)

    def merge_models(self, paths, update_type: str = "stats") -> int:
        """MergeMultiModels (box_wrapper.h:812-815): fold several saved
        models into the live table in order. ``update_type`` mirrors the
        closed-core knob's observable surface: "stats" accumulates
        show/clk/delta_score for shared keys and keeps live weights
        (merge_model semantics per file); "overwrite" applies each file
        as a delta (load(merge=True) — later files win). Returns total
        rows merged."""
        if update_type not in ("stats", "overwrite"):
            raise ValueError(f"unknown update_type {update_type!r}")
        total = 0
        for p in paths:
            total += (self.merge_model(p) if update_type == "stats"
                      else self.load(p, merge=True))
        return total

    def shrink(self, delete_threshold: Optional[float] = None,
               decay: Optional[float] = None) -> int:
        """Age features: decay show/clk/delta_score, then drop rows whose
        decayed score falls below threshold (ShrinkTable semantics:
        box_wrapper.h:638, ctr_accessor shrink rules). Returns rows freed."""
        thr = (FLAGS.shrink_delete_threshold
               if delete_threshold is None else delete_threshold)
        dk = FLAGS.show_click_decay_rate if decay is None else decay
        fence = getattr(self, "fence", None)
        if callable(fence):
            # tables with an async end_pass epilogue (pass_table,
            # tiered) must drain in-flight write-backs first: aging on
            # pre-write-back counters would drop rows the draining job
            # is about to refresh (HostStore.shrink has the same
            # audit via _barrier)
            fence()
        with self.host_lock:
            keys, rows = self.index.items()
            if len(keys) == 0:
                return 0
            data = np.asarray(jax.device_get(self.state.data)).copy()
            data[:, 0:3] *= dk  # decay show/clk/delta_score
            show, clk = data[rows, 0], data[rows, 1]
            score = (self.cfg.nonclk_coeff * (show - clk)
                     + self.cfg.clk_coeff * clk)
            drop = score < thr
            drop_keys = keys[drop]
            freed_rows = self.index.release(drop_keys)
            data[freed_rows] = 0.0
            self.state = TableState.from_logical(data, self.capacity,
                                                 ext=self.opt_ext)
            self._touched[freed_rows] = False
            self.slot_host[freed_rows] = 0
            self._reset_dev_index()
        log.info("shrink: freed %d/%d rows", len(freed_rows), len(keys))
        return int(len(freed_rows))

    @property
    def feature_count(self) -> int:
        return len(self.index)

    def obs_stats(self) -> Dict[str, float]:
        """Occupancy gauges for pass events (obs/hub.emit_pass_event)."""
        used = len(self.index)
        return {"capacity": self.capacity, "used": used,
                "fill_frac": round(used / max(self.capacity, 1), 6)}
