"""Host key→row index: native (C++) fast path with a python-dict fallback.

See paddlebox_tpu/native/kv_index.cpp for the role citation. Both
implementations share the contract used by the tables: assign / lookup /
release / items / len, uint64 keys → int32 rows with free-list reuse and a
hard row capacity (raises when full — Phase-5 eviction is the relief valve).
"""

from __future__ import annotations

import ctypes
from typing import Dict, Tuple

import numpy as np


class TableFullError(RuntimeError):
    pass


def _full_error(capacity: int) -> TableFullError:
    return TableFullError(
        f"embedding table full ({capacity} rows); raise "
        "FLAGS.table_capacity_per_shard or enable shrink")


class _PyArena:
    """Slot-arena allocator state (mirror of the native Arena struct):
    rows are carved from chunk-aligned extents owned by one slot each, so
    (slot, local) addresses any row compactly — the compact resident-pass
    wire's foundation (train/device_pass.py)."""

    def __init__(self, chunk_bits: int, n_slots: int, max_rows: int):
        self.chunk_bits = chunk_bits
        self.n_slots = n_slots  # default (slotless) arena = id n_slots
        self.max_chunks = (max_rows + (1 << chunk_bits) - 1) >> chunk_bits
        self.chunk_slot = np.full(self.max_chunks, -1, np.int32)
        self.chunk_rank = np.full(self.max_chunks, -1, np.int32)
        self.next_chunk = 0
        self.slot_nchunks = [0] * (n_slots + 1)
        self.slot_tail = [-1] * (n_slots + 1)
        self.slot_fill = [0] * (n_slots + 1)
        self.slot_free: list[list[int]] = [[] for _ in range(n_slots + 1)]

    def alloc(self, s: int, max_rows: int) -> int:
        if self.slot_free[s]:
            return self.slot_free[s].pop()
        cs = 1 << self.chunk_bits
        if self.slot_tail[s] < 0 or self.slot_fill[s] == cs:
            if self.next_chunk >= self.max_chunks:
                return -2
            c = self.next_chunk
            self.next_chunk += 1
            self.chunk_slot[c] = s
            self.chunk_rank[c] = self.slot_nchunks[s]
            self.slot_nchunks[s] += 1
            self.slot_tail[s] = c
            self.slot_fill[s] = 0
        row = (self.slot_tail[s] << self.chunk_bits) + self.slot_fill[s]
        self.slot_fill[s] += 1
        return row if row < max_rows else -2

    def local_of(self, row: int, s: int) -> int:
        if not 0 <= s < self.n_slots:  # incl. the default arena id
            return -1
        c = row >> self.chunk_bits
        if self.chunk_slot[c] != s:
            return -1
        return ((int(self.chunk_rank[c]) << self.chunk_bits)
                | (row & ((1 << self.chunk_bits) - 1)))


class PyKV:
    """Pure-python fallback (the original HostKV)."""

    def __init__(self, capacity: int) -> None:
        self.capacity = capacity
        self._map: Dict[int, int] = {}
        self._free: list[int] = []
        self._next = 0
        self._arena: _PyArena | None = None

    def __len__(self) -> int:
        return len(self._map)

    def arena_enable(self, chunk_bits: int, n_slots: int) -> None:
        if self._map or self._next:
            raise RuntimeError("arena_enable after rows were assigned")
        self._arena = _PyArena(chunk_bits, n_slots, self.capacity)

    @property
    def arena_enabled(self) -> bool:
        return self._arena is not None

    def _alloc(self, slot: int = -1) -> int:
        if self._arena is not None:
            # out-of-range slots clamp to the default (slotless) arena —
            # mirrors the native clamp_slot; the compact wire then sees
            # local = -1 and falls back instead of corrupting state
            s = (slot if 0 <= slot < self._arena.n_slots
                 else self._arena.n_slots)
            r = self._arena.alloc(s, self.capacity)
            if r == -2:
                raise _full_error(self.capacity)
            return r
        if self._free:
            return self._free.pop()
        if self._next < self.capacity:
            r = self._next
            self._next += 1
            return r
        raise _full_error(self.capacity)

    def assign(self, keys: np.ndarray) -> np.ndarray:
        rows = np.empty(len(keys), dtype=np.int32)
        m = self._map
        for i, k in enumerate(keys.tolist()):
            r = m.get(k)
            if r is None:
                r = self._alloc()
                m[k] = r
            rows[i] = r
        return rows

    def assign_slotted(self, keys: np.ndarray, slots: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """(global rows, slot-local rows); local = -1 where the key's row
        lives in another slot's arena (caller falls back to dedup wire)."""
        assert self._arena is not None
        rows = np.empty(len(keys), dtype=np.int32)
        locs = np.empty(len(keys), dtype=np.int32)
        m = self._map
        for i, (k, s) in enumerate(zip(keys.tolist(), slots.tolist())):
            r = m.get(k)
            if r is None:
                r = self._alloc(s)
                m[k] = r
            rows[i] = r
            locs[i] = self._arena.local_of(r, s)
        return rows, locs

    def arena_export(self) -> Tuple[np.ndarray, np.ndarray]:
        a = self._arena
        assert a is not None
        n = a.next_chunk
        return a.chunk_slot[:n].copy(), a.chunk_rank[:n].copy()

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        m = self._map
        return np.array([m.get(k, -1) for k in keys.tolist()], dtype=np.int32)

    def release(self, keys: np.ndarray) -> np.ndarray:
        rows = np.empty(len(keys), dtype=np.int32)
        a = self._arena
        for i, k in enumerate(keys.tolist()):
            r = self._map.pop(k, -1)
            if r >= 0:
                if a is not None:  # back to the OWNING arena
                    a.slot_free[a.chunk_slot[r >> a.chunk_bits]].append(r)
                else:
                    self._free.append(r)
            rows[i] = r
        return rows[rows >= 0]

    def items(self) -> Tuple[np.ndarray, np.ndarray]:
        if not self._map:
            return (np.empty(0, np.uint64), np.empty(0, np.int32))
        ks = np.fromiter(self._map.keys(), dtype=np.uint64,
                         count=len(self._map))
        rs = np.fromiter(self._map.values(), dtype=np.int32,
                         count=len(self._map))
        return ks, rs

    def assign_unique(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(unique rows, inverse): dedup keys and assign rows to the uniques."""
        uniq, inv = np.unique(keys, return_inverse=True)
        return self.assign(uniq), inv.astype(np.int32, copy=False)

    def lookup_unique(self, keys: np.ndarray,
                      sentinel: int) -> Tuple[np.ndarray, np.ndarray]:
        """Read-only dedup: ALL unknown keys collapse into ONE unique
        entry holding the sentinel row (same contract as the native
        kv_lookup_unique — keeps unique_rows duplicate-free)."""
        uniq, inv = np.unique(keys, return_inverse=True)
        rows = self.lookup(uniq)
        miss = rows < 0
        if not miss.any():
            return rows.astype(np.int32, copy=False), \
                inv.astype(np.int32, copy=False)
        # renumber: known uniques keep relative order, misses share one slot
        remap = np.empty(len(uniq), np.int32)
        known_idx = np.nonzero(~miss)[0]
        remap[known_idx] = np.arange(len(known_idx), dtype=np.int32)
        remap[np.nonzero(miss)[0]] = len(known_idx)
        out_rows = np.empty(len(known_idx) + 1, np.int32)
        out_rows[:len(known_idx)] = rows[known_idx]
        out_rows[len(known_idx)] = sentinel
        return out_rows, remap[inv].astype(np.int32, copy=False)


class NativeKV:
    """ctypes wrapper over native/kv_index.cpp."""

    def __init__(self, capacity: int, lib) -> None:
        self.capacity = capacity
        self._lib = lib
        self._h = lib.kv_create(min(capacity, 1 << 22), capacity)
        self.arena_enabled = False

    def arena_enable(self, chunk_bits: int, n_slots: int) -> None:
        if self._lib.kv_arena_enable(self._h, chunk_bits, n_slots) != 0:
            raise RuntimeError("arena_enable after rows were assigned")
        self.arena_enabled = True

    def assign_slotted(self, keys: np.ndarray, slots: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """(global rows, slot-local rows); local = -1 where the key's row
        lives in another slot's arena (caller falls back to dedup wire)."""
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        slots = np.ascontiguousarray(slots, dtype=np.uint16)
        n = len(keys)
        rows = np.empty(n, dtype=np.int32)
        locs = np.empty(n, dtype=np.int32)
        done = self._lib.kv_assign_slotted(
            self._h, self._buf(keys), self._buf(slots), n,
            self._buf(rows), self._buf(locs))
        if done != n:
            raise _full_error(self.capacity)
        return rows, locs

    def arena_export(self) -> Tuple[np.ndarray, np.ndarray]:
        n = int(self._lib.kv_arena_chunk_count(self._h))
        cs = np.empty(max(n, 1), dtype=np.int32)
        cr = np.empty(max(n, 1), dtype=np.int32)
        if n:
            self._lib.kv_arena_export(self._h, self._buf(cs), self._buf(cr))
        return cs[:n], cr[:n]

    def __del__(self) -> None:
        h = getattr(self, "_h", None)
        if h:
            self._lib.kv_destroy(h)
            self._h = None

    def __len__(self) -> int:
        return int(self._lib.kv_size(self._h))

    @staticmethod
    def _buf(a: np.ndarray):
        return a.ctypes.data_as(ctypes.c_void_p)

    def assign(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        rows = np.empty(len(keys), dtype=np.int32)
        done = self._lib.kv_assign(self._h, self._buf(keys), len(keys),
                                   self._buf(rows))
        if done != len(keys):
            raise _full_error(self.capacity)
        return rows

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        rows = np.empty(len(keys), dtype=np.int32)
        self._lib.kv_lookup(self._h, self._buf(keys), len(keys),
                            self._buf(rows))
        return rows

    def release(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        rows = np.empty(len(keys), dtype=np.int32)
        self._lib.kv_release(self._h, self._buf(keys), len(keys),
                             self._buf(rows))
        return rows[rows >= 0]

    def items(self) -> Tuple[np.ndarray, np.ndarray]:
        n = len(self)
        ks = np.empty(n, dtype=np.uint64)
        rs = np.empty(n, dtype=np.int32)
        if n:
            self._lib.kv_items(self._h, self._buf(ks), self._buf(rs))
        return ks, rs

    def assign_unique(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """One-pass hash dedup + row assign (O(n), no sort); uniques come in
        first-occurrence order. Contract matches PyKV.assign_unique."""
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        n = len(keys)
        uniq_rows = np.empty(n, dtype=np.int32)
        inv = np.empty(n, dtype=np.int32)
        u = self._lib.kv_assign_unique(self._h, self._buf(keys), n,
                                       self._buf(uniq_rows), self._buf(inv))
        if u < 0:
            raise _full_error(self.capacity)
        return uniq_rows[:u].copy(), inv

    def lookup_unique(self, keys: np.ndarray,
                      sentinel: int) -> Tuple[np.ndarray, np.ndarray]:
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        n = len(keys)
        uniq_rows = np.empty(max(n, 1), dtype=np.int32)
        inv = np.empty(n, dtype=np.int32)
        u = self._lib.kv_lookup_unique(self._h, self._buf(keys), n,
                                       sentinel, self._buf(uniq_rows),
                                       self._buf(inv))
        return uniq_rows[:u].copy(), inv


def make_kv(capacity: int):
    """Native index when buildable, python fallback otherwise."""
    from paddlebox_tpu.native import load_native
    lib = load_native()
    if lib is not None:
        return NativeKV(capacity, lib)
    return PyKV(capacity)


def dedup_first_seen_native(keys: np.ndarray):
    """Native one-pass first-seen dedup (kv_dedup_first_seen) — the fast
    route of ps/table.dedup_first_seen. Returns (uniq, first_idx, inv)
    with the oracle's exact dtypes, or None when the native library is
    unavailable (callers keep the python path unchanged)."""
    from paddlebox_tpu.native import load_native
    lib = load_native()
    if lib is None or not hasattr(lib, "kv_dedup_first_seen"):
        return None
    keys = np.ascontiguousarray(keys, np.uint64)
    n = len(keys)
    uniq = np.empty(max(n, 1), np.uint64)
    first = np.empty(max(n, 1), np.int64)
    inv = np.empty(max(n, 1), np.int32)
    u = lib.kv_dedup_first_seen(
        keys.ctypes.data_as(ctypes.c_void_p), n,
        uniq.ctypes.data_as(ctypes.c_void_p),
        first.ctypes.data_as(ctypes.c_void_p),
        inv.ctypes.data_as(ctypes.c_void_p))
    return uniq[:u].copy(), first[:u].copy(), inv[:n].astype(np.int64)


def dedup_slotted_first_seen_native(keys: np.ndarray, slots: np.ndarray):
    """Native first-seen dedup of a (key, slot) stream
    (kv_dedup_slotted_first_seen) — the fast route of
    ps/table.dedup_slotted_first_seen. Returns (uniq keys, their slots,
    inv int32), or None when the native library is unavailable."""
    from paddlebox_tpu.native import load_native
    lib = load_native()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, np.uint64)
    slots = np.ascontiguousarray(slots, np.uint16)
    n = len(keys)
    if keys.shape != (n,) or slots.shape != (n,):
        raise ValueError(f"one slot a key, both flat: keys {keys.shape}, "
                         f"slots {slots.shape}")
    uniq = np.empty(n, np.uint64)
    uslot = np.empty(n, np.uint16)
    inv = np.empty(n, np.int32)
    u = lib.kv_dedup_slotted_first_seen(
        keys.ctypes.data_as(ctypes.c_void_p),
        slots.ctypes.data_as(ctypes.c_void_p), n,
        uniq.ctypes.data_as(ctypes.c_void_p),
        uslot.ctypes.data_as(ctypes.c_void_p),
        inv.ctypes.data_as(ctypes.c_void_p))
    return uniq[:u], uslot[:u], inv
