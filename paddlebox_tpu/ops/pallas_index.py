"""Device-resident key→row assignment: an open-addressing hash index.

Reference: the HeterPS ``HashTable`` (SURVEY §2.2; heter_ps/hashtable.h
``get``/``insert`` over a GPU bucket array) — the structure that lets
PaddleBox pull/push take RAW feature ids with dedup and row assignment
happening on the accelerator instead of host threads. Here the analogue
is a linear-probe table over three int32 HBM arrays (key-hi, key-lo,
row; 64-bit ids ride as two 32-bit halves so the whole pipeline stays
x64-free):

- ``insert``: probe each key's bucket chain; an EMPTY bucket is claimed
  and the key allocated the next first-seen row. Two formulations with
  IDENTICAL row/new-mask output (gated in tests/test_pallas_index.py):
  * ``_insert_xla`` — vectorized parallel claim rounds in a
    ``while_loop``: every prober scatter-mins its stream index into a
    claim array (the compare-and-swap analogue: claim, then VERIFY the
    readback picked you), losers re-probe; rows come from a first-seen
    prefix-sum over the new-key mask after the loop.
  * ``_insert_pallas`` — a Pallas kernel gridded over key blocks. The
    TPU grid is SEQUENTIAL, so a row cursor in SMEM scratch carried
    across grid steps allocates first-seen rows with NO atomics (the
    per-block cursor of ISSUE 19), and the claim needs no CAS at all —
    the read-check-write on the aliased ANY-space bucket refs is
    race-free by construction.
- ``lookup``: the same probe, read-only; miss → row -1. Termination at
  ``_MAX_PROBE`` is safe because ``insert`` never PLACES a key more
  than ``_MAX_PROBE`` buckets from home (it overflows instead).
- ``scatter_add_update``: unique-row scatter-add of update deltas into
  the value table (aliased in-place Pallas kernel / ``.at[].add`` XLA
  twin) — the push-side op of the megakernel path.

Probe-chain validity note: the parallel-claim and sequential
formulations may place a key in DIFFERENT buckets (a lost claim skips a
bucket the sequential order would have taken), but every placement
leaves the key's whole probe prefix occupied and nothing is ever
deleted, so both layouts are valid linear-probe tables for the SAME key
set and either ``lookup`` finds every key in either layout. Rows depend
only on first-seen allocation order, which both share — parity gates
target rows/new-mask, never bucket bytes.

Mosaic status: random-access single-element HBM loads are not a
Mosaic primitive (same constraint that demoted the per-row DMA
gather — see ops/pallas_kernels.py status), so on a REAL TPU backend
``insert``/``lookup`` route to the XLA formulation, which is still
fully device-resident (one fused while_loop program, no host round
trip) and is booked as what it is: ``impl="xla"`` (``device_impl``).
The Pallas kernels run under interpret mode everywhere tier-1 runs.

Overflow contract: a key that probes ``_MAX_PROBE`` buckets without
placing, or a batch whose new keys exceed remaining row capacity, makes
the WHOLE call return overflow — the functional bucket updates are
simply not committed, and the caller (``DeviceKeyIndex`` → the
``use_pallas_index`` seam in ps/table.py / ps/sharded.py) degrades
LOUDLY to the host index with every decision booked in
``pbox_kernel_dispatch_total{kernel="index.*",impl}`` under the
formulation that actually ran (pallas | xla | host).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.ops.device_unique import dedup_keys_first_seen
from paddlebox_tpu.ops.pallas_kernels import (_book_dispatch, _interpret,
                                              _round_up)
from paddlebox_tpu.utils.logging import get_logger

log = get_logger(__name__)

_EMPTY = -1        # row sentinel marking an unclaimed bucket
_MAX_PROBE = 64    # probe-chain bound; longer chains overflow to host
_BK = 256          # keys per Pallas grid block


def book_index_dispatch(op: str, impl: str) -> None:
    """Book one index-seam dispatch decision (op ∈ {assign, lookup},
    impl ∈ {pallas, xla, host}) — the loud half of the fallback
    contract."""
    _book_dispatch(f"index.{op}", impl)


# ---------------------------------------------------------------------------
# Key split / hash
# ---------------------------------------------------------------------------

def split_keys(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """uint64 [N] → (hi, lo) int32 [N] halves (little-endian word order)."""
    w = np.ascontiguousarray(keys, np.uint64).view(np.uint32)
    lo = np.ascontiguousarray(w[0::2]).view(np.int32)
    hi = np.ascontiguousarray(w[1::2]).view(np.int32)
    return hi, lo


def join_keys(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """(hi, lo) int32 halves → uint64 keys."""
    return ((hi.astype(np.int64).astype(np.uint64) << np.uint64(32))
            | lo.view(np.uint32).astype(np.uint64))


def _hash32(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """uint32 bucket hash MIXING BOTH HALVES (ids that collide mod 2^32
    must not collide here) — two odd-constant folds + an xorshift
    finalizer, murmur3/splitmix style."""
    h = (lo.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + hi.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


# ---------------------------------------------------------------------------
# insert — XLA parallel-claim formulation
# ---------------------------------------------------------------------------

def _insert_xla(bh, bl, br, kh, kl, num_valid, next_row):
    """Parallel claim rounds: all unplaced keys probe at once; an empty
    bucket goes to the LOWEST stream index probing it this round (the
    first-seen winner), verified by reading the claim back. Returns
    (bh, bl, br, rows, new, overflow) — rows/new padded like kh."""
    k = kh.shape[0]
    nb = br.shape[0]
    mask = jnp.uint32(nb - 1)
    pos = jnp.arange(k, dtype=jnp.int32)
    h = _hash32(kh, kl)

    def cond(st):
        return jnp.any(~st[7]) & (st[8] < _MAX_PROBE)

    def step(st):
        bh, bl, br, off, row, new, newb, done, rounds = st
        b = ((h + off.astype(jnp.uint32)) & mask).astype(jnp.int32)
        r = br[b]
        active = ~done
        is_match = active & (r != _EMPTY) & (bh[b] == kh) & (bl[b] == kl)
        is_empty = active & (r == _EMPTY)
        # claim: scatter-min the stream index, verify the readback —
        # the functional compare-and-swap
        want = jnp.where(is_empty, b, nb)
        claim = jnp.full(nb, k, jnp.int32).at[want].min(pos, mode="drop")
        win = is_empty & (claim[jnp.minimum(want, nb - 1)] == pos)
        wb = jnp.where(win, b, nb)
        bh = bh.at[wb].set(kh, mode="drop")
        bl = bl.at[wb].set(kl, mode="drop")
        # placeholder row: must only read as non-EMPTY; real rows land
        # after the first-seen prefix-sum (no other live key equals a
        # just-claimed key — the stream is deduped)
        br = br.at[wb].set(0, mode="drop")
        row = jnp.where(is_match, r, row)
        new = new | win
        newb = jnp.where(win, b, newb)
        done = done | is_match | win
        off = off + (active & ~is_match & ~win).astype(jnp.int32)
        return bh, bl, br, off, row, new, newb, done, rounds + 1

    valid = pos < num_valid
    st = (bh, bl, br, jnp.zeros(k, jnp.int32), jnp.full(k, -1, jnp.int32),
          jnp.zeros(k, bool), jnp.full(k, nb, jnp.int32), ~valid,
          jnp.int32(0))
    bh, bl, br, _, row, new, newb, done, _ = jax.lax.while_loop(
        cond, step, st)
    overflow = jnp.any(~done)
    rank = jnp.cumsum(new.astype(jnp.int32)) - 1   # first-seen prefix-sum
    nrow = next_row + rank
    row = jnp.where(new, nrow, row)
    br = br.at[jnp.where(new, newb, nb)].set(nrow, mode="drop")
    return bh, bl, br, row, new.astype(jnp.int32), overflow


# ---------------------------------------------------------------------------
# insert — Pallas blocked-grid formulation
# ---------------------------------------------------------------------------

def _insert_kernel(meta_ref, kh_ref, kl_ref, bh_in, bl_in, br_in,
                   bh_ref, bl_ref, br_ref, rows_ref, new_ref, cur_ref):
    del bh_in, bl_in, br_in  # aliased — all access via the out refs
    blk = pl.program_id(0)
    nv = meta_ref[0]
    nb = br_ref.shape[0]
    mask = jnp.uint32(nb - 1)

    @pl.when(blk == 0)
    def _():
        cur_ref[0] = meta_ref[1]   # row cursor starts at next_row

    def body(j, carry):
        del carry
        g = blk * _BK + j
        kh = kh_ref[0, j]
        kl = kl_ref[0, j]
        h = _hash32(kh, kl)

        def cond(st):
            return ~st[3] & (st[0] < _MAX_PROBE)

        def step(st):
            off, row, new, done = st
            b = ((h + off.astype(jnp.uint32)) & mask).astype(jnp.int32)
            r = br_ref[b]
            is_empty = r == _EMPTY
            is_match = ~is_empty & (bh_ref[b] == kh) \
                & (bl_ref[b] == kl)
            cur = cur_ref[0]

            @pl.when(is_empty)
            def _():
                # sequential grid ⇒ read-check-write is race-free: the
                # atomic-free claim + per-block cursor of ISSUE 19
                bh_ref[b] = kh
                bl_ref[b] = kl
                br_ref[b] = cur
                cur_ref[0] = cur + 1

            row = jnp.where(is_empty, cur, jnp.where(is_match, r, row))
            return (off + (~is_empty & ~is_match).astype(jnp.int32), row,
                    new | is_empty, done | is_empty | is_match)

        st = (jnp.int32(0), jnp.int32(-1), False, g >= nv)
        _, row, new, _ = jax.lax.while_loop(cond, step, st)
        rows_ref[0, j] = row
        new_ref[0, j] = new.astype(jnp.int32)
        return 0

    jax.lax.fori_loop(0, _BK, body, 0)


def _insert_pallas(bh, bl, br, kh, kl, num_valid, next_row):
    k = kh.shape[0]
    nblk = k // _BK
    meta = jnp.stack([num_valid.astype(jnp.int32),
                      next_row.astype(jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((1, _BK), lambda i, m: (i, 0)),
            pl.BlockSpec((1, _BK), lambda i, m: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, _BK), lambda i, m: (i, 0)),
            pl.BlockSpec((1, _BK), lambda i, m: (i, 0)),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
    )
    nb = br.shape[0]
    bh, bl, br, rows2, new2 = pl.pallas_call(
        _insert_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nb,), jnp.int32),
            jax.ShapeDtypeStruct((nb,), jnp.int32),
            jax.ShapeDtypeStruct((nb,), jnp.int32),
            jax.ShapeDtypeStruct((nblk, _BK), jnp.int32),
            jax.ShapeDtypeStruct((nblk, _BK), jnp.int32),
        ],
        input_output_aliases={3: 0, 4: 1, 5: 2},
        interpret=_interpret(),
    )(meta, kh.reshape(nblk, _BK), kl.reshape(nblk, _BK), bh, bl, br)
    rows = rows2.reshape(k)
    new = new2.reshape(k)
    pos = jnp.arange(k, dtype=jnp.int32)
    overflow = jnp.any((pos < num_valid) & (rows < 0))
    return bh, bl, br, rows, new, overflow


# ---------------------------------------------------------------------------
# lookup — both formulations
# ---------------------------------------------------------------------------

def _lookup_xla(bh, bl, br, kh, kl, num_valid):
    k = kh.shape[0]
    mask = jnp.uint32(br.shape[0] - 1)
    pos = jnp.arange(k, dtype=jnp.int32)
    h = _hash32(kh, kl)

    def cond(st):
        return jnp.any(~st[2]) & (st[3] < _MAX_PROBE)

    def step(st):
        off, row, done, rounds = st
        b = ((h + off.astype(jnp.uint32)) & mask).astype(jnp.int32)
        r = br[b]
        active = ~done
        is_match = active & (r != _EMPTY) & (bh[b] == kh) & (bl[b] == kl)
        is_empty = active & (r == _EMPTY)   # chain ends → miss
        row = jnp.where(is_match, r, row)
        done = done | is_match | is_empty
        return (off + (active & ~is_match & ~is_empty).astype(jnp.int32),
                row, done, rounds + 1)

    valid = pos < num_valid
    st = (jnp.zeros(k, jnp.int32), jnp.full(k, -1, jnp.int32), ~valid,
          jnp.int32(0))
    _, row, _, _ = jax.lax.while_loop(cond, step, st)
    return row


def _lookup_kernel(meta_ref, kh_ref, kl_ref, bh_ref, bl_ref, br_ref,
                   rows_ref):
    blk = pl.program_id(0)
    nv = meta_ref[0]
    mask = jnp.uint32(br_ref.shape[0] - 1)

    def body(j, carry):
        del carry
        g = blk * _BK + j
        kh = kh_ref[0, j]
        kl = kl_ref[0, j]
        h = _hash32(kh, kl)

        def cond(st):
            return ~st[2] & (st[0] < _MAX_PROBE)

        def step(st):
            off, row, done = st
            b = ((h + off.astype(jnp.uint32)) & mask).astype(jnp.int32)
            r = br_ref[b]
            is_empty = r == _EMPTY
            is_match = ~is_empty & (bh_ref[b] == kh) \
                & (bl_ref[b] == kl)
            return (off + 1, jnp.where(is_match, r, row),
                    done | is_empty | is_match)

        st = (jnp.int32(0), jnp.int32(-1), g >= nv)
        _, row, _ = jax.lax.while_loop(cond, step, st)
        rows_ref[0, j] = row
        return 0

    jax.lax.fori_loop(0, _BK, body, 0)


def _lookup_pallas(bh, bl, br, kh, kl, num_valid):
    k = kh.shape[0]
    nblk = k // _BK
    meta = jnp.stack([num_valid.astype(jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((1, _BK), lambda i, m: (i, 0)),
            pl.BlockSpec((1, _BK), lambda i, m: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, _BK), lambda i, m: (i, 0)),
    )
    rows2 = pl.pallas_call(
        _lookup_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nblk, _BK), jnp.int32),
        interpret=_interpret(),
    )(meta, kh.reshape(nblk, _BK), kl.reshape(nblk, _BK), bh, bl, br)
    return rows2.reshape(k)


# ---------------------------------------------------------------------------
# jitted entry points
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("use_pallas",))
def insert(bh, bl, br, kh, kl, num_valid, next_row, *, use_pallas=True):
    """Insert the (deduped, first-seen-ordered) key stream. Returns
    (bh, bl, br, rows, new, overflow); on overflow the caller must
    DISCARD the returned bucket arrays (functional rollback)."""
    if use_pallas:
        return _insert_pallas(bh, bl, br, kh, kl, num_valid, next_row)
    return _insert_xla(bh, bl, br, kh, kl, num_valid, next_row)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def lookup(bh, bl, br, kh, kl, num_valid, *, use_pallas=True):
    """Probe rows for keys; miss (or pad position) → -1."""
    if use_pallas:
        return _lookup_pallas(bh, bl, br, kh, kl, num_valid)
    return _lookup_xla(bh, bl, br, kh, kl, num_valid)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def dedup_insert(bh, bl, br, kh, kl, num_valid, next_row, *,
                 use_pallas=True):
    """Raw-id front: device first-seen dedup + insert in ONE program —
    the pull-side shape of the megakernel path. Returns
    (bh, bl, br, uniq_hi, uniq_lo, first_pos, inv, num_unique,
    rows_u, new_u, overflow)."""
    uh, ul, first_pos, inv, nu = dedup_keys_first_seen(kh, kl, num_valid)
    if use_pallas:
        bh, bl, br, rows, new, ovf = _insert_pallas(
            bh, bl, br, uh, ul, nu, next_row)
    else:
        bh, bl, br, rows, new, ovf = _insert_xla(
            bh, bl, br, uh, ul, nu, next_row)
    return bh, bl, br, uh, ul, first_pos, inv, nu, rows, new, ovf


# ---------------------------------------------------------------------------
# scatter_add_update — push-side unique-row delta apply
# ---------------------------------------------------------------------------

def scatter_add_update(values: jax.Array, rows: jax.Array,
                       deltas: jax.Array,
                       use_pallas: Optional[bool] = None) -> jax.Array:
    """values [C, D] += deltas [U, D] at rows [U] (int32, duplicate-free
    in-bounds; rows outside [0, C) are DROPPED). The Pallas impl aliases
    the table and adds in place, one row-block per grid step."""
    if use_pallas is None:
        use_pallas = True
    if not use_pallas:
        c = values.shape[0]
        u = rows.shape[0]
        # negative rows would WRAP pythonically before the drop check —
        # remap them to distinct out-of-bounds ids so they drop too
        # (distinct keeps the unique_indices promise honest)
        safe = jnp.where(rows < 0, c + jnp.arange(u, dtype=rows.dtype),
                         rows)
        return values.at[safe].add(deltas, mode="drop",
                                   unique_indices=True)
    c, d = values.shape
    u = rows.shape[0]
    # dropped rows are routed to a sacrificial row c (stripped on
    # return) so every REAL row's output block is visited exactly once —
    # revisited blocks can read a stale pipeline copy, which is fine
    # only for content nobody keeps
    ext = jnp.concatenate([values, jnp.zeros((1, d), values.dtype)])

    def kernel(rows_ref, tbl_ref, val_ref, out_ref):
        del tbl_ref
        i = pl.program_id(0)
        r = rows_ref[i]
        ok = (r >= 0) & (r < c)
        out_ref[...] = jnp.where(ok, out_ref[...] + val_ref[...],
                                 out_ref[...])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(u,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # aliased table
            pl.BlockSpec((1, d), lambda i, rows_ref: (i, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, d),
            lambda i, rows_ref: (jnp.where(
                (rows_ref[i] >= 0) & (rows_ref[i] < c), rows_ref[i], c), 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c + 1, d), values.dtype),
        input_output_aliases={1: 0},
        interpret=_interpret(),
    )(rows, ext, deltas)
    return out[:c]


# ---------------------------------------------------------------------------
# Host-facing index object
# ---------------------------------------------------------------------------

def _pad_to_block(a: np.ndarray) -> np.ndarray:
    k = _round_up(max(len(a), 1), _BK)
    out = np.zeros(k, a.dtype)
    out[:len(a)] = a
    return out


def default_use_pallas() -> bool:
    """Kernel choice for the device path: Pallas under interpret mode,
    the XLA while_loop formulation on a real TPU (see module docstring —
    Mosaic has no random-access HBM load; both are device-resident)."""
    return _interpret()


def device_impl() -> str:
    """The dispatch-counter name of what ``DeviceKeyIndex`` runs here."""
    return "pallas" if default_use_pallas() else "xla"


class DeviceKeyIndex:
    """The device half of one table's id→row index: bucket arrays in
    device memory plus the host-tracked next-row cursor. The host kv
    stays AUTHORITATIVE for lifecycle (save/load/shrink/items); this
    object mirrors it only while the kv's allocation is dense
    (next_row == len(kv), no free-list holes) — any state it cannot
    mirror exactly flips ``degraded`` and the seam falls back to the
    host path, loudly, forever (sticky)."""

    def __init__(self, capacity: int, n_buckets: Optional[int] = None):
        if n_buckets is None:
            n_buckets = max(_BK * 2, 1 << int(2 * capacity - 1).bit_length())
        if n_buckets & (n_buckets - 1):
            raise ValueError(f"n_buckets must be a power of 2: {n_buckets}")
        self.capacity = int(capacity)
        self.n_buckets = int(n_buckets)
        self.bh = jnp.zeros(self.n_buckets, jnp.int32)
        self.bl = jnp.zeros(self.n_buckets, jnp.int32)
        self.br = jnp.full(self.n_buckets, _EMPTY, jnp.int32)
        self.next_row = 0
        self.degraded = False
        self.degrade_reason = ""

    def degrade(self, reason: str) -> None:
        if not self.degraded:
            log.warning("device key index degraded -> host path: %s",
                        reason)
        self.degraded = True
        self.degrade_reason = reason

    def seed_from_kv(self, kv) -> bool:
        """Mirror an existing kv: only possible when its allocation is
        dense (rows are exactly 0..len-1); inserting the keys in row
        order then reproduces every row. Returns False (→ degrade)
        otherwise."""
        keys, rows = kv.items()
        n = len(keys)
        if n == 0:
            return True
        if n > self.capacity:
            return False
        order = np.argsort(rows, kind="stable")
        if not np.array_equal(rows[order],
                              np.arange(n, dtype=rows.dtype)):
            return False
        out = self.assign_unique(keys[order])
        if out is None:
            return False
        srows, snew = out
        return bool(np.array_equal(srows, np.arange(n, dtype=np.int64))
                    and snew.all())

    def assign_unique(self, uniq: np.ndarray
                      ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Assign rows to a duplicate-free first-seen-ordered key
        stream. Returns (rows int64, new_mask bool) or None on
        probe/capacity overflow (state unchanged — functional
        rollback)."""
        n = len(uniq)
        if n == 0:
            return np.zeros(0, np.int64), np.zeros(0, bool)
        hi, lo = split_keys(np.ascontiguousarray(uniq, np.uint64))
        bh, bl, br, rows, new, ovf = insert(
            self.bh, self.bl, self.br,
            jnp.asarray(_pad_to_block(hi)), jnp.asarray(_pad_to_block(lo)),
            jnp.int32(n), jnp.int32(self.next_row),
            use_pallas=default_use_pallas())
        if bool(ovf):
            return None
        rows = np.asarray(rows[:n]).astype(np.int64)
        new = np.asarray(new[:n]).astype(bool)
        num_new = int(new.sum())
        if self.next_row + num_new > self.capacity:
            return None
        self.bh, self.bl, self.br = bh, bl, br
        self.next_row += num_new
        return rows, new

    def assign_raw(self, keys: np.ndarray) -> Optional[Tuple[
            np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
        """Raw-id front door: device dedup + insert in one program.
        Returns (uniq u64, first_idx, inv, rows_u int64, new_mask) in
        first-seen order, or None on overflow (state unchanged)."""
        n = len(keys)
        if n == 0:
            z = np.zeros(0, np.int64)
            return (np.zeros(0, np.uint64), z.astype(np.int32),
                    np.zeros(0, np.int32), z, np.zeros(0, bool))
        hi, lo = split_keys(np.ascontiguousarray(keys, np.uint64))
        bh, bl, br, uh, ul, first_pos, inv, nu, rows, new, ovf = \
            dedup_insert(
                self.bh, self.bl, self.br,
                jnp.asarray(_pad_to_block(hi)),
                jnp.asarray(_pad_to_block(lo)),
                jnp.int32(n), jnp.int32(self.next_row),
                use_pallas=default_use_pallas())
        if bool(ovf):
            return None
        u = int(nu)
        uniq = join_keys(np.asarray(uh[:u]), np.asarray(ul[:u]))
        rows_u = np.asarray(rows[:u]).astype(np.int64)
        new_u = np.asarray(new[:u]).astype(bool)
        num_new = int(new_u.sum())
        if self.next_row + num_new > self.capacity:
            return None
        self.bh, self.bl, self.br = bh, bl, br
        self.next_row += num_new
        return (uniq, np.asarray(first_pos[:u]), np.asarray(inv[:n]),
                rows_u, new_u)

    def lookup_rows(self, keys: np.ndarray) -> np.ndarray:
        """Probe rows for keys (any order, duplicates fine); miss → -1."""
        n = len(keys)
        if n == 0:
            return np.zeros(0, np.int64)
        hi, lo = split_keys(np.ascontiguousarray(keys, np.uint64))
        rows = lookup(self.bh, self.bl, self.br,
                      jnp.asarray(_pad_to_block(hi)),
                      jnp.asarray(_pad_to_block(lo)), jnp.int32(n),
                      use_pallas=default_use_pallas())
        return np.asarray(rows[:n]).astype(np.int64)
