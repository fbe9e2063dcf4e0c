"""Static-shape on-device key dedup (DedupKeysAndFillIdx on the chip).

Reference: the host/CUDA dedup pipeline ``DedupKeysAndFillIdx``
(box_wrapper_impl.h:129) runs per batch before the PS pull. In the
device-resident pass mode (train/device_pass.py) the batch's per-key ROWS
are already in HBM, so dedup happens inside the jit step instead — no host
round-trip.

TPU-shaped formulation: XLA wants static shapes and a TPU gather or
scatter is paid by the index (5-8 ns each at K = 213k, whatever the
element), so ``jnp.unique``, a capacity-sized presence bitmap (~100 ms at
8M rows — measured) and K-wide scatters of scalars are all out. Instead:
sort the K row ids with their positions, mark run starts, prefix-sum the
marks into dense unique ids, and place both results by two more sorts —
sorts, one cumsum and elementwise work over K only, O(K log K) in the
BATCH size, independent of table capacity. Unique order is ascending row id.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def dedup_rows(rows: jax.Array, capacity: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dedup per-key row ids into a compacted unique list.

    Args:
      rows: int32 [K]; invalid/padding keys must carry the sentinel row
        ``capacity`` (the zero row) — it then appears as one regular
        unique entry, exactly like the host path's miss collapse.
      capacity: table row capacity (sentinel row id).

    Returns:
      (unique_rows, gather_idx, num_unique): int32 [K] unique row list,
      int32 [K] mapping each key to its unique position — the
      (unique_rows, gather_idx) contract of ``PullIndex`` — and the
      distinct count U as an int32 scalar on the device. U counts the
      sentinel entry that pad keys collapse into: ``gather_idx`` points
      at it, so it lies inside ``[0, U)``. Padding positions (≥ U) hold
      DISTINCT out-of-bounds values > capacity, never pointed at by
      gather_idx, so that gathers through them clamp to the zero
      sentinel row and table scatters drop them; they are
      ``capacity + 1 + p`` of the sorted positions ``p`` that repeat
      their run's row, ascending (until PR 36: of the positions ≥ U).
      (They do NOT let ``apply_push`` promise ``unique_indices``: it
      scatters LINES, and rows that share a line repeat one.) The
      unique axis is K wide whatever U is, and a TPU gather or scatter
      costs per index, pad or real: U is what lets
      ``gather_full_rows`` / ``apply_push`` stop at the rows the batch
      touched.
    """
    k = rows.shape[0]
    # ONE sort carrying original positions groups the repeats; the two
    # results are then PLACED by sorts, not by K-wide scatters of
    # scalars: at K = 213k on a TPU v5 lite a sort reads 0.24 ms where
    # a scatter read 1.0, the whole function 0.72 where it read 2.26
    # (my chip runs, PR 36). Chosen once, from that measurement.
    pos = jnp.arange(k, dtype=jnp.int32)
    sr, perm = jax.lax.sort((rows, pos), num_keys=1)
    is_first = jnp.concatenate(
        [jnp.ones(1, bool), sr[1:] != sr[:-1]])
    uid_sorted = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    # each key's unique id rides back through the sort permutation:
    # sorting by a permutation applies its inverse
    _, gather_idx = jax.lax.sort((perm, uid_sorted), num_keys=1)
    # compaction: a run's first entry keeps its row (≤ capacity, already
    # ascending), every repeat becomes a distinct id above capacity; one
    # sort brings the rows to the front
    unique_rows = jax.lax.sort(
        jnp.where(is_first, sr, capacity + 1 + pos))
    return unique_rows, gather_idx, uid_sorted[-1] + 1


def dedup_keys_first_seen(
        key_hi: jax.Array, key_lo: jax.Array, num_valid: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """First-seen dedup of 64-bit FEATURE IDS (not row ids) on device —
    the bitwise generalization of ``ps/table.dedup_first_seen``
    (ISSUE 19 stage a): raw ids ride as (hi, lo) int32 halves so the
    whole pipeline stays x64-free.

    Args:
      key_hi, key_lo: int32 [K_pad] — the key's upper/lower 32 bits
        (any bit pattern; keys are compared for EQUALITY only, so
        signedness never matters). Positions ≥ num_valid are padding
        and may hold anything.
      num_valid: int32 scalar — number of real keys.

    Returns ``(uniq_hi, uniq_lo, first_pos, inv, num_unique)``, all
    padded to K_pad:
      - uniq_hi/uniq_lo [K_pad]: the distinct keys in FIRST-SEEN order
        (positions ≥ num_unique hold pad-key garbage — callers slice
        by num_unique).
      - first_pos [K_pad] int32: each unique's first occurrence
        position in the input stream (ascending by construction; pads
        hold K_pad).
      - inv [K_pad] int32: per input position, the unique's first-seen
        rank (``uniq[inv[i]] == key[i]``); pad positions point past
        num_unique.
      - num_unique: int32 scalar count of real uniques.

    Matches the host oracle bit for bit: ``uniq`` equals
    ``dedup_first_seen(keys)[0]``, ``first_pos[:U]`` its first-index
    array and ``inv[:nv]`` its inverse — gated in tier-1
    (tests/test_pallas_index.py)."""
    k = key_hi.shape[0]
    pos = jnp.arange(k, dtype=jnp.int32)
    valid = pos < num_valid
    # validity is the LEADING sort key: pads group after every real key
    # and never merge into a real run even when their stale bits match
    # a real id; (hi, lo) only need to group equal keys, so the signed
    # int32 sort order is fine
    vkey = (~valid).astype(jnp.int32)
    _, sh, sl, perm = jax.lax.sort(
        (vkey, key_hi.astype(jnp.int32), key_lo.astype(jnp.int32), pos),
        num_keys=3)
    sv = perm < num_valid
    is_first = jnp.concatenate(
        [jnp.ones(1, bool),
         (sh[1:] != sh[:-1]) | (sl[1:] != sl[:-1])
         | (sv[1:] != sv[:-1])])
    uid_sorted = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    # each run's first stream position: the sort is stable on pos (it
    # rides as the last key), so a segment-min over the run recovers it
    first_pos = jnp.full(k, k, jnp.int32).at[uid_sorted].min(perm)
    # first-seen rank = order of runs by first position; the pad run
    # (first pad position == num_valid) sorts after every real run and
    # unused slots (first_pos == K_pad) sort last
    order = jnp.argsort(first_pos)
    rank = jnp.zeros(k, jnp.int32).at[order].set(pos)
    inv = jnp.zeros(k, jnp.int32).at[perm].set(rank[uid_sorted],
                                               unique_indices=True)
    fp = first_pos[order]
    gather_at = jnp.minimum(fp, k - 1)
    return (key_hi[gather_at], key_lo[gather_at],
            jnp.where(fp < num_valid, fp, k).astype(jnp.int32), inv,
            jnp.sum((is_first & sv).astype(jnp.int32)))
