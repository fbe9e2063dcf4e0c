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

