"""The slot arena's chunk map, read on the device without a gather.

The compact wire (train/device_pass.py) ships a key as its slot-local
row; the device turns it into a table row through the arena's chunk map,
``cmap[slot, local >> chunk_bits]``. As a gather that is one index a
key, and a TPU gather is paid by the index: 1.55 ms a step at cell 1's
212,992 keys, whatever the map's size. Where the trace can see that key
p belongs to slot p % S, :func:`cmap_select` reads the same integers by
a one-hot product and a select: 0.05 ms at cell 1's 26 x 2,048 map, 0.33
at a stride of 32,768, 1.37 at 131,072 (TPU v5 lite: my chip runs,
PR 36). An arena of 8.4M rows in chunks of 4,096 has a stride of at most
2,048.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the chunks of a row's stretch, the second level, as a power of two
# (32; 16, 64 and 128 read 0.16, 0.10 and 0.66 ms where 32 read 0.07)
_CMAP_LANE_BITS = 5


def cmap_select(cmap: jax.Array, c: jax.Array, max_chunk: int
                ) -> jax.Array:
    """``cmap[s, c[b, s]]`` for every cell of a [B, S] grid of chunk
    indices, with no gather: column s only ever reads row s (int32
    [S, stride]) of the map.

    Two levels. A row is cut into stretches of 32 chunks
    (``_CMAP_LANE_BITS``); a one-hot of the stretch ``c >> 5`` times the
    row's stretches (a batched product over s, on the MXU) hands every
    cell its stretch, and a compare-and-select over its 32 entries picks
    ``c & 31``. The product is exact at any matmul precision: a chunk id
    (<= ``max_chunk``) rides as bytes, so every operand is an integer
    under 256 (a bfloat16 holds those) and a one-hot row sums one
    product. A ``c`` outside the row reads 0 (the gather clamps; the
    decode masks such keys either way)."""
    s, stride = cmap.shape
    lanes = 1 << _CMAP_LANE_BITS
    h = -(-stride // lanes)
    t = jnp.pad(cmap, ((0, 0), (0, h * lanes - stride))
                ).reshape(s, h, lanes)
    nbytes = -(-max(int(max_chunk).bit_length(), 1) // 8)
    tab = jnp.concatenate([(t >> (8 * i)) & 0xFF for i in range(nbytes)],
                          axis=-1).astype(jnp.float32)
    onehot = ((c >> _CMAP_LANE_BITS)[..., None]
              == jnp.arange(h, dtype=jnp.int32)).astype(jnp.float32)
    y = jnp.einsum("bsh,shn->bsn", onehot, tab).astype(jnp.int32)
    word = y[..., :lanes]
    for i in range(1, nbytes):
        word = word | (y[..., i * lanes:(i + 1) * lanes] << (8 * i))
    pick = ((c & (lanes - 1))[..., None]
            == jnp.arange(lanes, dtype=jnp.int32))
    return jnp.sum(jnp.where(pick, word, 0), axis=-1)
