"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO sequence-parallel mechanism (verified by repo-wide
grep, SURVEY.md §2.6/§5.7 — its only sequence-length device is the
data-level uid-merge split, data_feed.h:624). Long-context support is
nonetheless first-class here, TPU-native by construction:

- ``ring_attention``: blockwise attention with K/V blocks rotating around
  the mesh axis via ``jax.lax.ppermute`` (ICI neighbor exchange), merged
  with the numerically-stable online-softmax accumulation (flash-style
  running max/denominator). Memory per chip is O(T_local²-ish block
  work); the full T_global×T_global score matrix never materializes.
  Compute of ring hop i overlaps the ppermute of hop i+1 (XLA schedules
  the collective-permute concurrently with the einsum).
- ``ulysses_attention``: the all-to-all alternative — resharding
  [B, T/n, H, D] → [B, T, H/n, D] over ICI, local full attention on a
  head subset, and the inverse all-to-all. Cheaper for moderate T with
  many heads; ring wins when T_global is too large for any single chip.

Both run under ``jax.shard_map`` over a mesh axis and are exercised on
the 8-device CPU mesh in tests (tests/test_ring_attention.py) against a
single-device reference attention.

Callers (ROADMAP D5 reads this): every function of this module is
test-only: no model of the benchmark calls ``ring_attention``,
``ulysses_attention``, ``make_context_parallel_attention`` or
``reference_attention``. The one-chip form that the benchmark runs
(cell ``nemotron3-nano-30b-a3b.train-packed-8k``) is
``ops/causal_attention.causal_gqa_attention``: ``_flash_block``'s online
softmax with a causal block loop, grouped-query heads and a backward
pass that recomputes the scores.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _flash_block(q, k, v, scale, mask, o, m, l):
    """One blockwise attention accumulation step (online softmax).

    q [B,Tq,H,D], k/v [B,Tk,H,D]; o [B,Tq,H,D] running numerator,
    m [B,H,Tq] running max, l [B,H,Tq] running denominator.
    mask [Tq,Tk] True = attend, or None.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (m_new == -inf): exp(-inf - -inf) = nan
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr.transpose(0, 2, 1)[..., None] \
        + jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o_new, m_new, l_new


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Context-parallel attention over a mesh axis (call under shard_map).

    q/k/v: [B, T_local, H, D] — the sequence dim sharded over
    ``axis_name`` in contiguous blocks (block i = positions
    [i*T_local, (i+1)*T_local)). Returns [B, T_local, H, D].
    """
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    b, t, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)
    perm = [(i, (i + 1) % n) for i in range(n)]  # ring: pass K/V rightward

    q_pos = me * t + jnp.arange(t)

    o = jnp.zeros_like(q)
    m = jnp.full((b, h, t), -jnp.inf, q.dtype)
    l = jnp.zeros((b, h, t), q.dtype)

    def hop(i, carry, rotate):
        o, m, l, k_cur, v_cur = carry
        src = (me - i) % n  # whose block we hold at hop i
        if causal:
            k_pos = src * t + jnp.arange(t)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = None
        o, m, l = _flash_block(q, k_cur, v_cur, scale, mask, o, m, l)
        if rotate:
            # rotate K/V for the next hop (overlaps this hop's compute)
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        return o, m, l, k_cur, v_cur

    # peel the last hop: its rotation would be dead ICI traffic
    carry = jax.lax.fori_loop(
        0, n - 1, lambda i, c: hop(i, c, rotate=True), (o, m, l, k, v))
    o, m, l, _, _ = hop(n - 1, carry, rotate=False)
    l_t = l.transpose(0, 2, 1)[..., None]  # [B,T,H,1]
    return o / jnp.maximum(l_t, 1e-20)


def ulysses_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism: reshard
    sequence-sharded → head-sharded, full local attention, reshard back.
    Requires H % axis_size == 0. Call under shard_map.

    q/k/v: [B, T_local, H, D] → returns [B, T_local, H, D].
    """
    n = jax.lax.psum(1, axis_name)
    b, t, h, d = q.shape
    if h % n != 0:
        raise ValueError(
            f"ulysses_attention reshards heads over the axis: H={h} must "
            f"be divisible by axis size {n} (use ring_attention otherwise)")

    def to_heads(x):  # [B,T/n,H,D] → [B,T,H/n,D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    def to_seq(x):    # [B,T,H/n,D] → [B,T/n,H,D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * scale
    if causal:
        tg = t * n
        mask = jnp.arange(tg)[:, None] >= jnp.arange(tg)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    oh = jnp.einsum("bhqk,bkhd->bqhd", p, vh)
    return to_seq(oh)


def reference_attention(q, k, v, causal=False, sm_scale=None):
    """Single-device full attention — the correctness oracle for both
    parallel formulations (and the T-fits-on-one-chip fallback)."""
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        t, tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def make_context_parallel_attention(mesh, axis_name: str,
                                    kind: str = "ring",
                                    causal: bool = False):
    """jit-ready [B, T, H, D] → [B, T, H, D] attention sharded over
    ``axis_name`` (sequence dim). ``kind``: "ring" | "ulysses"."""
    from jax.sharding import PartitionSpec as P

    fn = ring_attention if kind == "ring" else ulysses_attention
    spec = P(None, axis_name, None, None)

    @jax.jit
    def attn(q, k, v):
        return jax.shard_map(
            functools.partial(fn, axis_name=axis_name, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    return attn
