"""Causal grouped-query attention, blockwise, forward and backward.

One chip's whole sequence (``parallel/ring_attention`` is the form whose
key/value blocks travel a mesh axis; its ``_flash_block`` is the step this
grew from). The [T, T] scores exist for one pair of blocks at a time: the
forward pass keeps the output and each row's log-sum-exp, the backward
pass computes every block pair's probabilities again from them. The
backward pass reads five values: q, k and v, which a caller's
``jax.checkpoint`` gets back from a projection and a rotation, and the
blocked output and the log-sum-exp, which only the forward loops
produce. ``_attention_fwd`` marks those two by name
(``LOOP_RESIDUALS``): a checkpoint whose policy keeps them
(``models/lm_parts.KEEP_ATTN_LOOPS``, every model's attention layer) runs
the forward sweep once a step; under one that keeps nothing the sweep
runs again before the backward sweep, and without a checkpoint a name is
the identity. Blocks
above the diagonal are never visited: query block i loops over key blocks
0..i, a loop whose trip count is data, which is why the backward pass is
written out (``jax.custom_vjp``) and not derived. With a ``window`` W a
query reads the W keys that end at itself, and the loop starts at the
first key block that holds a key of some row's window
(``_first_block``): blocks left of it are not visited either, forward or
backward, and the mask cuts inside the first block that is.

The ``G = H / KV`` query heads that share a key/value head are one matrix
side: a query block is [G * block, D] against its key block [block, D].
Matrix products take ``mm_dtype`` operands (bfloat16) and accumulate in
float32; the softmax is float32.

``rotary_embedding`` is the position embedding a model applies to its
queries and keys before the call (``models/lfm2.py``; ``models/mellum.py``
hands it a table a layer kind, ``yarn_inv_freq``'s on its full layers,
and ``models/laguna.py`` turns only half of a full layer's head); a
model without one (``models/nemotron_h.py``) calls the attention as it
is.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

#: the names of the two residuals that only the forward block loops can
#: produce, as ``_attention_fwd`` marks them for a ``jax.checkpoint``
#: policy: the blocked output and each row's log-sum-exp
LOOP_RESIDUALS = ("pbox_attn_out_blocks", "pbox_attn_row_lse")

#: in place of -inf under the mask: exp() of it is 0 and no row is NaN
_NEG = -1e30


def rotary_embedding(x: jax.Array, theta: Optional[float] = None, *,
                     inv_freq=None, amplitude: float = 1.0,
                     rotary_dim: Optional[int] = None) -> jax.Array:
    """Rotate-half position embedding, float32: x [B, T, heads, D], the
    pair (x_i, x_{i + D/2}) of position t turned by the angle
    ``t * inv_freq_i``; a position is the index in the sequence. The
    inverse frequencies are ``theta^(-2i / D)`` unless a model gives its
    own [D / 2] (``yarn_inv_freq``), and cos and sin are multiplied by
    ``amplitude`` where it is not 1. With a ``rotary_dim`` R under D only
    a head's first R dimensions turn, as a head of R would (the pair
    (x_i, x_{i + R/2}), the table's D read as R), and the other D - R
    pass as they are: a configuration's ``partial_rotary_factor``."""
    if (theta is None) == (inv_freq is None):
        raise ValueError("give the base theta or the inverse frequencies, "
                         "one and not both")
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        turned = rotary_embedding(x[..., :rotary_dim], theta,
                                  inv_freq=inv_freq, amplitude=amplitude)
        return jnp.concatenate([turned, x[..., rotary_dim:]], -1)
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)

    def table(fn):
        return fn(ang) if amplitude == 1.0 else fn(ang) * amplitude

    return x * table(jnp.cos) \
        + jnp.concatenate([-x2, x1], -1) * table(jnp.sin)


def yarn_inv_freq(dim: int, base: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies [dim / 2], float32. Pair i turns
    ``original_len base^(-2i / dim) / (2 pi)`` times over the original
    length, so it turns n times at ``i = c(n)``: pairs up to ``low =
    floor(c(beta_fast))`` keep ``base^(-2i / dim)``, those from ``high =
    ceil(c(beta_slow))`` on have it divided by ``factor``, and between
    the two it is blended along the linear ramp ``(i - low) / (high -
    low)``."""
    def c(turns):
        return dim * math.log(original_len / (2 * math.pi * turns)) \
            / (2 * math.log(base))
    low = max(math.floor(c(beta_fast)), 0)
    high = min(math.ceil(c(beta_slow)), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = base ** (-2 * i / dim)
    return ((1 - ramp) * plain + ramp * plain / factor).astype(np.float32)


def _first_block(i, block: int, window: Optional[int]):
    """The first key block that query block ``i``'s loop visits: 0
    without a window, else the block of the oldest key that the block's
    first row reads."""
    if window is None:
        return 0
    first = (i * block - (window - 1)) // block
    return max(first, 0) if isinstance(first, int) else jnp.maximum(first, 0)


def _blocks(q, k, v, block):
    """q [B,T,KV,G,D] -> [nb,B,KV,G*blk,D]; k [B,T,KV,D], v [B,T,KV,Dv]
    -> [nb,B,KV,blk,D], [nb,B,KV,blk,Dv]."""
    bsz, t, kv, g, d = q.shape
    nb = t // block
    qb = q.reshape(bsz, nb, block, kv, g, d).transpose(1, 0, 3, 4, 2, 5)
    qb = qb.reshape(nb, bsz, kv, g * block, d)
    kb = k.reshape(bsz, nb, block, kv, k.shape[-1]).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(bsz, nb, block, kv, v.shape[-1]).transpose(1, 0, 3, 2, 4)
    return qb, kb, vb


def _unblock_q(ob, shape, block):
    """A query-side block array [nb,B,KV,G*blk,W] back to [B,T,KV,G,W]:
    ``shape`` gives B, T, KV and G, ``ob`` its own width W (the queries'
    D, or the values' Dv for the output)."""
    bsz, t, kv, g = shape[:4]
    nb, w = t // block, ob.shape[-1]
    ob = ob.reshape(nb, bsz, kv, g, block, w).transpose(1, 0, 4, 2, 3, 5)
    return ob.reshape(bsz, t, kv, g, w)


def _unblock_kv(xb, shape):
    bsz, t, kv, d = shape
    return xb.transpose(1, 0, 3, 2, 4).reshape(bsz, t, kv, d)


def _scores(qi, kj, i, j, block, scale, window):
    """Masked scores of query block i against key block j, float32
    [B,KV,G*blk,blk], and the mask: ``0 <= row - col`` (``< window``)."""
    s = jnp.einsum("bkmd,bknd->bkmn", qi, kj,
                   preferred_element_type=jnp.float32) * scale
    rows = i * block + jnp.arange(qi.shape[2]) % block
    cols = j * block + jnp.arange(block)
    mask = rows[:, None] >= cols[None, :]
    if window is not None:
        mask &= rows[:, None] - cols[None, :] < window
    return jnp.where(mask, s, _NEG), mask


def _forward(q, k, v, block, scale, mm_dtype, window):
    """The forward sweep -> (the output by query block [nb,B,KV,G*blk,Dv],
    each row's log-sum-exp [nb,B,KV,G*blk]), float32."""
    qb, kb, vb = _blocks(q.astype(mm_dtype), k.astype(mm_dtype),
                         v.astype(mm_dtype), block)
    nb, bsz, kv, m, _ = qb.shape
    f32 = jnp.float32

    def q_block(i):
        qi = qb[i]

        def kv_block(j, carry):
            top, den, acc = carry
            s, _ = _scores(qi, kb[j], i, j, block, scale, window)
            new_top = jnp.maximum(top, jnp.max(s, -1))
            p = jnp.exp(s - new_top[..., None])
            corr = jnp.exp(top - new_top)
            den = den * corr + jnp.sum(p, -1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkmn,bknd->bkmd", p.astype(mm_dtype), vb[j],
                preferred_element_type=f32)
            return new_top, den, acc

        top, den, acc = jax.lax.fori_loop(
            _first_block(i, block, window), i + 1, kv_block,
            (jnp.full((bsz, kv, m), _NEG, f32), jnp.zeros((bsz, kv, m), f32),
             jnp.zeros((bsz, kv, m, vb.shape[-1]), f32)))
        return acc / den[..., None], top + jnp.log(den)

    return jax.lax.map(q_block, jnp.arange(nb))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention(q, k, v, block, scale, mm_dtype, window):
    ob, _ = _forward(q, k, v, block, scale, mm_dtype, window)
    return _unblock_q(ob, q.shape, block)


def _attention_fwd(q, k, v, block, scale, mm_dtype, window):
    ob, lse = _forward(q, k, v, block, scale, mm_dtype, window)
    ob = checkpoint_name(ob, LOOP_RESIDUALS[0])
    lse = checkpoint_name(lse, LOOP_RESIDUALS[1])
    return _unblock_q(ob, q.shape, block), (q, k, v, ob, lse)


def _attention_bwd(block, scale, mm_dtype, window, res, dout):
    q, k, v, ob, lse = res
    f32 = jnp.float32
    qb, kb, vb = _blocks(q.astype(mm_dtype), k.astype(mm_dtype),
                         v.astype(mm_dtype), block)
    dob = _blocks(dout, k, v, block)[0]                    # [nb,B,KV,M,Dv]
    delta = jnp.sum(ob * dob, -1)                          # [nb,B,KV,M]
    dob = dob.astype(mm_dtype)
    nb = qb.shape[0]

    def q_block(carry, i):
        qi, doi, lse_i, delta_i = qb[i], dob[i], lse[i], delta[i]

        def kv_block(j, carry):
            dq, dk, dv = carry
            s, mask = _scores(qi, kb[j], i, j, block, scale, window)
            p = jnp.where(mask, jnp.exp(s - lse_i[..., None]), 0.0)
            dp = jnp.einsum("bkmd,bknd->bkmn", doi, vb[j],
                            preferred_element_type=f32)
            ds = (p * (dp - delta_i[..., None]) * scale).astype(mm_dtype)
            dq = dq + jnp.einsum("bkmn,bknd->bkmd", ds, kb[j],
                                 preferred_element_type=f32)
            dk = dk.at[j].add(jnp.einsum("bkmn,bkmd->bknd", ds, qi,
                                         preferred_element_type=f32))
            dv = dv.at[j].add(jnp.einsum("bkmn,bkmd->bknd",
                                         p.astype(mm_dtype), doi,
                                         preferred_element_type=f32))
            return dq, dk, dv

        dk, dv = carry
        dq, dk, dv = jax.lax.fori_loop(
            _first_block(i, block, window), i + 1, kv_block,
            (jnp.zeros(qi.shape, f32), dk, dv))
        return (dk, dv), dq

    (dk, dv), dq = jax.lax.scan(
        q_block, (jnp.zeros(kb.shape, f32), jnp.zeros(vb.shape, f32)),
        jnp.arange(nb))
    return (_unblock_q(dq, q.shape, block).astype(q.dtype),
            _unblock_kv(dk, k.shape).astype(k.dtype),
            _unblock_kv(dv, v.shape).astype(v.dtype))


_attention.defvjp(_attention_fwd, _attention_bwd)


def causal_gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         block: int = 512, sm_scale=None,
                         mm_dtype=jnp.bfloat16,
                         window: Optional[int] = None) -> jax.Array:
    """q, k [B,T,H,D] / [B,T,KV,D], v [B,T,KV,Dv] with H % KV == 0 (query
    head h reads key/value head h // (H / KV)) -> [B,T,H,Dv] float32;
    values may be narrower or wider than queries and keys (latent
    attention's 192 / 128, ``models/kimi_linear.py``). The block is the
    largest divisor of T that ``block`` allows. Position t reads the keys
    ``s <= t``, with a ``window`` those of ``t - window < s <= t`` (the
    query itself counts; any positive size, whatever the block). The
    scale is ``D^-0.5`` unless ``sm_scale`` says."""
    bsz, t, h, d = q.shape
    kv = k.shape[2]
    scale = float(sm_scale) if sm_scale is not None else d ** -0.5
    blk = math.gcd(t, block)
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} keys holds no query")
    out = _attention(q.reshape(bsz, t, kv, h // kv, d), k, v, blk, scale,
                     mm_dtype, window)
    return out.reshape(bsz, t, h, v.shape[-1])
