"""Chunked state-space scan (Mamba-2's SSD form of the selective scan).

The recurrence, per head h with a scalar decay a_h < 0 and per step t:

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t        S [P, N]
    y_t = S_t C_t

is computed in chunks of ``chunk`` steps. Inside a chunk the outputs are
two matrix products on the MXU (scores ``C B^T`` masked and decayed, times
the inputs), the chunk's contribution to the state is a third, and only
the state at each chunk boundary is carried, in float32, through a
``lax.scan`` over the chunks. Autodiff of this form keeps the boundary
states (T / chunk of them) and never a state per step; under the model's
per-layer ``jax.checkpoint`` they live for one layer's backward pass.

Matrix products take ``mm_dtype`` operands (bfloat16) and accumulate in
float32; decays, cumulative sums and the carried state are float32.
``B`` and ``C`` are shared by the ``H / G`` heads of a group and are never
repeated in memory: the score product runs once a group.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int = 128,
             mm_dtype=jnp.bfloat16) -> jax.Array:
    """x [B,T,H,P], dt [B,T,H] (after softplus), a [H] (negative),
    b, c [B,T,G,N] with H % G == 0 -> y [B,T,H,P] float32 (without the
    ``D x`` skip). Any T: the tail is padded with steps of dt = 0, which
    leave the state as it is."""
    bsz, t, h, p = x.shape
    g, n = b.shape[-2], b.shape[-1]
    k = h // g
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc, q = (t + pad) // chunk, chunk
    f32 = jnp.float32
    x = x.reshape(bsz, nc, q, g, k, p)
    dt = dt.astype(f32).reshape(bsz, nc, q, g, k)
    b = b.reshape(bsz, nc, q, g, n).astype(mm_dtype)
    c = c.reshape(bsz, nc, q, g, n).astype(mm_dtype)
    cum = jnp.cumsum(dt * a.astype(f32).reshape(g, k), axis=2)  # [B,C,Q,G,K]
    xdt = (x.astype(f32) * dt[..., None])

    # inside a chunk: y_l = sum_{s<=l} (C_l . B_s) exp(cum_l - cum_s) dt_s x_s
    scores = jnp.einsum("bclgn,bcsgn->bcgls", c, b,
                        preferred_element_type=f32)           # [B,C,G,Q,Q]
    cum_t = jnp.moveaxis(cum, 2, -1)                          # [B,C,G,K,Q]
    gap = cum_t[..., :, None] - cum_t[..., None, :]           # [B,C,G,K,Q,Q]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, gap, -jnp.inf))
    m = (scores[:, :, :, None] * decay).astype(mm_dtype)
    y = jnp.einsum("bcgkls,bcsgkp->bclgkp", m, xdt.astype(mm_dtype),
                   preferred_element_type=f32)

    # what a chunk adds to the state by its end, and how much of the
    # state that entered it is left by then
    to_end = jnp.exp(cum[:, :, -1:] - cum)                    # [B,C,Q,G,K]
    add = jnp.einsum("bcsgkp,bcsgn->bcgkpn",
                     (xdt * to_end[..., None]).astype(mm_dtype), b,
                     preferred_element_type=f32)
    keep = jnp.exp(cum[:, :, -1])                             # [B,C,G,K]

    def carry_state(s, xs):
        add_c, keep_c = xs
        return keep_c[..., None, None] * s + add_c, s

    _, entered = jax.lax.scan(
        carry_state, jnp.zeros((bsz, g, k, p, n), f32),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(keep, 1, 0)))
    entered = jnp.moveaxis(entered, 0, 1)                     # [B,C,G,K,P,N]

    # what the state that entered the chunk gives each of its steps
    y = y + jnp.einsum("bclgn,bcgkpn->bclgkp", c, entered.astype(mm_dtype),
                       preferred_element_type=f32) \
        * jnp.exp(cum)[..., None]
    return y.reshape(bsz, nc * q, h, p)[:, :t]
