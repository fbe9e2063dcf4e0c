"""Causal depthwise convolution over a few positions, and the gated short
convolution built on it.

``causal_depthwise_conv`` is the one statement of the conv that both
sequence models run: ``models/nemotron_h.py``'s Mamba-2 mixer (4 taps,
then its own bias and silu) and ``models/lfm2.py``'s operator (3 taps, no
bias, no activation, two gates around it). It is K shifted products over
one zero-padded copy: memory-bound elementwise passes that XLA fuses with
their neighbours, forward and (derived) backward; float32 throughout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_depthwise_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """x [B, T, C], taps w [K, C] -> [B, T, C]: position t is
    ``sum_j w[j] * x[t - (K - 1) + j]``, zero before the sequence."""
    k, t = w.shape[0], x.shape[1]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(pad[:, j:j + t] * w[j] for j in range(k))


def gated_short_conv(bcv: jax.Array, w: jax.Array) -> jax.Array:
    """``[B | C | v]`` [B, T, 3 C] (an input projection's result) and
    taps w [K, C] -> ``C * conv(B * v)`` [B, T, C]."""
    b, c, v = jnp.split(bcv, 3, axis=-1)
    return c * causal_depthwise_conv(b * v, w)
