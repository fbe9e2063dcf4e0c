"""Wire bit-packing for pass uploads — host pack (numpy), device unpack
(jit, a few gathers/shifts on the VPU).

Rationale: the resident-pass pack (train/device_pass.py) is pure index
data whose value ranges are far below 32 bits — unique table rows fit 24
bits at the default 8M-row shard, per-key gather positions fit 18 bits at
the default batch sizes. Host→device bandwidth is the scarce resource
(production PCIe is shared with everything else the host streams), so
the pack ships split low/high
arrays and the step reassembles them in-register:

  - 24-bit ("u24"): uint16 low + uint8 high  (3 B/value vs 4)
  - 18-bit ("u18"): uint16 low + 2-bit high packed 4/byte (2.25 B/value)

Both unpacks are exact; values must be non-negative and in range (the
packers assert).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def pack_u24(values: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int array (any shape, values in [0, 2^24)) → (lo uint16, hi uint8)."""
    v = values.astype(np.uint32, copy=False)
    assert v.max(initial=0) < (1 << 24), "pack_u24 range"
    return (v & 0xFFFF).astype(np.uint16), (v >> 16).astype(np.uint8)


def unpack_u24(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """(lo uint16, hi uint8) → int32, elementwise."""
    return (lo.astype(jnp.int32)
            | (hi.astype(jnp.int32) << 16))


def pack_delta(values: np.ndarray, num_real: np.ndarray,
               max_exceptions: int, bits: int = 16):
    """Ascending per-row sequences → ``bits``-wide (8 or 16) delta wire.

    ``values`` int [nb, U]; rows must be ASCENDING over their real prefix
    ``num_real[i]`` (checked — returns None on violation, as a negative
    delta would wrap mod 2^bits and silently decode to a wrong value).
    Returns (d uint{bits} [nb, U], epos int32 [nb, E], eext int32 [nb, E])
    — deltas relative to values[:, 0] (the base travels separately), with
    up to E per-row gap exceptions (delta ≥ 2^bits) as position+remainder
    pairs (unused slots: epos = U, eext = 0) — or None when a row needs
    more than E exceptions (caller falls back to a wider encoding).

    Decode contract (:func:`unpack_delta16`): value[j] = base +
    cumsum(d)[j] + Σ_e [j ≥ epos_e] · eext_e for j < num_real."""
    assert bits in (8, 16)
    d = _delta_matrix(values, num_real)
    if d is None:
        return None
    return _pack_delta_from(d, max_exceptions, bits)


def _delta_matrix(values: np.ndarray, num_real: np.ndarray):
    """Per-row deltas over the real prefix (int64 [nb, U]), or None if
    any real-prefix row is not ascending."""
    nb, u_pad = values.shape
    d = np.zeros((nb, u_pad), np.int64)
    d[:, 1:] = values[:, 1:].astype(np.int64) - values[:, :-1].astype(np.int64)
    real = np.arange(u_pad)[None, :] < num_real[:, None]
    d[~real] = 0
    if (d < 0).any():
        return None
    return d


def _pack_delta_from(d: np.ndarray, max_exceptions: int, bits: int):
    nb, u_pad = d.shape
    big = d >= (1 << bits)
    if int(big.sum(axis=1).max(initial=0)) > max_exceptions:
        return None
    dn = d.astype(np.uint8 if bits == 8 else np.uint16)
    epos = np.full((nb, max_exceptions), u_pad, np.int32)
    eext = np.zeros((nb, max_exceptions), np.int32)
    for i in range(nb):
        bj = np.nonzero(big[i])[0]
        epos[i, :len(bj)] = bj
        eext[i, :len(bj)] = (d[i, bj] - dn[i, bj]).astype(np.int64)
    return dn, epos, eext


def pack_delta_auto(values: np.ndarray, num_real: np.ndarray,
                    max_exc8: int, max_exc16: int):
    """One delta scan, narrowest width that fits: u8 wire (≤ max_exc8
    gap exceptions per row), else u16 (≤ max_exc16), else None."""
    d = _delta_matrix(values, num_real)
    if d is None:
        return None
    return (_pack_delta_from(d, max_exc8, 8)
            or _pack_delta_from(d, max_exc16, 16))



def unpack_delta16(d16: jax.Array, epos: jax.Array, eext: jax.Array,
                   base: jax.Array) -> jax.Array:
    """One row of the pack_delta16 wire → int32 [U] absolute values
    (traced; valid over the real prefix — callers mask the tail)."""
    u_pad = d16.shape[-1]
    upos = jnp.arange(u_pad, dtype=jnp.int32)
    cum = base + jnp.cumsum(d16.astype(jnp.int32))
    corr = jnp.sum(jnp.where(upos[:, None] >= epos[None, :],
                             eext[None, :], 0), axis=1)
    return cum + corr


def pack_u16m(values: np.ndarray, mbits: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    """int array [..., K] (values in [0, 2^(16+m)), m ∈ {1,2,4,8},
    K % (8/m) == 0) → (lo uint16 [..., K], hi uint8 [..., K*m/8] —
    8/m m-bit highs per byte, little-endian within the byte)."""
    assert mbits in (1, 2, 4, 8)
    v = values.astype(np.uint32, copy=False)
    assert v.max(initial=0) < (1 << (16 + mbits)), "pack_u16m range"
    per = 8 // mbits
    assert v.shape[-1] % per == 0, "pack_u16m alignment"
    lo = (v & 0xFFFF).astype(np.uint16)
    hi = (v >> 16).astype(np.uint8)
    h = hi.reshape(*hi.shape[:-1], -1, per)
    packed = np.zeros(h.shape[:-1], np.uint8)
    for j in range(per):
        packed |= h[..., j] << (j * mbits)
    return lo, packed


def unpack_u16m(lo: jax.Array, hi: jax.Array, mbits: int) -> jax.Array:
    """(lo uint16 [..., K], hi uint8 [..., K*m/8]) → int32 [..., K]
    (traced). A byte's 8/m highs fan out over a new minor axis by
    shifts and fold back by a reshape: no gather (``hi[pos // per]`` is
    one index a key, and a TPU gather is paid by the index; at m = 8 it
    returned its operand for 1.7 ms a step of cell 1: ledger, PR 35)."""
    per = 8 // mbits
    h = hi.astype(jnp.int32)
    if per > 1:
        shifts = jnp.arange(per, dtype=jnp.int32) * mbits
        h = ((h[..., None] >> shifts) & ((1 << mbits) - 1)
             ).reshape(lo.shape)
    return lo.astype(jnp.int32) | (h << 16)


def pack_u12(values: np.ndarray) -> Tuple[np.ndarray]:
    """int array [..., K] (values in [0, 2^12), K % 2 == 0) → one uint8
    stream [..., K*3/2]: value pairs ride as 3 bytes (lo8_a,
    hi4_a | lo4_b<<4, hi8_b). The thousand-slot wire lever: per-slot
    CTR vocabularies are a few thousand entries, so slot-local rows fit
    12 bits and the u16 wire ships 25% padding (the thousand-slot
    shape's wire is ~all per-key locals)."""
    v = values.astype(np.uint32, copy=False)
    assert v.max(initial=0) < (1 << 12), "pack_u12 range"
    assert v.shape[-1] % 2 == 0, "pack_u12 alignment"
    p = v.reshape(*v.shape[:-1], -1, 2)
    out = np.empty((*p.shape[:-1], 3), np.uint8)
    out[..., 0] = p[..., 0] & 0xFF
    out[..., 1] = ((p[..., 0] >> 8) & 0xF) | ((p[..., 1] & 0xF) << 4)
    out[..., 2] = (p[..., 1] >> 4) & 0xFF
    return (out.reshape(*v.shape[:-1], -1),)


def unpack_u12(b: jax.Array) -> jax.Array:
    """uint8 [K*3/2] → int32 [K] (traced)."""
    t = b.reshape(*b.shape[:-1], -1, 3).astype(jnp.int32)
    a = t[..., 0] | ((t[..., 1] & 0xF) << 8)
    c = (t[..., 1] >> 4) | (t[..., 2] << 4)
    return jnp.stack([a, c], axis=-1).reshape(*b.shape[:-1], -1)


def pack_u18(values: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """18-bit :func:`pack_u16m` (kept for call-site clarity)."""
    return pack_u16m(values, 2)


def unpack_u18(lo: jax.Array, hi2: jax.Array) -> jax.Array:
    """(lo uint16 [K], hi2 uint8 [K/4]) → int32 [K] (traced)."""
    return unpack_u16m(lo, hi2, 2)
