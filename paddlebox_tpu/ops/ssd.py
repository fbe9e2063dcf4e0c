"""Chunked state-space scan (Mamba-2's SSD form of the selective scan).

The recurrence, per head h with a scalar decay a_h < 0 and per step t:

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t        S [P, N]
    y_t = S_t C_t

is computed in chunks of ``chunk`` steps. Inside a chunk the outputs are
two matrix products on the MXU (scores ``C B^T`` masked and decayed, times
the inputs), the chunk's contribution to the state is a third, and only
the state at each chunk boundary is carried, in float32, from chunk to
chunk.

Matrix products take ``mm_dtype`` operands (bfloat16) and accumulate in
float32; decays, cumulative sums and the carried state are float32.
``B`` and ``C`` are shared by the ``H / G`` heads of a group and are never
repeated in memory: the score product runs once a group.

Two forms of the one algorithm, chosen by what the shapes show
(``_tiles``), booked as ``pbox_kernel_dispatch_total{kernel="ssd_scan"}``:

* **The kernels** (``impl="pallas"``; Mosaic on a TPU, interpret mode
  elsewhere) where ``chunk``, ``N`` and a group's ``(H / G) P`` columns
  are whole 128-lane tiles. One grid cell is one (sequence, chunk): the
  chunk's rows of ``[x | B | C]``, every group in turn; the chunk axis is
  sequential and the state ``[N, H P]`` lives in VMEM scratch across it.
  The ``Q x Q`` score, gap, decay and ``m`` tiles, ``x dt``, the chunk's
  addition to the state: all VMEM. HBM sees ``x``, ``B``, ``C`` read and
  ``y`` (with the ``skip x`` term) written once. The backward pass is
  written by hand (``jax.custom_vjp``): it walks the chunks in reverse
  with the state's cotangent in scratch, recomputes every tile from the
  inputs (in the transposed domain, so that no tile is ever turned) and
  writes ``[dx | dB | dC]`` once. **Kept for the backward pass**: the
  inputs, and the float32 state that entered each chunk (``T / chunk`` of
  them, 134 MB a sequence of the benchmark's cell, written by the forward
  sweep that the rule runs; a call that is not differentiated, and the
  forward pass of a ``jax.checkpoint``, write none). Recomputing them in
  a sweep of their own would read ``x``, ``B`` and ``dt`` again to save
  one write and one read of as many bytes: not taken. Only the per-head
  vectors over time stay with XLA: ``cumsum(dt a)`` within a chunk before
  the kernels and its reverse after them, each a product with a triangle
  of ones at the highest precision (float32), on arrays ``[H, T]``, a
  two-thousandth of ``x``.
* **The composition** (``impl="xla"``) at every other shape: einsums and
  a ``lax.scan`` over the chunks, differentiated by JAX. It is the
  kernels' oracle.

Where each rounding to ``mm_dtype`` falls, in both forms alike: ``B`` and
``C`` on entry; ``m = scores * decay``; ``x dt``; ``x dt`` weighed by the
decay to the chunk's end; the state that entered a chunk where it meets
``C``. In the hand-written backward pass a cotangent is rounded where it
enters a product (``dy``, ``dy exp(cum)``, the state's cotangent, the
scores' cotangent summed over a group's heads), and nowhere else.
"""

from __future__ import annotations

import functools
import inspect
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.obs import trace
from paddlebox_tpu.ops import pallas_kernels as pk

_LANES = 128
_F32 = jnp.float32


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int = 128, mm_dtype=jnp.bfloat16,
             skip: Optional[jax.Array] = None) -> jax.Array:
    """x [B,T,H,P], dt [B,T,H] (after softplus), a [H] (negative),
    b, c [B,T,G,N] with H % G == 0 -> y [B,T,H,P] float32; with ``skip``
    [H] (Mamba's ``D``), ``y + skip x``, float32 like the rest. Any T:
    the tail is padded with steps of dt = 0, which leave the state as it
    is."""
    t, h = x.shape[1:3]
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    if _tiles(x.shape, b.shape, chunk):
        pk._book_dispatch("ssd_scan", "pallas")
        y = _scan_kernels(
            x, dt, a, b, c,
            jnp.zeros((h,), _F32) if skip is None else skip,
            chunk, jnp.dtype(mm_dtype))
    else:
        pk._book_dispatch("ssd_scan", "xla")
        y = _scan_composed(x, dt, a, b, c, chunk, mm_dtype)
        if skip is not None:
            y = y + skip.astype(_F32)[:, None] * x.astype(_F32)
    return y[:, :t]


def _tiles(x_shape, b_shape, chunk: int) -> bool:
    """Whether a chunk's work is whole (8, 128) tiles: the chunk and the
    state's N are lanes of the score and state tiles, a group's heads lie
    side by side in ``(H / G) P`` lanes, a head is a whole share of a
    128-lane tile or whole tiles, and the heads are whole 8-row tiles of
    the per-head vectors."""
    _, _, h, p = x_shape
    g, n = b_shape[-2:]
    return (chunk % _LANES == 0 and n % _LANES == 0
            and (h // g) * p % _LANES == 0 and h % 8 == 0
            and (_LANES % p == 0 or p % _LANES == 0))


# ---- the composition: every other shape, and the kernels' oracle ------------

def _scan_composed(x, dt, a, b, c, chunk, mm_dtype):
    bsz, t, h, p = x.shape
    g, n = b.shape[-2], b.shape[-1]
    k = h // g
    nc, q = t // chunk, chunk
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
    return y.reshape(bsz, nc * q, h, p)


# ---- the kernels -------------------------------------------------------------
#
# Layout of a cell: the chunk's Q rows of ``[x | B | C]`` packed as the
# model's conv leaves them ([Q, H P + 2 G N]: where ``ssd_scan``'s caller
# split such an array, XLA undoes the split), worked a group at a time.
# A group's K heads lie side by side in K P columns of ``x``, so a head
# is a run of P lanes; the columns are worked a *unit* at a time:
# W = max(P, 128) lanes, W / P heads. A per-head vector over the chunk's
# steps crosses HBM as a row a head ([H, Q] of [B, H, T]: dense, T in the
# lanes); with the same tile turned in VMEM (a column a head, [Q, 128] a
# 128 heads) it makes the Q x Q gap tile by broadcasting alone, and the
# column, spread over the head's lanes, scales ``x``-shaped tiles. The
# state is held transposed, [N, H P], so that every product but the two
# that turn ``B`` or ``C`` contracts the last axis of its left operand.

def _unit(p: int):
    """(lanes of a unit, heads in it)."""
    w = max(p, _LANES)
    return w, w // p


def _column(cols, rows, h: int):
    """Head h's column of ``cols`` [H / 128, Q, 128]: [rows, 1]."""
    return cols[h // _LANES, rows, h % _LANES:h % _LANES + 1]


def _spread(cols, rows, heads, lanes, w: int):
    """Each head's column of a unit's heads over that head's lanes
    (``_head_lanes``): [rows, W]."""
    n = rows.stop - rows.start
    out = None
    for h, mine in zip(heads, lanes):
        col = jnp.broadcast_to(_column(cols, rows, h), (n, w))
        out = col if out is None else jnp.where(mine[:n], col, out)
    return out


def _dot(lhs, rhs, contract_l: int, contract_r: int):
    """float32-accumulated product; float32 operands are multiplied as
    float32 (the tests' ``mm_dtype``), not in one bfloat16 pass."""
    precision = (jax.lax.Precision.HIGHEST if lhs.dtype == jnp.float32
                 else None)
    return jax.lax.dot_general(
        lhs, rhs, (((contract_l,), (contract_r,)), ((), ())),
        precision=precision, preferred_element_type=_F32)


def _to_columns(rows_ref, cols):
    """A [H, Q] block, a row a head, into ``cols`` [H / 128, Q, 128], a
    column a head."""
    h, q = rows_ref.shape
    for j in range(cols.shape[0]):
        rows = rows_ref[j * _LANES:min(h, (j + 1) * _LANES), :]
        if rows.shape[0] < _LANES:
            rows = jnp.concatenate(
                [rows, jnp.zeros((_LANES - rows.shape[0], q), _F32)], axis=0)
        cols[j] = rows.T


def _as_rows(cols, h: int):
    """``_to_columns`` undone: the columns of ``cols`` as [H, Q]."""
    tiles = [cols[j].T for j in range(cols.shape[0])]
    return (tiles[0] if len(tiles) == 1
            else jnp.concatenate(tiles, axis=0))[:h]


def _head_lanes(q: int, w: int, p: int):
    """For each head of a unit, where its lanes are in [Q, W]; nothing to
    mask where a unit is one head."""
    if w == p:
        return [None]
    head_of = jax.lax.broadcasted_iota(jnp.int32, (q, w), 1) // p
    return [head_of == i for i in range(w // p)]


def _own(v, mine):
    """``v`` [rows, W] on a head's own lanes, zero on its neighbours'."""
    return v if mine is None else jnp.where(mine[:v.shape[0]], v,
                                            jnp.zeros_like(v))


class _Cell:
    """Where a group's and a unit's columns lie in a packed row."""

    def __init__(self, h: int, p: int, g: int, n: int):
        self.k, self.p, self.g, self.n = h // g, p, g, n
        self.w, self.per = _unit(p)
        self.units = self.k * p // self.w

    def b(self, j: int):
        lo = self.g * self.k * self.p + j * self.n
        return slice(lo, lo + self.n)

    def c(self, j: int):
        lo = self.g * (self.k * self.p + self.n) + j * self.n
        return slice(lo, lo + self.n)

    def unit(self, j: int, u: int):
        """(the unit's lanes of ``x``, its heads)."""
        lo = j * self.k * self.p + u * self.w
        first = j * self.k + u * self.per
        return slice(lo, lo + self.w), list(range(first, first + self.per))


def _fwd_kernel(xbc_ref, dtr_ref, cumr_ref, skip_ref, y_ref, *rest,
                cell: _Cell, mm, save: bool):
    entered_ref = rest[0] if save else None
    state, dt_ref, cum_ref = rest[-3:]   # [N, H P]; columns [H/128, Q, 128]
    q = xbc_ref.shape[0]
    p, w = cell.p, cell.w
    every, last = slice(0, q), slice(q - 1, q)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if save:
        entered_ref[...] = state[...]
    _to_columns(dtr_ref, dt_ref)
    _to_columns(cumr_ref, cum_ref)
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    lanes = _head_lanes(q, w, p)
    for j in range(cell.g):
        b = xbc_ref[:, cell.b(j)].astype(_F32)
        bm, cm = b.astype(mm), xbc_ref[:, cell.c(j)].astype(mm)
        b_t = b.T.astype(mm)       # turned in float32: the same rounding
        scores = _dot(cm, bm, 1, 1)                   # [Q_l, Q_s]
        for u in range(cell.units):
            cols, heads = cell.unit(j, u)
            cum = _spread(cum_ref, every, heads, lanes, w)
            end = _spread(cum_ref, last, heads, lanes, w)          # [1, W]
            x = xbc_ref[:, cols].astype(_F32)
            xdt = x * _spread(dt_ref, every, heads, lanes, w)
            xdt_m = xdt.astype(mm)
            entered = state[:, cols]
            # the state that entered the chunk, seen from each step
            y = _dot(cm, entered.astype(mm), 1, 0) * jnp.exp(cum)
            # the steps of the chunk, seen from each later one
            for h, mine in zip(heads, lanes):
                gap = _column(cum_ref, every, h) - cumr_ref[h:h + 1, :]
                decay = jnp.exp(jnp.where(causal, gap, -jnp.inf))
                y = y + _own(_dot((scores * decay).astype(mm), xdt_m, 1, 0),
                             mine)
            y_ref[:, cols] = y + skip_ref[:, cols] * x
            # what the chunk leaves of the state, and adds, by its end
            add = _dot(b_t, (xdt * jnp.exp(end - cum)).astype(mm), 1, 0)
            state[:, cols] = jnp.exp(end) * entered + add


def _bwd_kernel(xbc_ref, dtr_ref, cumr_ref, skip_ref, dy_ref, entered_ref,
                dxbc_ref, ddtr_ref, dcumr_ref, dskip_ref, dstate, dt_ref,
                cum_ref, ddt_ref, dcum_ref, *, cell: _Cell, mm):
    """One chunk of the reverse walk. ``dstate`` holds the cotangent of
    the state that LEFT this chunk; it leaves holding that of the state
    that entered. The Q x Q tiles are built turned ([s, l]: row s gives,
    column l receives), so that ``m^T dy`` and the scores' cotangent
    contract last axes. ``dcum`` gathers in two parts, a row a head (what
    the steps it is the later of send it) and a column (the rest), and
    leaves as rows."""
    q = xbc_ref.shape[0]
    p, w = cell.p, cell.w
    every, last = slice(0, q), slice(q - 1, q)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    _to_columns(dtr_ref, dt_ref)
    _to_columns(cumr_ref, cum_ref)
    causal_t = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
                <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    is_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    lanes = _head_lanes(q, w, p)
    for j in range(cell.g):
        c = xbc_ref[:, cell.c(j)].astype(_F32)
        bm, cm = xbc_ref[:, cell.b(j)].astype(mm), c.astype(mm)
        c_t = c.T.astype(mm)
        scores_t = _dot(bm, cm, 1, 1)                 # [Q_s, Q_l]
        dscores_t = jnp.zeros((q, q), _F32)
        db = jnp.zeros((q, cell.n), _F32)
        dc = jnp.zeros((q, cell.n), _F32)
        for u in range(cell.units):
            cols, heads = cell.unit(j, u)
            cum = _spread(cum_ref, every, heads, lanes, w)
            end = _spread(cum_ref, last, heads, lanes, w)
            dt = _spread(dt_ref, every, heads, lanes, w)
            x = xbc_ref[:, cols].astype(_F32)
            xdt = x * dt
            xdt_m = xdt.astype(mm)
            to_end, keep = jnp.exp(end - cum), jnp.exp(end)
            entered = entered_ref[:, cols]
            entered_m = entered.astype(mm)
            left = dstate[:, cols]
            left_m = left.astype(mm)
            dy = dy_ref[:, cols]
            dy_m = dy.astype(mm)

            # y_off = (C entered) exp(cum)
            from_entered = dy * jnp.exp(cum)
            from_entered_m = from_entered.astype(mm)
            dc = dc + _dot(from_entered_m, entered_m, 1, 1)
            dcum = from_entered * _dot(cm, entered_m, 1, 0)      # [Q, W]
            # state' = keep entered + B^T (x dt to_end)
            dstate[:, cols] = keep * left + _dot(c_t, from_entered_m, 1, 0)
            dkeep = jnp.sum(left * entered, axis=0, keepdims=True) * keep
            dweighed = _dot(bm, left_m, 1, 0)                    # [Q, W]
            db = db + _dot((xdt * to_end).astype(mm), left_m, 1, 1)
            dxdt = dweighed * to_end
            dcum = dcum - dxdt * xdt      # to_end = exp(end - cum), at s
            dend = jnp.sum(dxdt * xdt, axis=0, keepdims=True) + dkeep

            # y_own = m xdt, tile by tile and turned
            for h, mine in zip(heads, lanes):
                gap_t = cumr_ref[h:h + 1, :] - _column(cum_ref, every, h)
                decay_t = jnp.exp(jnp.where(causal_t, gap_t, -jnp.inf))
                weighed = scores_t * decay_t
                dxdt = dxdt + _own(_dot(weighed.astype(mm), dy_m, 1, 0),
                                   mine)                         # [Q_s, W]
                dm_t = _dot(_own(xdt_m, mine), dy_m, 1, 1)       # [Q_s, Q_l]
                dscores_t = dscores_t + dm_t * decay_t
                dgap_t = dm_t * weighed
                dcumr_ref[h:h + 1, :] = jnp.sum(dgap_t, axis=0,
                                                keepdims=True)
                # the head's column: the sums over its lanes, and the
                # end's share on the last row
                col = (jnp.sum(_own(dcum, mine), axis=1, keepdims=True)
                       - jnp.sum(dgap_t, axis=1, keepdims=True))
                at_end = jnp.sum(_own(dend, mine), axis=1, keepdims=True)
                dcum_ref[h // _LANES, :, h % _LANES:h % _LANES + 1] = (
                    col + jnp.where(is_last, at_end, 0.0))
            dxbc_ref[:, cols] = (dxdt * dt + skip_ref[:, cols] * dy
                                 ).astype(dxbc_ref.dtype)
            dskip_ref[:, cols] += jnp.sum(dy * x, axis=0, keepdims=True)
            dxdt_x = dxdt * x
            for h, mine in zip(heads, lanes):
                ddt_ref[h // _LANES, :, h % _LANES:h % _LANES + 1] = jnp.sum(
                    _own(dxdt_x, mine), axis=1, keepdims=True)
        dxbc_ref[:, cell.b(j)] = (db + _dot(dscores_t.astype(mm), cm, 1, 0)
                                  ).astype(dxbc_ref.dtype)
        dxbc_ref[:, cell.c(j)] = (dc + _dot(dscores_t.T.astype(mm), bm, 1, 0)
                                  ).astype(dxbc_ref.dtype)
    # rows: what the tiles sent (written above) and the columns, turned
    h = dtr_ref.shape[0]
    dcumr_ref[...] += _as_rows(dcum_ref, h)
    ddtr_ref[...] = _as_rows(ddt_ref, h)


def _earlier(chunk: int):
    """[l, s]: 1 where step s is l or earlier in the chunk."""
    return jnp.tril(jnp.ones((chunk, chunk), _F32))


def _per_head(dt, a, chunk: int):
    """dt [B,T,H], a [H] -> dt and the chunk-wise cumsum(dt a), float32,
    a row a head: [B,H,T] each. The sum is a product with a triangle of
    ones at the highest precision (float32 to a rounding, as the
    composition's ``cumsum`` is): the reduce-window XLA makes of a
    ``cumsum`` over [B, C, Q, G, K] cost 1.9 ms a sequence on a v5e, more
    than the backward sweep (PR 32's traced runs)."""
    bsz, t, h = dt.shape
    dt = jnp.swapaxes(dt.astype(_F32), 1, 2)
    da = (dt * a.astype(_F32)[:, None]).reshape(bsz, h, t // chunk, chunk)
    cum = jnp.einsum("bhcs,ls->bhcl", da, _earlier(chunk),
                     precision=jax.lax.Precision.HIGHEST)
    return dt, cum.reshape(bsz, h, t)


def _packed(x, b, c):
    """[x | B | C] a step: [B, T, H P + 2 G N], the layout the scan's
    caller cut them from (XLA then drops the cut and this)."""
    bsz, t = x.shape[:2]
    return jnp.concatenate([v.reshape(bsz, t, -1) for v in (x, b, c)], -1)


def _over_lanes(skip, p: int):
    """skip [H] -> [1, H P]: a head's weight over its P lanes."""
    return jnp.repeat(skip.astype(_F32), p).reshape(1, -1)


def _specs(h, p, n, chunk, width, step):
    """Block specs of a (sequence, chunk) cell; ``step`` maps the grid's
    second index to the chunk (the reverse walk turns it)."""
    hp = h * p
    packed = pl.BlockSpec((None, chunk, width), lambda s, i: (s, step(i), 0))
    wide = pl.BlockSpec((None, chunk, hp), lambda s, i: (s, step(i), 0))
    rows = pl.BlockSpec((None, h, chunk), lambda s, i: (s, 0, step(i)))
    entered = pl.BlockSpec((None, None, n, hp),
                           lambda s, i: (s, step(i), 0, 0))
    # a row over the heads' lanes: the skip's weights, and (a sequence's
    # own, gathered over its chunks) their gradient
    lanes = pl.BlockSpec((1, hp), lambda s, i: (0, 0))
    dlanes = pl.BlockSpec((None, 1, hp), lambda s, i: (s, 0, 0))
    return packed, wide, rows, entered, lanes, dlanes


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=100 * 1024 * 1024)


def _columns(h: int, chunk: int):
    return pltpu.VMEM((-(-h // _LANES), chunk, _LANES), _F32)


def _sweep(rule):
    """A sweep as the model's layers share it. Jitted, so that every call
    at the same shapes (a layer's, the next layer's) is one trace of the
    kernel's body and one function of the lowered module: tracing a
    kernel is seconds of set-up in every process, cached programs or not.
    And every op of it under the scan's scope, whatever stack the caller's
    transformation leaves it: a ``custom_vjp`` rule's ops carry the names
    the rule gives them, and the primal that ``optimize_remat`` puts in a
    checkpoint's forward pass carries none."""
    @functools.wraps(rule)
    def scoped(*args, **static):
        with jax.named_scope(trace.SCOPE_SSM_SCAN):
            return rule(*args, **static)
    return jax.jit(scoped, static_argnames=tuple(
        name for name, par in inspect.signature(rule).parameters.items()
        if par.kind is par.KEYWORD_ONLY))


@_sweep
def _forward(x, dt, a, b, c, skip, *, chunk, mm, save: bool):
    bsz, t, h, p = x.shape
    g, n = b.shape[-2:]
    xbc = _packed(x, b, c)
    packed, wide, rows, entered, lanes, _ = _specs(
        h, p, n, chunk, xbc.shape[-1], lambda i: i)
    out_shape = [jax.ShapeDtypeStruct((bsz, t, h * p), _F32)]
    out_specs = [wide]
    if save:
        out_shape.append(
            jax.ShapeDtypeStruct((bsz, t // chunk, n, h * p), _F32))
        out_specs.append(entered)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, cell=_Cell(h, p, g, n), mm=mm,
                          save=save),
        grid=(bsz, t // chunk),
        in_specs=[packed, rows, rows, lanes],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, h * p), _F32)]
        + [_columns(h, chunk)] * 2,
        compiler_params=_params(), interpret=pk._interpret(),
    )(xbc, *_per_head(dt, a, chunk), _over_lanes(skip, p))
    return out[0].reshape(bsz, t, h, p), (out[1] if save else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_kernels(x, dt, a, b, c, skip, chunk, mm):
    return _forward(x, dt, a, b, c, skip, chunk=chunk, mm=mm, save=False)[0]


def _scan_kernels_fwd(x, dt, a, b, c, skip, chunk, mm):
    y, entered = _forward(x, dt, a, b, c, skip, chunk=chunk, mm=mm,
                          save=True)
    return y, (x, dt, a, b, c, skip, entered)


def _scan_kernels_bwd(chunk, mm, res, dy):
    return _backward(*res, dy, chunk=chunk, mm=mm)


@_sweep
def _backward(x, dt, a, b, c, skip, entered, dy, *, chunk, mm):
    bsz, t, h, p = x.shape
    g, n = b.shape[-2:]
    nc = t // chunk
    xbc = _packed(x, b, c)
    packed, wide, rows, entered_spec, lanes, dlanes = _specs(
        h, p, n, chunk, xbc.shape[-1], lambda i: nc - 1 - i)
    dt_r, cum_r = _per_head(dt, a, chunk)
    per_head = jax.ShapeDtypeStruct((bsz, h, t), _F32)
    dxbc, ddt, dcum, dskip = pl.pallas_call(
        functools.partial(_bwd_kernel, cell=_Cell(h, p, g, n), mm=mm),
        grid=(bsz, nc),
        in_specs=[packed, rows, rows, lanes, wide, entered_spec],
        out_specs=[packed, rows, rows, dlanes],
        out_shape=[
            jax.ShapeDtypeStruct(xbc.shape, xbc.dtype), per_head, per_head,
            jax.ShapeDtypeStruct((bsz, 1, h * p), _F32)],
        scratch_shapes=[pltpu.VMEM((n, h * p), _F32)]
        + [_columns(h, chunk)] * 4,
        compiler_params=_params(), interpret=pk._interpret(),
    )(xbc, dt_r, cum_r, _over_lanes(skip, p),
      dy.astype(_F32).reshape(bsz, t, h * p), entered)
    dx, db, dc = jnp.split(dxbc, [h * p, h * p + g * n], -1)
    # cum = cumsum(dt a) within a chunk: its cotangent sums from each
    # step to the chunk's end (the same triangle, turned)
    dda = jnp.einsum("bhcl,ls->bhcs", dcum.reshape(bsz, h, nc, chunk),
                     _earlier(chunk), precision=jax.lax.Precision.HIGHEST
                     ).reshape(bsz, h, t)
    ddt = ddt + dda * a.astype(_F32)[:, None]
    return (dx.reshape(x.shape).astype(x.dtype),
            jnp.swapaxes(ddt, 1, 2).astype(dt.dtype),
            jnp.sum(dda * dt_r, axis=(0, 2)).astype(a.dtype),
            db.reshape(b.shape).astype(b.dtype),
            dc.reshape(c.shape).astype(c.dtype),
            jnp.sum(dskip.reshape(bsz, h, p), axis=(0, 2)).astype(skip.dtype))


# optimize_remat: the forward pass of a ``jax.checkpoint`` around the scan
# (the model's layers) runs the sweep that writes no states
_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd,
                     optimize_remat=True)
