"""Pallas TPU kernels for the device-side CTR op family (ISSUE 13).

The rest of PaddleBox's CTR op family after the PR 11 embed-pool-CVM
suite — the ops `rank_attention_op.cu`, `batch_fc_op.cu` and
`cross_norm_hadamard.cu.h` run as hand-fused CUDA kernels in the
reference (SURVEY §0) but were naive XLA compositions here. Worst
offender: the `rank_attention` einsum gathered `param[block]` into an
`[N, K, D, P]` tensor (~800 MB at N=4096, D=P=128) where the CUDA
reference streams batched GEMMs. The kernels below apply the PR 11
recipe (blocked VMEM residency + one-hot matmuls on the MXU — the
FusedMM / Ragged-Paged-Attention shape, PAPERS.md):

- ``fused_rank_attention`` — block-grouped formulation: the at most
  ``max_rank²`` (≤ 9) param blocks stay VMEM-resident for the whole
  grid; per grid step one TN-row block of the gathered co-shown-ad
  features streams in and, per param block b, a (row, key)-one-hot
  [TN, TN·K] folds the keep mask into the MXU matmul
  ``onehot_b @ x_block @ P[b]`` accumulated into the output block —
  the `[N, K, D, P]` gather is never materialized. The ``custom_vjp``
  scatters the param cotangent into the max_rank² blocks and lets dX
  flow only under ``enable_input_bp`` (covers ``rank_attention`` and
  ``rank_attention2``).
- ``fused_batch_fc`` — per-slot blocked batched GEMM: one slot's
  weight block stays VMEM-resident while TN-row input blocks stream
  through, with the bias add fused while the output block is still in
  VMEM (default, batchcount and transpose_weight modes — the
  transpose rides ``dot_general`` dimension numbers, no materialized
  weight transpose).
- ``fused_cross_norm_hadamard`` — one VMEM pass per (row-block,
  field): loads the field's [a, b] pair block once and emits the
  normalized ``[a, b, a⊙b, a·b]`` output block in the same residency
  (the data_norm mean/scale are applied before the block leaves VMEM;
  the summary update and the sharded ``sync_stats`` psum stay outside
  in ``ops/cross_norm``).

Backwards are hand-written jnp mirroring the XLA compositions'
autodiff ops exactly, so given the same upstream cotangent the grads
match the flag-off path bitwise (gated in tests/test_pallas_ctr.py);
only the forwards carry MXU summation-order f32 drift.

Dispatch: each op's module owns ONE seam reading its
``FLAGS.use_pallas_{rank_attention,batch_fc,cross_norm}`` flag
(ops/rank_attention.py, ops/batch_fc.py, ops/cross_norm.py); a shape
that overflows the kernel's VMEM residency budget (checked statically
— these ops have no runtime raggedness) falls back to the XLA
composition, and both decisions book
``pbox_kernel_dispatch_total{kernel,impl}``. All kernels run in
interpret mode off-TPU (the CPU-mesh testability contract of
ops/pallas_kernels).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddlebox_tpu.ops.pallas_kernels import (_book_dispatch, _interpret,
                                              _round_up)

#: rows per grid step (output block height) shared by the CTR kernels
_TN = 128
#: VMEM budget for a kernel's resident working set (bytes) — param
#: blocks + one streamed input/output block must fit comfortably under
#: the ~16 MB VMEM with room for the pipeline's double buffer
_VMEM_BUDGET = 8 * 1024 * 1024


# ---------------------------------------------------------------------------
# rank_attention — block-grouped MXU kernel
# ---------------------------------------------------------------------------

def decode_rank_offset(rank_offset: jax.Array, max_rank: int,
                       n: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``rank_offset`` [N, 1+2K] → (blk [N, K] int32 with −1 for
    invalid entries, idx [N, K] clipped X-row indices, valid [N, K]).

    blk = (own−1)·max_rank + (rank_k−1), the RankParam block id of the
    (own-rank, co-rank) pair (rank_attention_op.cu:60-90); entries with
    own ≤ 0 or rank_k ≤ 0 get the −1 drop marker (they contribute
    nothing on every path). Out-of-range ranks clip into the block
    table exactly like the historical einsum path."""
    ks = jnp.arange(max_rank)
    own = rank_offset[:, 0] - 1                       # [N], −1 ⇒ invalid
    faster = rank_offset[:, 1 + 2 * ks] - 1           # [N, K]
    idx = jnp.clip(rank_offset[:, 2 + 2 * ks], 0, n - 1)
    valid = (own[:, None] >= 0) & (faster >= 0)
    blk = jnp.clip(own[:, None], 0, max_rank - 1) * max_rank \
        + jnp.clip(faster, 0, max_rank - 1)
    return jnp.where(valid, blk, -1).astype(jnp.int32), idx, valid


def normalize_rank_param(rank_param: jax.Array, max_rank: int,
                         d: int) -> jax.Array:
    """[max_rank²·D, P] (reference layout) or [max_rank², D, P] →
    the 3-D block view."""
    if rank_param.ndim == 2:
        return rank_param.reshape(max_rank * max_rank, d,
                                  rank_param.shape[-1])
    return rank_param


def rank_attention_fits(max_rank: int, d: int, p: int) -> bool:
    """Static residency check for the fused kernel: all max_rank² param
    blocks plus one [TN·K, D] input and [TN, P] output block must sit
    in the VMEM budget (overflow → the seam's XLA fallback)."""
    mr2 = max_rank * max_rank
    d_pad, p_pad = _round_up(d, 128), _round_up(p, 128)
    resident = mr2 * d_pad * p_pad * 4
    streamed = _TN * max_rank * d_pad * 4 + _TN * p_pad * 4
    return mr2 <= 16 and resident + 2 * streamed <= _VMEM_BUDGET


def _rank_attn_kernel(blk_ref, x_ref, p_ref, o_ref, *, tn: int, k: int,
                      mr2: int):
    nk = tn * k
    rows = jax.lax.broadcasted_iota(jnp.int32, (tn, nk), 0)
    row_of = jax.lax.broadcasted_iota(jnp.int32, (tn, nk), 1) // k
    blk = blk_ref[...]                                # [1, nk]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for b in range(mr2):                              # ≤ 9, fully unrolled
        # onehot[r, j] = 1 iff key j belongs to row r AND routes to
        # param block b — the keep mask (−1 never matches) folds into
        # the same matmul that groups the gathered rows
        onehot = ((row_of == rows) & (blk == b)).astype(jnp.float32)
        g = jnp.dot(onehot, x_ref[...],
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)   # [tn, d_pad]
        acc = acc + jnp.dot(g, p_ref[b],
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    o_ref[...] = acc


def _rank_attention_forward(x: jax.Array, rank_offset: jax.Array,
                            rank_param: jax.Array,
                            max_rank: int) -> jax.Array:
    n, d = x.shape
    param3 = normalize_rank_param(rank_param, max_rank, d)
    mr2, _, p = param3.shape
    blk, idx, _valid = decode_rank_offset(rank_offset, max_rank, n)
    k = max_rank
    n_pad = _round_up(max(n, 1), _TN)
    d_pad, p_pad = _round_up(d, 128), _round_up(p, 128)

    # the gathered co-shown-ad features, [N·K, D] — this stays an XLA
    # row gather (cheap, K ≤ max_rank); the kernel's one-hot drops the
    # invalid entries so no pre-masking is needed
    x_flat = x[idx].reshape(n * k, d).astype(jnp.float32)
    xp = jnp.zeros((n_pad * k, d_pad), jnp.float32)
    xp = xp.at[:n * k, :d].set(x_flat)
    blk_row = jnp.full((1, n_pad * k), -1, jnp.int32)
    blk_row = blk_row.at[0, :n * k].set(blk.reshape(n * k))
    pp = jnp.zeros((mr2, d_pad, p_pad), jnp.float32)
    pp = pp.at[:, :d, :p].set(param3.astype(jnp.float32))

    out = pl.pallas_call(
        functools.partial(_rank_attn_kernel, tn=_TN, k=k, mr2=mr2),
        grid=(n_pad // _TN,),
        in_specs=[
            pl.BlockSpec((1, _TN * k), lambda i: (0, i)),
            pl.BlockSpec((_TN * k, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((mr2, d_pad, p_pad), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((_TN, p_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, p_pad), jnp.float32),
        interpret=_interpret(),
    )(blk_row, xp, pp)
    return out[:n, :p].astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_rank_attention(x: jax.Array, rank_offset: jax.Array,
                         rank_param: jax.Array, max_rank: int = 3,
                         enable_input_bp: bool = False) -> jax.Array:
    """Block-grouped rank attention on the MXU (see module docstring).

    Same contract as ``ops.rank_attention.rank_attention``: x [N, D],
    rank_offset int32 [N, 1+2·max_rank], rank_param [max_rank²·D, P]
    or [max_rank², D, P] → [N, P]. The backward scatters the param
    cotangent into the max_rank² blocks with the SAME einsum forms the
    XLA composition's autodiff produces (bitwise-equal grads given the
    same upstream cotangent); dX flows only under
    ``enable_input_bp``."""
    return _rank_attention_forward(x, rank_offset, rank_param, max_rank)


def _ra_fwd(x, rank_offset, rank_param, max_rank, enable_input_bp):
    out = _rank_attention_forward(x, rank_offset, rank_param, max_rank)
    return out, (x, rank_offset, rank_param)


def _ra_bwd(max_rank, enable_input_bp, res, g):
    x, rank_offset, rank_param = res
    n, d = x.shape
    param3 = normalize_rank_param(rank_param, max_rank, d)
    mr2 = max_rank * max_rank
    blk, idx, valid = decode_rank_offset(rank_offset, max_rank, n)
    # the SAME block-grouped residuals the XLA fallback builds — its
    # autodiff emits exactly these einsums, so flag-on grads match the
    # flag-off path bitwise
    x_k = jnp.where(valid[..., None], x[idx], 0.0)            # [N, K, D]
    onehot = (blk[..., None] == jnp.arange(mr2)).astype(x.dtype)
    gmat = jnp.einsum("nkd,nkb->bnd", x_k, onehot)
    d_param3 = jnp.einsum("bnd,np->bdp", gmat, g)
    d_param = d_param3.reshape(rank_param.shape).astype(rank_param.dtype)
    if enable_input_bp:
        d_gmat = jnp.einsum("np,bdp->bnd", g, param3)
        d_xk = jnp.einsum("bnd,nkb->nkd", d_gmat, onehot)
        d_xk = jnp.where(valid[..., None], d_xk, 0.0)
        dx = jnp.zeros_like(x).at[idx].add(d_xk.astype(x.dtype))
    else:
        dx = jnp.zeros_like(x)
    return (dx, None, d_param)


fused_rank_attention.defvjp(_ra_fwd, _ra_bwd)


# ---------------------------------------------------------------------------
# batch_fc — per-slot blocked batched GEMM, bias fused in-VMEM
# ---------------------------------------------------------------------------

def batch_fc_fits(i_dim: int, o_dim: int) -> bool:
    """Static residency check: one slot's weight block + a streamed
    [TN, I] input and [TN, O] output block within the VMEM budget
    (row-count independent — rows stream in TN blocks)."""
    i_pad, o_pad = _round_up(i_dim, 128), _round_up(o_dim, 128)
    resident = i_pad * o_pad * 4 + o_pad * 4
    streamed = _TN * (i_pad + o_pad) * 4
    return resident + 2 * streamed <= _VMEM_BUDGET


def _batch_fc_kernel(x_ref, w_ref, b_ref, o_ref, *, transpose_weight: bool):
    xb = x_ref[0]                                     # [tn, i_pad]
    wb = w_ref[0]                    # [i_pad, o_pad] or [o_pad, i_pad]
    dims = (((1,), (1,)), ((), ())) if transpose_weight \
        else (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(xb, wb, dimension_numbers=dims,
                              preferred_element_type=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST)
    o_ref[0] = acc + b_ref[0]        # bias add while VMEM-resident


def _batch_fc_forward(xb: jax.Array, w: jax.Array, bias: jax.Array,
                      transpose_weight: bool) -> jax.Array:
    """xb [S, N, I] × w [S, I, O] (or [S, O, I] transposed) + bias
    [S, O] → [S, N, O], one slot-weight residency per grid column."""
    s, n, i_dim = xb.shape
    o_dim = w.shape[1] if transpose_weight else w.shape[2]
    n_pad = _round_up(max(n, 1), _TN)
    i_pad, o_pad = _round_up(i_dim, 128), _round_up(o_dim, 128)

    xp = jnp.zeros((s, n_pad, i_pad), jnp.float32)
    xp = xp.at[:, :n, :i_dim].set(xb.astype(jnp.float32))
    wshape = (s, o_pad, i_pad) if transpose_weight else (s, i_pad, o_pad)
    wp = jnp.zeros(wshape, jnp.float32)
    wp = wp.at[:, :w.shape[1], :w.shape[2]].set(w.astype(jnp.float32))
    bp = jnp.zeros((s, 1, o_pad), jnp.float32)
    bp = bp.at[:, 0, :o_dim].set(bias.astype(jnp.float32))

    wi, wo = wshape[1], wshape[2]
    out = pl.pallas_call(
        functools.partial(_batch_fc_kernel,
                          transpose_weight=transpose_weight),
        grid=(s, n_pad // _TN),
        in_specs=[
            pl.BlockSpec((1, _TN, i_pad), lambda si, ni: (si, ni, 0)),
            pl.BlockSpec((1, wi, wo), lambda si, ni: (si, 0, 0)),
            pl.BlockSpec((1, 1, o_pad), lambda si, ni: (si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _TN, o_pad), lambda si, ni: (si, ni, 0)),
        out_shape=jax.ShapeDtypeStruct((s, n_pad, o_pad), jnp.float32),
        interpret=_interpret(),
    )(xp, wp, bp)
    return out[:, :n, :o_dim].astype(xb.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_batch_fc(x: jax.Array, w: jax.Array, bias: jax.Array,
                   batchcount: int = 0,
                   transpose_weight: bool = False) -> jax.Array:
    """Fused-bias blocked batched GEMM — same contract as
    ``ops.batch_fc.batch_fc`` (default [S, N, I] mode, batchcount flat
    [bc·N, I] mode, transpose_weight — batchcount mode only, like the
    reference attr). Backward mirrors the XLA einsums' autodiff
    bitwise."""
    out, _ = _bfc_fwd(x, w, bias, batchcount, transpose_weight)
    return out


def _bfc_fwd(x, w, bias, batchcount, transpose_weight):
    if transpose_weight and batchcount <= 0:
        # the reference op defines transpose_weight only for the
        # batchcount layout; silently contracting an [S, O, I] weight
        # on the wrong axis would return garbage, not an error
        raise ValueError(
            "batch_fc: transpose_weight requires batchcount > 0")
    if batchcount > 0:
        ins = x.shape[0] // batchcount
        xb = x.reshape(batchcount, ins, x.shape[-1])
        out = _batch_fc_forward(xb, w, bias, transpose_weight)
        out = out.reshape(batchcount * ins, -1)
    else:
        out = _batch_fc_forward(x, w, bias, False)
    return out, (x, w, bias)


def _bfc_bwd(batchcount, transpose_weight, res, g):
    x, w, bias = res
    if batchcount > 0:
        ins = x.shape[0] // batchcount
        xb = x.reshape(batchcount, ins, x.shape[-1])
        gb = g.reshape(batchcount, ins, -1)
        wb = jnp.swapaxes(w, 1, 2) if transpose_weight else w
        dx = jnp.einsum("bno,bio->bni", gb, wb).reshape(x.shape)
        dwb = jnp.einsum("bni,bno->bio", xb, gb)
        dw = jnp.swapaxes(dwb, 1, 2) if transpose_weight else dwb
        db = gb.sum(axis=1)
    else:
        dx = jnp.einsum("sno,sio->sni", g, w)
        dw = jnp.einsum("sni,sno->sio", x, g)
        db = g.sum(axis=1)
    return (dx.astype(x.dtype), dw.astype(w.dtype), db.astype(bias.dtype))


fused_batch_fc.defvjp(_bfc_fwd, _bfc_bwd)


# ---------------------------------------------------------------------------
# cross_norm_hadamard — one-VMEM-pass cross blocks + data_norm apply
# ---------------------------------------------------------------------------

def cross_norm_fits(embed_dim: int) -> bool:
    """Static residency check: one field's [TB, d] a/b pair blocks, the
    [TB, 3d+1] output block and the field's mean/scale rows."""
    d_pad = _round_up(embed_dim, 128)
    w_pad = _round_up(3 * embed_dim + 1, 128)
    streamed = _TN * (2 * d_pad + w_pad) * 4 + 2 * w_pad * 4
    return 2 * streamed <= _VMEM_BUDGET


def _cross_norm_kernel(a_ref, b_ref, m_ref, s_ref, o_ref, *, d: int):
    av = a_ref[...]                                   # [tb, d_pad]
    bv = b_ref[...]
    had = av * bv
    # d_pad tail columns are zero, so the dot product over the padded
    # lane dim is exact
    dot = jnp.sum(had, axis=-1, keepdims=True)        # [tb, 1]
    w_pad = o_ref.shape[-1]
    pad = w_pad - (3 * d + 1)
    feats = jnp.concatenate(
        [av[:, :d], bv[:, :d], had[:, :d], dot,
         jnp.zeros((av.shape[0], pad), jnp.float32)], axis=-1)
    # normalization applied in the SAME residency (mean/scale pads are
    # zero, so the pad columns stay exactly zero)
    o_ref[...] = (feats - m_ref[...]) * s_ref[...]


def _cross_norm_forward(x: jax.Array, mean: jax.Array, scale: jax.Array,
                        fields_num: int, embed_dim: int) -> jax.Array:
    b = x.shape[0]
    n, d = fields_num, embed_dim
    w_out = 3 * d + 1
    tb = _TN
    b_pad = _round_up(max(b, 1), tb)
    d_pad, w_pad = _round_up(d, 128), _round_up(w_out, 128)

    # FIELD-MAJOR operands: the field axis leads and is squeezed out of
    # every block, so each block's last two dims are (tb, lanes) tiles
    # — a (tb, 1, lanes) block over a [B, n, lanes] array puts a
    # 1-of-n slice in the sublane dim, which Mosaic refuses for n > 1
    pairs = x.reshape(b, n, 2, d).astype(jnp.float32)
    ap = jnp.zeros((n, b_pad, d_pad), jnp.float32)
    ap = ap.at[:, :b, :d].set(pairs[:, :, 0].swapaxes(0, 1))
    bp = jnp.zeros((n, b_pad, d_pad), jnp.float32)
    bp = bp.at[:, :b, :d].set(pairs[:, :, 1].swapaxes(0, 1))
    mp = jnp.zeros((n, 1, w_pad), jnp.float32)
    mp = mp.at[:, 0, :w_out].set(
        mean.reshape(n, w_out).astype(jnp.float32))
    sp = jnp.zeros((n, 1, w_pad), jnp.float32)
    sp = sp.at[:, 0, :w_out].set(
        scale.reshape(n, w_out).astype(jnp.float32))

    out = pl.pallas_call(
        functools.partial(_cross_norm_kernel, d=d),
        grid=(b_pad // tb, n),
        in_specs=[
            pl.BlockSpec((None, tb, d_pad), lambda i, j: (j, i, 0)),
            pl.BlockSpec((None, tb, d_pad), lambda i, j: (j, i, 0)),
            pl.BlockSpec((None, 1, w_pad), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, 1, w_pad), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, tb, w_pad), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b_pad, w_pad), jnp.float32),
        interpret=_interpret(),
    )(ap, bp, mp, sp)
    return out[:, :b, :w_out].swapaxes(0, 1).reshape(
        b, n * w_out).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_cross_norm_hadamard(x: jax.Array, mean: jax.Array,
                              scale: jax.Array, fields_num: int,
                              embed_dim: int) -> jax.Array:
    """One fused VMEM pass: per (row-block, field) build the
    ``[a, b, a⊙b, a·b]`` cross block and apply the data_norm
    ``(v - mean)·scale`` while the block is still resident. ``mean``/
    ``scale`` are the flat [fields_num·(3·embed_dim+1)] data_norm
    vectors (the seam in ``ops/cross_norm`` derives them from the
    summary, keeping the summary-cotangent chain outside this op)."""
    out, _ = _cn_fwd(x, mean, scale, fields_num, embed_dim)
    return out


def _cn_fwd(x, mean, scale, fields_num, embed_dim):
    out = _cross_norm_forward(x, mean, scale, fields_num, embed_dim)
    return out, (x, mean, scale)


def _cn_bwd(fields_num, embed_dim, res, g):
    x, mean, scale = res
    n, d = fields_num, embed_dim
    w_out = 3 * d + 1
    b = x.shape[0]
    pairs = x.reshape(b, n, 2, d)
    a, bb = pairs[:, :, 0], pairs[:, :, 1]
    g3 = g.reshape(b, n, w_out)
    sc = scale.reshape(n, w_out)
    mn = mean.reshape(n, w_out)
    ge = g3 * sc[None]                      # d y / d feats = scale
    ga, gb = ge[..., :d], ge[..., d:2 * d]
    gh, gd = ge[..., 2 * d:3 * d], ge[..., 3 * d:]
    da = ga + gh * bb + gd * bb             # dot = Σ a·b ⇒ ∂/∂a = b
    db = gb + gh * a + gd * a
    dx = jnp.stack([da, db], axis=2).reshape(x.shape).astype(x.dtype)
    # feats recomputed for the scale cotangent (cheap — one mul + sum)
    had = a * bb
    feats = jnp.concatenate(
        [a, bb, had, jnp.sum(had, axis=-1, keepdims=True)], axis=-1)
    dmean = (-ge.sum(axis=0)).reshape(mean.shape).astype(mean.dtype)
    dscale = ((g3 * (feats - mn[None])).sum(axis=0)
              ).reshape(scale.shape).astype(scale.dtype)
    return (dx, dmean, dscale)


fused_cross_norm_hadamard.defvjp(_cn_fwd, _cn_bwd)


__all__ = [
    "fused_rank_attention", "fused_batch_fc", "fused_cross_norm_hadamard",
    "decode_rank_offset", "normalize_rank_param", "rank_attention_fits",
    "batch_fc_fits", "cross_norm_fits", "_book_dispatch",
]
