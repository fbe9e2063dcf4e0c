"""Pallas TPU kernels for the embedding-PS hot paths.

Reference hot kernels being replaced (SURVEY.md §2.1-2.2, §2.4):
- ``PullCopy``/``CopyForPull`` gather (fleet/box_wrapper.cu:75,945) and the
  HeterPS hashtable ``get`` → here ``gather_rows``: a scalar-prefetch row
  gather where the Pallas pipeline double-buffers eight aligned (8, D)
  tile DMAs per grid step (HBM→VMEM), overlapping fetches across steps.
- ``PushMergeCopy`` scatter (box_wrapper.cu:417) + in-kernel optimizer write
  (heter_ps/optimizer.cuh.h) → no kernel: ``table.apply_push`` is XLA's
  masked scatter-add over packed table lines, with the optimizer math in
  jnp where XLA fuses it (a per-row scatter kernel is ruled out below).
- ``FusedSeqpoolKernelNormal`` (fused/fused_seqpool_cvm_op.cu:36) →
  ``segment_sum_mxu``: the ragged per-slot sum-pool recast as a blocked
  one-hot × values matmul so it runs on the MXU systolic array instead of
  scalar scatter-adds — the TPU-first formulation of segment_sum.

- ``FusedSeqpoolCVMKernel*`` + ``FusedCVMKernelWithCVM``
  (fused/fused_seqpool_cvm_op.cu:36-298) → ``fused_embed_pool_cvm`` /
  ``fused_pool_cvm_forward``: ONE blocked Pallas pass that streams
  key-blocks of pulled embeddings HBM→VMEM (the pipeline double-buffers
  the block DMA, indices scalar-prefetched), pools them on the MXU via
  the one-hot × values matmul, and applies the CVM log transform while
  the output block is still VMEM-resident. The ``custom_vjp`` backward
  produces per-row grads with ``segment_gather_mxu`` — the transposed
  one-hot matmul — instead of an XLA per-element gather.

All kernels auto-fall back to interpret mode off-TPU so the whole suite is
testable on the CPU mesh (SURVEY.md §4 implication).

The suite's CTR op family half (``fused_rank_attention``,
``fused_batch_fc``, ``fused_cross_norm_hadamard`` — ISSUE 13) lives in
the sibling ``ops/pallas_ctr.py``, sharing this module's interpret/
padding/dispatch-booking helpers and the same MXU one-hot recipe.

Mosaic status on the current installation (jax 0.9.0 / libtpu 0.0.34,
TPU v5e — chip_smoke.py kernels phase): ``gather_rows``,
``fused_pool_cvm_forward``, ``segment_gather_mxu``/``segment_sum_mxu``
compile and agree with the XLA compositions. Every block's last two
dims must be (8, 128)-aligned or span the array: a (1, D) row block is
refused, which is why ``gather_rows`` moves whole 8-row tiles.

Measured verdict of an EARLIER installation (post ISSUE 12; one TPU
chip, DeepFM/criteo bench, AoS table [8M+1, 16] f32, 213k rows/batch) —
rationale, not today's rates:
- XLA's native gather/scatter lowers to PER-ELEMENT access: scatter
  [213k, 16] rows = 26 ms (~7.6 ns/element), gather = 8 ms. The hints
  (unique_indices / indices_are_sorted / mode) change nothing.
- Manual per-row DMA is NOT viable on current Mosaic at any width:
  (a) D=16 rows cannot compile — every Mosaic memref (HBM included) is
  laid out with a 128-lane minor tile, so a 16-wide row slice is
  "unaligned" regardless of memory space; (b) at D=128 the scalar-core
  loop issues DMAs at ~320 µs each (2048 rows = 656 ms), ~1000x off.
  So no per-row DMA kernel exists, not even as an interpret-mode
  reference. Revisit only if Mosaic grows a batched gather/scatter DMA
  primitive or SparseCore access, and measure any such kernel against
  the MXU family below.
- The viable TPU formulation of the irregular hot path is the MXU
  one-hot matmul family below: ``segment_sum_mxu`` (pool forward),
  ``segment_gather_mxu`` (pool backward / ragged gather by
  nondecreasing ids), and ``fused_pool_cvm_forward`` (pool + CVM in
  one VMEM residency). The expand gather (``vals_u[gather_idx]``,
  UNSORTED ids) stays on XLA's clamped gather — the one-hot form is
  O(K·U·D) there and per-row DMA is ruled out above. Whether any of
  them beats its XLA composition is not measured on this installation
  (PERF.md section 7).

The first Pallas kernels with a benchmarked caller are not in this file:
``ops/ssd.py`` (PR 32), the forward and the hand-written backward sweep
of the Mamba layers' chunked scan, which cell 2 of the benchmark
(``nemotron3-nano-30b-a3b.train-packed-8k``) runs 24 times a step and
judges by ``kernels.ssm_scan_roofline``. They take this module's
``_interpret`` and ``_book_dispatch`` and have no flag: the shapes choose
between them and their XLA composition (PERF.md section 6, PR 32).
"""

from __future__ import annotations

import functools
import inspect
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.config import FLAGS


def _interpret() -> bool:
    """Interpret mode unless this process's devices are TPU chips, as
    ``jax.devices()[0].platform`` reports them — the one backend test
    every kernel seam derives from."""
    return jax.devices()[0].platform != "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _book_dispatch(kernel: str, impl: str) -> None:
    """Book one ``pbox_kernel_dispatch_total{kernel,impl}`` tick.

    Dispatch decisions are made at TRACE time (inside jit the python
    branch runs once per compiled executable), so the counter counts
    compiled-program dispatches, not per-batch executions — enough to
    prove which implementation a run's programs actually contain
    (docs/OBSERVABILITY.md, instrument catalog). Inert without an active
    hub."""
    try:
        from paddlebox_tpu.obs.hub import get_hub
        hub = get_hub()
        if hub.active:
            hub.counter(
                "pbox_kernel_dispatch_total",
                "device-kernel dispatch decisions by kernel and impl",
            ).inc(kernel=kernel, impl=impl)
    except Exception:  # pragma: no cover - telemetry must never break math
        pass


def mxu_dot(lhs, rhs, contract_l: int, contract_r: int):
    """A kernel's float32-accumulated product of two 2-D tiles; float32
    operands are multiplied as float32 (the tests' ``mm_dtype``), not in
    one bfloat16 pass. (``ops/ssd.py`` keeps its own copy of this and of
    ``scoped_sweep``: a Mosaic kernel's serialized body carries its ops'
    source locations, so moving them would change cell 2's program.)"""
    precision = (jax.lax.Precision.HIGHEST if lhs.dtype == jnp.float32
                 else None)
    return jax.lax.dot_general(
        lhs, rhs, (((contract_l,), (contract_r,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


def scoped_sweep(scope: str):
    """A kernel sweep as a model's layers share it. Jitted, so that every
    call at the same shapes (a layer's, the next layer's) is one trace of
    the kernel's body and one function of the lowered module: tracing a
    kernel is seconds of set-up in every process, cached programs or not.
    And every op of it under ``scope``, whatever stack the caller's
    transformation leaves it: a ``custom_vjp`` rule's ops carry the names
    the rule gives them, and the primal that ``optimize_remat`` puts in a
    checkpoint's forward pass carries none. The sweep's keyword-only
    arguments are static."""
    def wrap(rule):
        @functools.wraps(rule)
        def scoped(*args, **static):
            with jax.named_scope(scope):
                return rule(*args, **static)
        return jax.jit(scoped, static_argnames=tuple(
            name for name, par in inspect.signature(rule).parameters.items()
            if par.kind is par.KEYWORD_ONLY))
    return wrap


# ---------------------------------------------------------------------------
# Row gather (pull_sparse hot path)
# ---------------------------------------------------------------------------

#: rows per gather grid step — one sublane tile. Mosaic block shapes
#: must be (8, 128)-aligned in their last two dims (a (1, D) row block
#: is refused), so the gather moves whole 8-row tiles.
_GR = 8


def gather_rows(table: jax.Array, rows: jax.Array) -> jax.Array:
    """table [C, D], rows [U] int32 → [U, D].

    ``_GR`` rows per grid step: the row indices are scalar-prefetched,
    each of the step's ``_GR`` input specs maps to the aligned
    ``(_GR, D)`` tile holding one requested row (the pipeline issues
    the HBM→VMEM tile DMAs for step i+1 while step i copies out), and
    the kernel picks the row's sublane out of its tile.
    Out-of-bounds pad rows (> C-1, the OOB-pad contract of
    table._build_index / device_unique.dedup_rows) clamp to the sentinel
    row C-1, matching XLA's clamped-gather semantics.
    """
    c, d = table.shape
    u = rows.shape[0]
    u_pad = _round_up(max(u, 1), _GR)
    rows_p = jnp.zeros((u_pad,), jnp.int32).at[:u].set(
        jnp.minimum(rows.astype(jnp.int32), c - 1))

    def kernel(rows_ref, *refs):
        tiles, out_ref = refs[:_GR], refs[_GR]
        base = pl.program_id(0) * _GR
        for j in range(_GR):
            sub = rows_ref[base + j] % _GR
            out_ref[pl.ds(j, 1), :] = tiles[j][pl.ds(sub, 1), :]

    def tile_of(j):
        return lambda i, rows_ref: (rows_ref[i * _GR + j] // _GR, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(u_pad // _GR,),
        in_specs=[pl.BlockSpec((_GR, d), tile_of(j)) for j in range(_GR)],
        out_specs=pl.BlockSpec((_GR, d), lambda i, rows_ref: (i, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((u_pad, d), table.dtype),
        interpret=_interpret(),
    )(rows_p, *([table] * _GR))
    return out[:u]


# ---------------------------------------------------------------------------
# MXU segment-sum (fused_seqpool hot path)
# ---------------------------------------------------------------------------
#
# Block-sparse formulation: segments MUST be nondecreasing (batch builder
# emits segment ids ins*S+slot in key order, so this holds for every seqpool
# caller). A key block of TK keys then overlaps at most TK/TB+1 output
# blocks, so instead of the full (segments × keys) cross product the grid is
# a flat list of (output-block, key-block) pairs built host-side: per key
# block j, pairs i = start_block[j]..end_block[j] (clamped, padded to the
# static TK/TB+1 per block). Work is O(K·TB·D) on the MXU — independent of
# num_segments — vs the scatter-add's O(K·D) serialized irregular writes.

def _tiles(k: int, n: int, d: int):
    """Shared pair-grid tiling: (tb, tk, k_pad, s_pad, d_pad, nkb, ppb,
    n_pairs) for K keys × N segments × D features. One definition so
    the tk heuristic and padding rules cannot drift between the one-hot
    kernels."""
    tb = 128
    tk = min(512, max(128, _round_up(max(k, 1), 128)))
    k_pad = _round_up(max(k, 1), tk)
    s_pad = _round_up(max(n, 1), tb)
    d_pad = _round_up(d, 128)
    nkb = k_pad // tk
    ppb = tk // tb + 1
    return tb, tk, k_pad, s_pad, d_pad, nkb, ppb, nkb * ppb


def _pad_ids(ids: jax.Array, k_pad: int, n: int) -> jax.Array:
    """[K] ids → [k_pad] int32 with the −1 drop routing: pads and ids
    outside [0, n) all become the drop marker (the one-hot never
    matches −1)."""
    ii = ids.astype(jnp.int32)
    seg = jnp.full((k_pad,), -1, jnp.int32)
    return seg.at[:ii.shape[0]].set(
        jnp.where((ii < 0) | (ii >= n), -1, ii))


def show_clk_keep(values: jax.Array, show_coeff: float, clk_coeff: float,
                  threshold: float) -> jax.Array:
    """THE show/clk significance filter (QuantFilter :93-133), bool [K].
    Single definition shared by every seqpool keep-mask site."""
    show, clk = values[:, 0], values[:, 1]
    return ((show - clk) * show_coeff + clk * clk_coeff) >= threshold


def keep_or_ones(values: jax.Array, need_filter: bool, show_coeff: float,
                 clk_coeff: float, threshold: float) -> jax.Array:
    """bool [K] keep mask: the show/clk filter when requested, all-ones
    otherwise — the one idiom every need_filter-only seqpool site uses."""
    if need_filter:
        return show_clk_keep(values, show_coeff, clk_coeff, threshold)
    return jnp.ones((values.shape[0],), dtype=bool)


def _pair_grid(seg: jax.Array, nkb: int, tk: int, tb: int):
    """Host-side (traced, static shapes) pair construction shared by the
    one-hot matmul kernels: per key block j, pairs i =
    start_block[j]..end_block[j] (clamped, padded to the static
    ``tk // tb + 1`` per block). −1 drop markers may appear anywhere;
    only the valid entries must be nondecreasing.

    Returns ``(i_arr, first, last, valid, overflow)``: the output-block
    index per pair, whether the pair is the first/last visit of its
    output block (i_arr is monotone, so visits are contiguous — ``first``
    gates the zero-init, ``last`` gates in-VMEM epilogues), the pair
    validity mask, and the runtime overflow predicate (a key block
    spanning more output blocks than the static bound ⇒ the caller must
    branch to its XLA fallback — correctness is unconditional)."""
    ppb = tk // tb + 1
    n_pairs = nkb * ppb
    segs2 = seg.reshape(nkb, tk)
    valid_m = segs2 >= 0
    has_valid = valid_m.any(axis=1)
    first_seg = jnp.min(jnp.where(valid_m, segs2, jnp.iinfo(jnp.int32).max),
                        axis=1)
    last_seg = jnp.max(segs2, axis=1)         # nondecreasing ⇒ max = last
    start_b = jnp.where(has_valid, first_seg // tb, 0)
    end_b = jnp.where(has_valid, last_seg // tb, -1)
    # carry forward so all-pad blocks produce in-bounds, monotone i indices
    prev_end = jnp.maximum(jax.lax.cummax(end_b), 0)
    start_b = jnp.where(has_valid, start_b, prev_end)
    end_b = jnp.where(has_valid, end_b, prev_end)

    slot = jnp.arange(n_pairs, dtype=jnp.int32) % ppb
    jb = jnp.arange(n_pairs, dtype=jnp.int32) // ppb
    i_raw = start_b[jb] + slot
    i_arr = jnp.minimum(i_raw, end_b[jb])
    valid = (i_raw <= end_b[jb]) & has_valid[jb]
    edge = i_arr[1:] != i_arr[:-1]
    first = jnp.concatenate([jnp.ones((1,), bool), edge])
    last = jnp.concatenate([edge, jnp.ones((1,), bool)])
    overflow = jnp.any((end_b - start_b + 1) > ppb)
    return i_arr, first, last, valid, overflow


def _seg_sum_kernel(i_ref, first_ref, valid_ref, seg_ref, vals_ref, out_ref,
                    *, tb: int, tk: int):
    p = pl.program_id(0)

    @pl.when(first_ref[p] != 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(valid_ref[p] != 0)
    def _acc():
        base = i_ref[p] * tb
        # onehot[r, k] = 1 iff segments[k] == base + r (never true for -1)
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (tb, tk), 0) + base
        onehot = (row_ids == seg_ref[...]).astype(jnp.float32)
        out_ref[...] += jnp.dot(onehot, vals_ref[...],
                                preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.HIGHEST)


def _segment_sum_mxu_impl(values: jax.Array, segments: jax.Array,
                          num_segments: int) -> jax.Array:
    k, d = values.shape
    tb, tk, k_pad, s_pad, d_pad, nkb, ppb, n_pairs = \
        _tiles(k, num_segments, d)

    v = jnp.zeros((k_pad, d_pad), jnp.float32)
    v = v.at[:k, :d].set(values.astype(jnp.float32))
    # historical contract: ids here may legally equal num_segments-1's
    # discard bin, so only pads (not OOB) route to −1
    seg = jnp.full((k_pad,), -1, jnp.int32)
    seg = seg.at[:k].set(segments.astype(jnp.int32))

    # The static ppb bound holds only when segment occupancy is dense (the
    # CTR seqpool shape: num_segments ≈ B*S ≲ K). If any key block spans
    # more output blocks than ppb (sparse occupancy), branch to the XLA
    # scatter-add at runtime — correctness is unconditional.
    i_arr, first, _last, valid, overflow = _pair_grid(seg, nkb, tk, tb)

    def pallas_branch(_):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_pairs,),
            in_specs=[
                pl.BlockSpec((1, tk), lambda p, i_a, f, v_: (0, p // ppb)),
                pl.BlockSpec((tk, d_pad),
                             lambda p, i_a, f, v_: (p // ppb, 0)),
            ],
            out_specs=pl.BlockSpec(
                (tb, d_pad), lambda p, i_a, f, v_: (i_a[p], 0)),
        )
        out = pl.pallas_call(
            functools.partial(_seg_sum_kernel, tb=tb, tk=tk),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s_pad, d_pad), jnp.float32),
            interpret=_interpret(),
        )(i_arr, first.astype(jnp.int32), valid.astype(jnp.int32),
          seg.reshape(1, k_pad), v)
        # segment ranges with no keys map to output blocks no pair visits;
        # their buffers are uninitialized — mask them to zero.
        visited = jnp.zeros((s_pad // tb,), bool).at[i_arr].max(valid)
        return jnp.where(jnp.repeat(visited, tb)[:, None], out, 0.0)

    def xla_branch(_):
        safe = jnp.where(seg >= 0, seg, num_segments)
        out = jax.ops.segment_sum(v, safe, num_segments=num_segments + 1)
        return jnp.zeros((s_pad, d_pad), jnp.float32).at[
            :num_segments].set(out[:num_segments])

    out = jax.lax.cond(overflow, xla_branch, pallas_branch, None)
    return out[:num_segments, :d].astype(values.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def segment_sum_mxu(values: jax.Array, segments: jax.Array,
                    num_segments: int) -> jax.Array:
    """values [K, D], segments [K] int32 → [num_segments, D].
    Contract: −1 entries are dropped (allowed anywhere); the NON-negative
    entries must be nondecreasing in array order. See notes above."""
    return _segment_sum_mxu_impl(values, segments, num_segments)


def _seg_sum_fwd(values, segments, num_segments):
    out = _segment_sum_mxu_impl(values, segments, num_segments)
    vtoken = jnp.zeros((0,), values.dtype)  # carries primal dtype
    return out, (segments, vtoken)


def _seg_sum_bwd(num_segments, res, g):
    segments, vtoken = res
    # d/dvalues of a segment sum is a gather of the cotangent rows; under
    # the flag it runs as the transposed one-hot matmul on the MXU
    # (bitwise equal for in-contract ids — each output row receives
    # exactly one 1.0·src contribution)
    if FLAGS.use_pallas_seqpool:
        g_values = segment_gather_mxu(g, segments)
    else:
        safe = jnp.clip(segments, 0, num_segments - 1)
        g_values = jnp.where((segments >= 0)[:, None], g[safe], 0.0)
    return (g_values.astype(vtoken.dtype), None)


segment_sum_mxu.defvjp(_seg_sum_fwd, _seg_sum_bwd)


# ---------------------------------------------------------------------------
# MXU segment-gather (seqpool backward / transposed one-hot matmul)
# ---------------------------------------------------------------------------

def _seg_gather_kernel(i_ref, firstk_ref, valid_ref, seg_ref, src_ref,
                       out_ref, *, tb: int, tk: int):
    p = pl.program_id(0)

    @pl.when(firstk_ref[p] != 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(valid_ref[p] != 0)
    def _acc():
        base = i_ref[p] * tb
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (tb, tk), 0) + base
        onehot = (row_ids == seg_ref[...]).astype(jnp.float32)  # [tb, tk]
        # onehotᵀ @ src_block → each key row receives its segment's src
        # row exactly once (single 1.0 contribution — bitwise a gather)
        out_ref[...] += jax.lax.dot_general(
            onehot, src_ref[...],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)


def segment_gather_mxu(src: jax.Array, ids: jax.Array) -> jax.Array:
    """src [N, D], ids [K] int32 → out [K, D] with out[k] = src[ids[k]];
    ids outside [0, N) produce zero rows.

    The transposed one-hot formulation of the segment-sum backward (the
    ``FusedSeqpoolCVMGrad*`` gather): per (key-block, source-block) pair
    the kernel runs onehotᵀ @ src on the MXU instead of XLA's
    per-element gather. Contract mirrors ``segment_sum_mxu``: the
    in-range ids must be nondecreasing in array order (−1/OOB drop
    markers may appear anywhere). Exact — each output row is one
    1.0·src contribution plus exact zeros, so results match the XLA
    gather bitwise (modulo -0.0 + 0.0 = +0.0)."""
    k = ids.shape[0]
    n, d = src.shape
    tb, tk, k_pad, s_pad, d_pad, nkb, ppb, n_pairs = _tiles(k, n, d)

    seg = _pad_ids(ids, k_pad, n)
    s = jnp.zeros((s_pad, d_pad), jnp.float32)
    s = s.at[:n, :d].set(src.astype(jnp.float32))

    i_arr, _first, _last, valid, overflow = _pair_grid(seg, nkb, tk, tb)
    # the OUTPUT here is keyed by key block (p // ppb), whose pairs are
    # consecutive — init on each key block's first pair
    firstk = (jnp.arange(n_pairs, dtype=jnp.int32) % ppb) == 0

    def pallas_branch(_):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_pairs,),
            in_specs=[
                pl.BlockSpec((1, tk), lambda p, i_a, f, v_: (0, p // ppb)),
                pl.BlockSpec((tb, d_pad),
                             lambda p, i_a, f, v_: (i_a[p], 0)),
            ],
            out_specs=pl.BlockSpec(
                (tk, d_pad), lambda p, i_a, f, v_: (p // ppb, 0)),
        )
        return pl.pallas_call(
            functools.partial(_seg_gather_kernel, tb=tb, tk=tk),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((k_pad, d_pad), jnp.float32),
            interpret=_interpret(),
        )(i_arr, firstk.astype(jnp.int32), valid.astype(jnp.int32),
          seg.reshape(1, k_pad), s)

    def xla_branch(_):
        safe = jnp.clip(seg, 0, s_pad - 1)
        return jnp.where((seg >= 0)[:, None], s[safe], 0.0)

    out = jax.lax.cond(overflow, xla_branch, pallas_branch, None)
    return out[:k, :d].astype(src.dtype)


# ---------------------------------------------------------------------------
# Fused embed-pool-CVM (pull gather + fused_seqpool + CVM, one VMEM pass)
# ---------------------------------------------------------------------------
#
# The tentpole kernel (ISSUE 12 / ROADMAP item 1): per pair-grid step the
# Pallas pipeline DMAs one key-block of pulled embeddings HBM→VMEM
# (double-buffered, indices scalar-prefetched — the gather_rows idiom at
# block granularity), accumulates the keep-masked one-hot × values
# matmul on the MXU, and on the LAST visit of each output block applies
# the CVM log transform while the block is still VMEM-resident — the
# TPU shape of PaddleBox's pull_box_sparse → FusedSeqpoolKernel* →
# FusedCVMKernel* CUDA chain, with no intermediate HBM round-trip
# between pool and CVM and no per-element scatter anywhere.

#: static CVM epilogue modes (which head columns transform in-VMEM)
CVM_NONE = 0      # no transform (use_cvm=False; caller slices the head)
CVM_FULL = 1      # [log1p(show), log1p(clk)-log1p(show), embedx…]
CVM_SHOW = 2      # clk_filter head: [log1p(show), embedx…]
CVM_CONV = 3      # conv head: [log1p(show), log1p(clk), log1p(conv)-log1p(clk)]


def _cvm_transform_wide(pooled: jax.Array, cvm_mode: int) -> jax.Array:
    """Column-in-place CVM transform on a lane-padded pooled block
    (shared by the in-kernel epilogue, the XLA overflow branch and the
    empty-segment filler — one definition, identical math)."""
    if cvm_mode == CVM_NONE:
        return pooled
    c = jax.lax.broadcasted_iota(jnp.int32, pooled.shape, pooled.ndim - 1)
    l0 = jnp.log1p(pooled[..., 0:1])
    if cvm_mode == CVM_FULL:
        l1 = jnp.log1p(pooled[..., 1:2]) - l0
        return jnp.where(c == 0, l0, jnp.where(c == 1, l1, pooled))
    if cvm_mode == CVM_SHOW:
        return jnp.where(c == 0, l0, pooled)
    l1 = jnp.log1p(pooled[..., 1:2])
    l2 = jnp.log1p(pooled[..., 2:3]) - l1
    return jnp.where(c == 0, l0,
                     jnp.where(c == 1, l1, jnp.where(c == 2, l2, pooled)))


def _pool_cvm_kernel(i_ref, first_ref, last_ref, valid_ref, seg_ref,
                     keep_ref, vals_ref, out_ref, *, tb: int, tk: int,
                     cvm_mode: int, pad_value: float):
    p = pl.program_id(0)

    @pl.when(first_ref[p] != 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(valid_ref[p] != 0)
    def _acc():
        base = i_ref[p] * tb
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (tb, tk), 0) + base
        # keep folds into the one-hot (0/1 × 0/1 — exact), so filtered
        # keys drop inside the same matmul that pools
        onehot = (row_ids == seg_ref[...]).astype(jnp.float32) \
            * keep_ref[...]
        out_ref[...] += jnp.dot(onehot, vals_ref[...],
                                preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.HIGHEST)

    @pl.when(last_ref[p] != 0)
    def _epilogue():
        # the block's accumulation is complete (i_arr is monotone —
        # no later pair revisits it): apply pad_value + CVM before the
        # block leaves VMEM
        out_ref[...] = _cvm_transform_wide(out_ref[...] + pad_value,
                                           cvm_mode)


def fused_pool_cvm_forward(values: jax.Array, segments: jax.Array,
                           keep: Optional[jax.Array], batch_size: int,
                           num_slots: int, *, cvm_mode: int = CVM_FULL,
                           cvm_offset: int = 2, ets: int = 0,
                           pad_value: float = 0.0) -> jax.Array:
    """values [K, D] pulled embeddings, segments [K] (ins*S + slot,
    nondecreasing; pads may be ≥ B*S or −1), keep [K] optional 0/1 key
    mask → the CVM-transformed pooled output [B, S, D_out] in ONE fused
    pass (see section comment). ``ets`` (embed_thres_size) only affects
    the CVM_NONE output slice. Raw forward — no custom_vjp; callers
    (ops/seqpool_cvm dispatch seam, ``fused_embed_pool_cvm``) own the
    reference backward contract."""
    k, d = values.shape
    n = batch_size * num_slots
    tb, tk, k_pad, s_pad, d_pad, nkb, ppb, n_pairs = _tiles(k, n, d)

    v = jnp.zeros((k_pad, d_pad), jnp.float32)
    v = v.at[:k, :d].set(values.astype(jnp.float32))
    kp = jnp.zeros((k_pad,), jnp.float32)
    kp = kp.at[:k].set(jnp.ones((k,), jnp.float32) if keep is None
                       else keep.astype(jnp.float32))
    # batch pads (≥ B*S) route to the −1 drop marker: the fused output
    # has no extra discard bin
    seg = _pad_ids(segments, k_pad, n)

    i_arr, first, last, valid, overflow = _pair_grid(seg, nkb, tk, tb)

    def pallas_branch(_):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_pairs,),
            in_specs=[
                pl.BlockSpec((1, tk),
                             lambda p, i_a, f, l, v_: (0, p // ppb)),
                pl.BlockSpec((1, tk),
                             lambda p, i_a, f, l, v_: (0, p // ppb)),
                pl.BlockSpec((tk, d_pad),
                             lambda p, i_a, f, l, v_: (p // ppb, 0)),
            ],
            out_specs=pl.BlockSpec(
                (tb, d_pad), lambda p, i_a, f, l, v_: (i_a[p], 0)),
        )
        out = pl.pallas_call(
            functools.partial(_pool_cvm_kernel, tb=tb, tk=tk,
                              cvm_mode=cvm_mode, pad_value=pad_value),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s_pad, d_pad), jnp.float32),
            interpret=_interpret(),
        )(i_arr, first.astype(jnp.int32), last.astype(jnp.int32),
          valid.astype(jnp.int32), seg.reshape(1, k_pad),
          kp.reshape(1, k_pad), v)
        # output blocks no valid pair visits hold uninitialized (or
        # zero-only) buffers — fill with the CVM of an empty segment
        # (pad_value everywhere), the same value the XLA branch produces
        visited = jnp.zeros((s_pad // tb,), bool).at[i_arr].max(valid)
        empty = _cvm_transform_wide(
            jnp.full((1, d_pad), pad_value, jnp.float32), cvm_mode)
        return jnp.where(jnp.repeat(visited, tb)[:, None], out, empty)

    def xla_branch(_):
        vk = v * kp[:, None]
        safe = jnp.where(seg >= 0, seg, s_pad)
        pooled = jax.ops.segment_sum(vk, safe,
                                     num_segments=s_pad + 1)[:s_pad]
        return _cvm_transform_wide(pooled + pad_value, cvm_mode)

    buf = jax.lax.cond(overflow, xla_branch, pallas_branch, None)[:n]
    # static column slice per head mode (InferShape width contract)
    if cvm_mode == CVM_NONE:
        out = buf[:, cvm_offset + ets:d]
    elif cvm_mode == CVM_FULL:
        out = buf[:, :d] if cvm_offset == 2 else jnp.concatenate(
            [buf[:, :2], buf[:, cvm_offset:d]], axis=-1)
    elif cvm_mode == CVM_SHOW:
        out = jnp.concatenate([buf[:, 0:1], buf[:, cvm_offset:d]], axis=-1)
    else:  # CVM_CONV: 3-column head transformed in place, full width
        out = buf[:, :d]
    return out.reshape(batch_size, num_slots, -1).astype(values.dtype)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def fused_embed_pool_cvm(
    values: jax.Array,          # [K, D] pulled embeddings (D incl. cvm dims)
    segments: jax.Array,        # [K] int32 ins*S + slot; pads ≥ B*S or −1
    batch_show_clk: jax.Array,  # [B, cvm_offset] batch show/clk
    batch_size: int,
    num_slots: int,
    use_cvm: bool = True,
    cvm_offset: int = 2,
    pad_value: float = 0.0,
    need_filter: bool = False,
    show_coeff: float = 0.2,
    clk_coeff: float = 1.0,
    threshold: float = 0.96,
) -> jax.Array:
    """The STANDALONE custom_vjp form of the fused kernel pair: forward
    is ``fused_pool_cvm_forward`` (one VMEM pass), backward replicates
    FusedSeqpoolCVMGradKernelWithCVM — embedx dims broadcast the output
    grad to every surviving key via ``segment_gather_mxu`` (transposed
    one-hot matmul, no XLA per-element gather), the first ``cvm_offset``
    dims carry the batch show/clk values, filtered/pad keys zero.
    Covers the kk=1 attr subset of ``ops.fused_seqpool_cvm``.

    NOTE the production dispatch seam does NOT route through this
    wrapper: ``ops.seqpool_cvm._fwd``/``_bwd`` call
    ``fused_pool_cvm_forward`` / ``segment_gather_mxu`` directly under
    ``FLAGS.use_pallas_seqpool`` (their own custom_vjp already owns the
    full attr surface). Use this op for direct kernel composition and
    for gradient-contract tests; grads match the XLA composition
    bitwise given the same upstream cotangent (gated in
    tests/test_pallas_kernels.py)."""
    out, _ = _fused_epc_fwd(values, segments, batch_show_clk, batch_size,
                            num_slots, use_cvm, cvm_offset, pad_value,
                            need_filter, show_coeff, clk_coeff, threshold)
    return out


def _fused_epc_fwd(values, segments, batch_show_clk, batch_size, num_slots,
                   use_cvm, cvm_offset, pad_value, need_filter, show_coeff,
                   clk_coeff, threshold):
    keep = keep_or_ones(values, need_filter, show_coeff, clk_coeff,
                        threshold).astype(jnp.float32)
    out = fused_pool_cvm_forward(
        values, segments, keep, batch_size, num_slots,
        cvm_mode=CVM_FULL if use_cvm else CVM_NONE,
        cvm_offset=cvm_offset, pad_value=pad_value)
    vtoken = jnp.zeros((0, values.shape[1]), values.dtype)
    return out, (segments, keep, batch_show_clk, vtoken)


def _fused_epc_bwd(batch_size, num_slots, use_cvm, cvm_offset, pad_value,
                   need_filter, show_coeff, clk_coeff, threshold, res, g):
    segments, keep, batch_show_clk, vtoken = res
    d = vtoken.shape[1]
    n = batch_size * num_slots
    # the CVM_FULL forward head is always TWO transformed columns
    # (log1p(show), ctr) regardless of cvm_offset — cvm_offset only
    # sets how many input columns the head REPLACES, so the output
    # slice offset is 2 while the grad width stays d - cvm_offset
    n_head = 2 if use_cvm else 0
    w = d - cvm_offset
    embedx_g = g[..., n_head:].reshape(n, w)
    g_embedx = segment_gather_mxu(embedx_g, segments)          # [K, w]
    ins = jnp.minimum(jnp.clip(segments, 0) // num_slots, batch_size - 1)
    pad = (segments < 0) | (segments >= n)
    g_cvm = batch_show_clk[ins].astype(g_embedx.dtype)
    g_values = jnp.where(
        ((keep > 0) & ~pad)[:, None],
        jnp.concatenate([g_cvm, g_embedx], axis=-1),
        0.0,
    ).astype(vtoken.dtype)
    return (g_values, None, None)


fused_embed_pool_cvm.defvjp(_fused_epc_fwd, _fused_epc_bwd)


def segment_sum(values: jax.Array, segments: jax.Array,
                num_segments: int) -> jax.Array:
    """Backend dispatch: MXU kernel when enabled (requires nondecreasing
    segments — true for all seqpool callers), XLA scatter-add otherwise
    (flag: FLAGS.use_pallas_seqpool)."""
    if FLAGS.use_pallas_seqpool:
        _book_dispatch("segment_sum", "mxu")
        return segment_sum_mxu(values, segments, num_segments)
    _book_dispatch("segment_sum", "xla")
    return jax.ops.segment_sum(values, segments, num_segments=num_segments)
