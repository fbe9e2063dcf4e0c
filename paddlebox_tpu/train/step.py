"""The fused train step — pull → fwd → bwd → push → dense update → metrics,
one jit-compiled XLA program.

Reference hot loop: BoxPSWorker::TrainFiles (framework/boxps_worker.cc:1278)
runs the ProgramDesc op list per batch: pull_box_sparse →
fused_seqpool_cvm → dense net fwd/bwd → push_box_sparse, then metric add.
Here the entire loop body is ONE traced function: XLA fuses the gather,
segment ops, MXU matmuls, scatter update and AUC histogram into a single
device program with zero host round-trips; buffer donation makes the table
and optimizer states update in place.

The pooling+CVM inside is itself a dispatch seam: under
``FLAGS.use_pallas_seqpool`` the ``fused_seqpool_cvm`` call (and its
backward feeding the push) routes to the fused Pallas MXU kernel
(ops/pallas_kernels.fused_pool_cvm_forward / segment_gather_mxu —
docs/PERFORMANCE.md §Device kernels); the trivial-layout fast path
(``pool_segments is None``) keeps its free reshape either way.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from paddlebox_tpu.data.batch import SlotBatch
from paddlebox_tpu.metrics import AucState, auc_add_batch
from paddlebox_tpu.obs import trace
from paddlebox_tpu.ops import fused_seqpool_cvm
from paddlebox_tpu.ps.sgd import SparseSGDConfig
from paddlebox_tpu.ps.table import (PullIndex, TableState, apply_push,
                                    expand_pull, gather_full_rows,
                                    pull_values, push_chunks)


def pack_floats(dense: np.ndarray, label: np.ndarray, show: np.ndarray,
                clk: np.ndarray, dtype=np.float32) -> np.ndarray:
    """THE float-block wire layout, [B, Dd+3] = [dense | label, show, clk].
    Single definition shared by the streaming path (make_device_batch) and
    the resident-pass packer; unpacked only by ``unpack_floats``."""
    return np.concatenate(
        [dense.astype(np.float32, copy=False),
         np.stack([label, show, clk], axis=1)],
        axis=1).astype(dtype, copy=False)


def unpack_floats(floats: jax.Array):
    """(dense, label, show, clk) views of a pack_floats block (traced)."""
    floats = floats.astype(jnp.float32)  # no-op for f32, upcast bf16 wire
    return floats[:, :-3], floats[:, -3], floats[:, -2], floats[:, -1]


def quantize_floats(dense: np.ndarray, label: np.ndarray, show: np.ndarray,
                    clk: np.ndarray, valid: Optional[np.ndarray] = None):
    """Optional q8 float wire: dense features as per-column affine uint8
    (q = round((x - zp) / scale)), label/show/clk as raw uint8 — CTR dense
    features are counts/logs where 8-bit affine precision is ample, and
    the reference itself runs int8 dense paths (scaled_int8fc,
    fused_scale_int8_op.cu). ``valid`` (bool [B]) restricts the range
    stats to real rows — batch-padding rows (show == 0, zero-filled)
    must not widen the range and dilute real-feature precision; their
    encodings clip, which is fine because ins_w masks them everywhere.
    Returns (block u8 [B, D+3], qmeta f32 [2, D] = [scale; zp]) or None
    when the data doesn't fit the wire (non-finite dense, or
    label/show/clk outside exact-u8 range) — callers fall back to the
    bf16 wire."""
    d = dense.astype(np.float32, copy=False)
    lsc = np.stack([label, show, clk], axis=1)
    if not np.isfinite(d).all():
        return None
    if (lsc < 0).any() or (lsc > 255).any() or (lsc != np.rint(lsc)).any():
        return None
    stat = d if valid is None else d[valid]
    if stat.size == 0:
        stat = d[:1]
    # winsorized range: heavy-tailed count features are the norm in CTR
    # logs and a single extreme value must not collapse a whole column's
    # precision to one bucket for the pass — clip the range to the
    # [0.1, 99.9] percentiles when the tails are outlier-dominated
    # (values beyond the range saturate; bounded error instead of
    # unbounded precision loss)
    lo = stat.min(axis=0)
    hi = stat.max(axis=0)
    if stat.shape[0] >= 1000:
        p_lo, p_hi = np.percentile(stat, [0.1, 99.9], axis=0)
        wild = (hi - lo) > 4.0 * np.maximum(p_hi - p_lo, 1e-30)
        lo = np.where(wild, p_lo, lo)
        hi = np.where(wild, p_hi, hi)
    scale = (hi - lo) / 255.0
    scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
    q = np.clip(np.rint((d - lo[None, :]) / scale[None, :]), 0, 255)
    block = np.concatenate([q, lsc], axis=1).astype(np.uint8)
    qmeta = np.stack([scale, lo.astype(np.float32)])
    return block, qmeta


def dequantize_floats(block: jax.Array, qmeta: jax.Array):
    """(dense, label, show, clk) from a quantize_floats block (traced)."""
    f = block.astype(jnp.float32)
    dense = f[:, :-3] * qmeta[0][None, :] + qmeta[1][None, :]
    return dense, f[:, -3], f[:, -2], f[:, -1]


class DeviceBatch(NamedTuple):
    """Everything the device step consumes for one batch, packed into THREE
    host→device transfers (the PCIe round-trip is the real cost, not
    bytes — the reference packs per-slot tensors into single copies for the
    same reason, MiniBatchGpuPack data_feed.cu:1210). ``key_valid`` is not
    shipped at all: it's derived on device from the real-key count carried
    in ``ints_u``'s last element. Accessors below unpack inside the traced
    step, where slices are free."""

    ints_u: jax.Array   # int32 [U_pad + 2] = unique_rows ++ [num_keys, pad_segment]
    ints_k: jax.Array   # int32 [2, K_pad] = [gather_idx; segments], or
                        #       [1, K_pad] when segments are derivable
    floats: jax.Array   # f32 [B, Dd + 3] = [dense | label | show | clk]

    @property
    def unique_rows(self) -> jax.Array:
        return self.ints_u[:-2]

    @property
    def num_keys(self) -> jax.Array:
        return self.ints_u[-2]

    @property
    def num_unique(self) -> None:
        """No count: the host cut ``unique_rows`` to the distinct
        count's bucket already (``gather_full_rows``)."""
        return None

    @property
    def gather_idx(self) -> jax.Array:
        return self.ints_k[0]

    @property
    def segments(self) -> jax.Array:
        if self.ints_k.shape[0] == 2:
            return self.ints_k[1]
        # trivial layout (one key per slot per record): segment i == i for
        # real keys, pad bin for the tail
        k_pad = self.ints_k.shape[1]
        i = jnp.arange(k_pad, dtype=jnp.int32)
        return jnp.where(i < self.num_keys, i, self.ints_u[-1])

    @property
    def key_valid(self) -> jax.Array:
        k_pad = self.ints_k.shape[1]
        return (jnp.arange(k_pad, dtype=jnp.int32)
                < self.num_keys).astype(jnp.float32)

    @property
    def segments_trivial(self) -> bool:
        return self.ints_k.shape[0] == 1

    @property
    def pool_segments(self):
        """Segments for fused_seqpool_cvm — None declares the trivial
        layout (pool becomes a reshape; no TPU scatter)."""
        return None if self.segments_trivial else self.segments

    @property
    def dense(self) -> jax.Array:
        return unpack_floats(self.floats)[0]

    @property
    def label(self) -> jax.Array:
        return unpack_floats(self.floats)[1]

    @property
    def show(self) -> jax.Array:
        return unpack_floats(self.floats)[2]

    @property
    def clk(self) -> jax.Array:
        return unpack_floats(self.floats)[3]


def make_device_batch(batch: SlotBatch, idx: PullIndex,
                      floats: Optional[jax.Array] = None) -> DeviceBatch:
    """``floats`` reuses an already-staged float block (multi-mf class
    sub-batches share one — the step reads only class 0's copy, so the
    others must not re-pack and re-ship it)."""
    u_pad = idx.unique_rows.shape[0]
    ints_u = np.empty(u_pad + 2, np.int32)
    ints_u[:u_pad] = idx.unique_rows
    ints_u[u_pad] = batch.num_keys
    ints_u[u_pad + 1] = batch.pad_segment
    if getattr(batch, "segments_trivial", False):
        ints_k = np.ascontiguousarray(idx.gather_idx[None, :])
    else:
        ints_k = np.stack([idx.gather_idx, batch.segments.astype(np.int32)])
    if floats is None:
        floats = jnp.asarray(pack_floats(batch.dense, batch.label,
                                         batch.show, batch.clk))
    return DeviceBatch(ints_u=jnp.asarray(ints_u),
                       ints_k=jnp.asarray(ints_k),
                       floats=floats)


def ctr_forward(table: TableState, params: Any, model, batch,
                batch_size: int, num_slots: int, use_cvm: bool = True,
                cvm_offset: int = 2, need_filter: bool = False,
                quant_ratio: int = 0) -> Tuple[jax.Array, jax.Array]:
    """THE CTR inference path (pull → fused_seqpool_cvm → model →
    sigmoid), shared by the train step's eval and the serving loader so
    the seqpool constants live in exactly one place. Returns
    (pred [B], ins_w [B]) — ins_w masks batch-padding instances."""
    batch_show_clk = jnp.stack([batch.show, batch.clk], axis=1)
    vals_u = pull_values(gather_full_rows(table, batch.unique_rows),
                         table.mf_dim)
    values_k = expand_pull(vals_u, batch.gather_idx)
    segs = getattr(batch, "pool_segments", batch.segments)
    pooled = fused_seqpool_cvm(
        values_k, segs, batch_show_clk, batch_size, num_slots,
        use_cvm, cvm_offset, 0.0, need_filter, 0.2, 1.0, 0.96, quant_ratio,
        key_valid=batch.key_valid)
    logits = model.apply(params, pooled, batch.dense)
    ins_w = (batch.show > 0).astype(jnp.float32)
    return jax.nn.sigmoid(logits), ins_w


class StepState(NamedTuple):
    table: TableState
    params: Any
    opt_state: Any
    auc: AucState
    step: jax.Array  # int32 scalar


class TrainStep:
    """Builds and caches the jitted step for a (model, table cfg) pair.
    One compilation per (K_pad, U_pad) bucket combo."""

    def __init__(
        self,
        model,               # flax Module: (pooled, dense) -> logits [B]
        tx: optax.GradientTransformation,
        sgd_cfg: SparseSGDConfig,
        batch_size: int,
        num_slots: int,
        use_cvm: bool = True,
        cvm_offset: int = 2,
        need_filter: bool = False,
        quant_ratio: int = 0,
        rng_seed: int = 0,
    ) -> None:
        self.model = model
        self.tx = tx
        self.sgd_cfg = sgd_cfg
        self.batch_size = batch_size
        self.num_slots = num_slots
        self.use_cvm = use_cvm
        self.cvm_offset = cvm_offset
        self.need_filter = need_filter
        self.quant_ratio = quant_ratio
        self.rng = jax.random.PRNGKey(rng_seed)
        self._jit = jax.jit(self._step, donate_argnums=(0,))
        self._jit_eval = jax.jit(self._eval_step, donate_argnums=(2,))

    @staticmethod
    def init_params_for(model, batch_size: int, num_slots: int,
                        mf_dim: int, dense_dim: int, use_cvm: bool = True,
                        cvm_offset: int = 2) -> Any:
        """Deterministic dense-param init without a TrainStep (lr_map
        scale building needs the param pytree before the tx is final)."""
        d = cvm_offset + 1 + mf_dim if use_cvm else 1 + mf_dim
        pooled = jnp.zeros((batch_size, num_slots, d))
        dense = jnp.zeros((batch_size, dense_dim))
        return model.init(jax.random.PRNGKey(0), pooled, dense)

    def init_params(self, mf_dim: int, dense_dim: int) -> Any:
        return self.init_params_for(self.model, self.batch_size,
                                    self.num_slots, mf_dim, dense_dim,
                                    self.use_cvm, self.cvm_offset)

    def init_state(self, table_state: TableState, params: Any,
                   auc: AucState) -> StepState:
        return StepState(table=table_state, params=params,
                         opt_state=self.tx.init(params), auc=auc,
                         step=jnp.zeros((), jnp.int32))

    # ---- the traced step ----
    def _step(self, state: StepState, batch: DeviceBatch,
              rng: jax.Array) -> Tuple[StepState, Dict[str, jax.Array]]:
        # every op of the step sits under one pbox.* scope of
        # obs/trace's catalog (metadata only), so a profile names device
        # time by what the step does and obs/xplane reduces it
        scope = jax.named_scope
        b, s = self.batch_size, self.num_slots
        with scope(trace.SCOPE_POOL_CVM):
            batch_show_clk = jnp.stack([batch.show, batch.clk], axis=1)
        with scope(trace.SCOPE_LOSS):
            ins_w = (batch.show > 0).astype(jnp.float32)  # mask padding

        # a unique axis built on the device is as wide as the key axis
        # and says where its pads start: gather and push stop there
        num_unique = batch.num_unique
        # ONE gather serves both the pull values and the push optimizer
        # state (AoS rows — see TableState)
        with scope(trace.SCOPE_PULL):
            rows_full = gather_full_rows(state.table, batch.unique_rows,
                                         num_unique)
            vals_u = pull_values(rows_full, state.table.mf_dim)

        pool_segs = getattr(batch, "pool_segments", batch.segments)

        def loss_fn(params, vals_u):
            with scope(trace.SCOPE_PULL):
                values_k = expand_pull(vals_u, batch.gather_idx)
            with scope(trace.SCOPE_POOL_CVM):
                pooled = fused_seqpool_cvm(
                    values_k, pool_segs, batch_show_clk, b, s,
                    self.use_cvm, self.cvm_offset, 0.0, self.need_filter,
                    0.2, 1.0, 0.96, self.quant_ratio,
                    key_valid=batch.key_valid)
            with scope(trace.SCOPE_DENSE):
                logits = self.model.apply(params, pooled, batch.dense)
            with scope(trace.SCOPE_LOSS):
                ls = optax.sigmoid_binary_cross_entropy(logits,
                                                        batch.label)
                loss = jnp.sum(ls * ins_w) / jnp.maximum(jnp.sum(ins_w),
                                                         1.0)
            return loss, logits

        (loss, logits), (g_params, g_vals_u) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(state.params, vals_u)

        # sparse push: autodiff through expand_pull (a gather) already
        # occurrence-merged the per-key grads into per-unique-row grads —
        # g_vals_u[:, 0] is Σ show over occurrences, etc. (the
        # PushMergeCopy/DedupKeys contract for free). Embed grads are scaled
        # by -batch_size as in PushCopy (box_wrapper.cu:368-372: the in-table
        # adagrad ADDS ratio*g/g_show, so push carries the negated sum-grad).
        with scope(trace.SCOPE_PUSH):
            g_vals_u = jnp.concatenate(
                [g_vals_u[:, :2], g_vals_u[:, 2:] * (-1.0 * b)], axis=1)
            # touched derives from the dup-free unique_rows contract
            # inside apply_push; slot is host metadata
            # (EmbeddingTable.slot_host) — no segment op spent on either
            table = apply_push(state.table, batch.unique_rows, g_vals_u,
                               self.sgd_cfg, rng, rows_full=rows_full,
                               num_unique=num_unique)

        with scope(trace.SCOPE_DENSE_OPT):
            updates, opt_state = self.tx.update(g_params, state.opt_state,
                                                state.params)
            params = optax.apply_updates(state.params, updates)

        with scope(trace.SCOPE_AUC):
            pred = jax.nn.sigmoid(logits)
            auc = auc_add_batch(state.auc, pred, batch.label, ins_w)

        new_state = StepState(table=table, params=params,
                              opt_state=opt_state, auc=auc,
                              step=state.step + 1)
        stats = {"loss": loss,
                 "pred_mean": jnp.sum(pred * ins_w) /
                 jnp.maximum(jnp.sum(ins_w), 1.0),
                 # per-instance preds for the dump subsystem; stays on
                 # device unless a DumpWriter fetches it
                 "pred": pred}
        if num_unique is not None:
            # the engagement counter: trips the push's loop made
            stats["push_chunks"] = push_chunks(
                batch.unique_rows.shape[0], num_unique)
        return new_state, stats

    def _forward(self, table: TableState, params: Any,
                 batch: DeviceBatch) -> Tuple[jax.Array, jax.Array]:
        """Shared inference path: pull → seqpool_cvm → model → pred."""
        return ctr_forward(table, params, self.model, batch,
                           self.batch_size, self.num_slots, self.use_cvm,
                           self.cvm_offset, self.need_filter,
                           self.quant_ratio)

    def _eval_step(self, table: TableState, params: Any, auc: AucState,
                   batch: DeviceBatch) -> Tuple[AucState, jax.Array]:
        """Forward-only pass: metrics accumulate, nothing trains
        (test_program / infer phase of the reference workers). Returns
        (auc, pred) — pred feeds the metric registry."""
        pred, ins_w = self._forward(table, params, batch)
        return auc_add_batch(auc, pred, batch.label, ins_w), pred

    def eval(self, table: TableState, params: Any, auc: AucState,
             batch: DeviceBatch) -> Tuple[AucState, jax.Array]:
        return self._jit_eval(table, params, auc, batch)

    def __call__(self, state: StepState, batch: DeviceBatch,
                 rng: jax.Array) -> Tuple[StepState, Dict[str, jax.Array]]:
        return self._jit(state, batch, rng)


class SeqTrainStep:
    """The fused train step of a SEQUENCE model (``model.sequence_model``:
    ``loss(params, vectors [S, T, D], labels [S, T], valid [S, T]) ->
    (loss, {name: scalar})``, the names those of ``model.step_scalars``),
    with ``TrainStep``'s state and call: pull -> net -> per-position loss
    -> push -> dense update, one traced function.

    What differs from the click step: a record is one position of a
    sequence and its one key is the token there. The slot is pulled
    UNPOOLED: position t of sequence s reads the vector (``embedx_w``) of
    its token's row, [S, T, mf_dim], no ``fused_seqpool_cvm`` and no CVM
    columns. The label is an integer id a position (the next token) and
    the loss a mean cross-entropy over the step's positions; there is no
    AUC. The push carries, a row, its occurrences (show) and the summed
    vector gradient times -positions, as the click step's does
    (``apply_push`` and the in-row rule are shared).

    It runs inside the resident pass program only
    (``ResidentPassRunner`` calls ``_step``): there is no streaming form.
    ``step_scalars`` names the stats that ``ResidentPassRunner`` hands
    out for EVERY step of a pass: the loss (a pass-long mean loss is the
    wrong number to hold against a reference, PERF.md PR 28) and then
    whatever the model names."""

    def __init__(self, model, tx: optax.GradientTransformation,
                 sgd_cfg: SparseSGDConfig, batch_size: int,
                 seq_len: int) -> None:
        if seq_len <= 0 or batch_size % seq_len:
            raise ValueError(f"a step is whole sequences: batch "
                             f"{batch_size}, seq_len {seq_len}")
        self.model = model
        self.tx = tx
        self.sgd_cfg = sgd_cfg
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.num_slots = 1
        self.step_scalars = ("loss",) + tuple(
            getattr(model, "step_scalars", ()))

    def init_params(self, mf_dim: int, dense_dim: int) -> Any:
        del mf_dim, dense_dim   # the model knows its own widths
        return self.model.init(jax.random.PRNGKey(0))

    init_state = TrainStep.init_state

    def _step(self, state: StepState, batch,
              rng: jax.Array) -> Tuple[StepState, Dict[str, jax.Array]]:
        scope = jax.named_scope
        b, t = self.batch_size, self.seq_len
        if batch.gather_idx.shape[0] != b:
            raise ValueError("a sequence step takes one key a position: "
                             f"{batch.gather_idx.shape[0]} keys, {b} "
                             f"positions")
        mf = state.table.mf_dim
        num_unique = batch.num_unique
        with scope(trace.SCOPE_PULL):
            rows_full = gather_full_rows(state.table, batch.unique_rows,
                                         num_unique)
            vecs_u = pull_values(rows_full, mf)[:, 3:]           # [U, mf]
        with scope(trace.SCOPE_LOSS):
            valid = (batch.show > 0).reshape(b // t, t)
            labels = jnp.maximum(batch.label.astype(jnp.int32),
                                 0).reshape(b // t, t)

        def loss_fn(params, vecs_u):
            with scope(trace.SCOPE_PULL):
                emb = vecs_u[batch.gather_idx].reshape(b // t, t, mf)
            return self.model.loss(params, emb, labels, valid)

        (loss, scalars), (g_params, g_vecs_u) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(state.params, vecs_u)

        with scope(trace.SCOPE_PUSH):
            u = vecs_u.shape[0]
            g_show = jax.ops.segment_sum(batch.show * batch.key_valid,
                                         batch.gather_idx, num_segments=u)
            zero = jnp.zeros((u, 2), jnp.float32)   # no click, no embed_w
            g_vals_u = jnp.concatenate(
                [g_show[:, None], zero, g_vecs_u * (-1.0 * b)], axis=1)
            table = apply_push(state.table, batch.unique_rows, g_vals_u,
                               self.sgd_cfg, rng, rows_full=rows_full,
                               num_unique=num_unique)

        with scope(trace.SCOPE_DENSE_OPT):
            updates, opt_state = self.tx.update(g_params, state.opt_state,
                                                state.params)
            params = optax.apply_updates(state.params, updates)

        new_state = StepState(table=table, params=params,
                              opt_state=opt_state, auc=state.auc,
                              step=state.step + 1)
        stats = dict(scalars, loss=loss)
        if num_unique is not None:
            stats["push_chunks"] = push_chunks(
                batch.unique_rows.shape[0], num_unique)
        return new_state, stats
