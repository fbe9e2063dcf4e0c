"""Fused train step + trainer for multi_mf (per-slot embedding dims).

One jit step per batch, same shape as train/step.py's TrainStep but with
C dim classes: per class pull → fused_seqpool_cvm over the class's slots,
then the pooled blocks concatenate in CANONICAL slot order (the
pull_gpups_sparse + seqpool + concat contract with per-slot widths,
feature_value.h:42-185 / ps_gpu_wrapper.cc multi-mf build) before the
dense model; the backward push applies per class table. Gather/scatter on
TPU costs per index, so the class split adds no device cost beyond C
small dispatch chains inside one XLA program. Each class's
``fused_seqpool_cvm`` (forward and push-feeding backward) rides the
``FLAGS.use_pallas_seqpool`` seam onto the fused Pallas MXU kernel
(docs/PERFORMANCE.md §Device kernels)."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from paddlebox_tpu.config import FLAGS
from paddlebox_tpu.metrics import auc_compute, init_auc_state
from paddlebox_tpu.obs import trace
from paddlebox_tpu.ops import fused_seqpool_cvm
from paddlebox_tpu.ps.multi_mf import MultiMfEmbeddingTable
from paddlebox_tpu.ps.table import (apply_push, expand_pull,
                                    gather_full_rows, pull_values)
from paddlebox_tpu.train.step import StepState, make_device_batch
from paddlebox_tpu.metrics import auc_add_batch
from paddlebox_tpu.utils.logging import get_logger
from paddlebox_tpu.utils.timer import Timer

log = get_logger(__name__)


class MultiMfTrainStep:
    """Jitted multi-class CTR step over a MultiMfEmbeddingTable."""

    def __init__(self, model, tx: optax.GradientTransformation,
                 table: MultiMfEmbeddingTable, batch_size: int,
                 use_cvm: bool = True, cvm_offset: int = 2,
                 rng_seed: int = 0) -> None:
        self.model = model
        self.tx = tx
        self.table = table
        self.batch_size = batch_size
        self.use_cvm = use_cvm
        self.cvm_offset = cvm_offset
        self.rng = jax.random.PRNGKey(rng_seed)
        self.class_slots = [len(s) for s in table.class_slots]
        self.dims = table.dims
        # canonical reassembly order: (class, rank) per global slot
        self.slot_route = table.slot_route()
        self._jit = jax.jit(self._step, donate_argnums=(0,))

    def init_params(self, dense_dim: int) -> Any:
        width = self.table.pooled_width(self.cvm_offset, self.use_cvm)
        flat = jnp.zeros((self.batch_size, width))
        dense = jnp.zeros((self.batch_size, dense_dim))
        return self.model.init(jax.random.PRNGKey(0), flat, dense)

    def init_state(self, params: Any) -> StepState:
        return StepState(
            table=tuple(t.state for t in self.table.tables),
            params=params, opt_state=self.tx.init(params),
            auc=init_auc_state(), step=jnp.zeros((), jnp.int32))

    # ---- traced ----
    def _pooled(self, vals_list, devs, batch_show_clk):
        parts = []
        for c, dev in enumerate(devs):
            values_k = expand_pull(vals_list[c], dev.gather_idx)
            parts.append(fused_seqpool_cvm(
                values_k, dev.segments, batch_show_clk,
                self.batch_size, self.class_slots[c],
                self.use_cvm, self.cvm_offset))
        # canonical slot order with per-slot widths
        flat = [parts[c][:, r, :] for c, r in self.slot_route]
        return jnp.concatenate(flat, axis=1)

    def _step(self, state: StepState, devs, rng
              ) -> Tuple[StepState, Dict[str, jax.Array]]:
        d0 = devs[0]
        batch_show_clk = jnp.stack([d0.show, d0.clk], axis=1)
        ins_w = (d0.show > 0).astype(jnp.float32)
        rows_fulls = [gather_full_rows(t, dev.unique_rows)
                      for t, dev in zip(state.table, devs)]
        vals_list = [pull_values(rf, t.mf_dim)
                     for rf, t in zip(rows_fulls, state.table)]

        def loss_fn(params, vals_list):
            x = self._pooled(vals_list, devs, batch_show_clk)
            logits = self.model.apply(params, x, d0.dense)
            ls = optax.sigmoid_binary_cross_entropy(logits, d0.label)
            loss = jnp.sum(ls * ins_w) / jnp.maximum(jnp.sum(ins_w), 1.0)
            return loss, logits

        (loss, logits), (g_params, g_vals) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(state.params, vals_list)

        new_tables = []
        for c, (t, dev, rf, g) in enumerate(
                zip(state.table, devs, rows_fulls, g_vals)):
            g = jnp.concatenate(
                [g[:, :2], g[:, 2:] * (-1.0 * self.batch_size)], axis=1)
            new_tables.append(apply_push(
                t, dev.unique_rows, g, self.table.tables[c].cfg,
                jax.random.fold_in(rng, c), rows_full=rf))

        updates, opt_state = self.tx.update(g_params, state.opt_state,
                                            state.params)
        params = optax.apply_updates(state.params, updates)
        pred = jax.nn.sigmoid(logits)
        auc = auc_add_batch(state.auc, pred, d0.label, ins_w)
        return StepState(table=tuple(new_tables), params=params,
                         opt_state=opt_state, auc=auc,
                         step=state.step + 1), \
            {"loss": loss, "pred": pred}

    def __call__(self, state, devs, rng):
        return self._jit(state, devs, rng)

    # ---- resident pass runner (whole pass as one fori_loop) ----
    def run_resident(self, state, rp: "MultiMfResidentPass", rng):
        cache = getattr(self, "_resident_cache", None)
        if cache is None:
            cache = self._resident_cache = {}
        nb = rp.num_batches
        if nb not in cache:
            cache[nb] = _mmf_resident_runner(self, nb)
        class_wires, floats = rp.dev
        return cache[nb](state, class_wires, floats,
                         jnp.zeros((), jnp.int32), rng)


class MultiMfTrainer:
    """Streaming trainer over a MultiMfEmbeddingTable (the BoxPSTrainer
    role for mixed-dim tables). Same pass contract as train.Trainer."""

    def __init__(self, model, table: MultiMfEmbeddingTable, desc,
                 tx=None, use_cvm: bool = True, seed: int = 0,
                 prefetch: int = 4) -> None:
        self.table = table
        self.desc = desc
        self.tx = tx or optax.adam(1e-3)
        self.step_fn = MultiMfTrainStep(model, self.tx, table,
                                        desc.batch_size, use_cvm=use_cvm,
                                        rng_seed=seed)
        self.state = self.step_fn.init_state(
            self.step_fn.init_params(desc.dense_dim))
        self._rng = jax.random.PRNGKey(seed + 1)
        self.global_step = 0
        self.prefetch = prefetch

    def train_pass(self, dataset, log_prefix: str = "") -> Dict[str, float]:
        from paddlebox_tpu.utils.prefetch import prefetch_iter

        def do_prep(b):
            cbs = self.table.prepare(b)
            devs = []
            for cb in cbs:
                devs.append(make_device_batch(
                    cb.batch, cb.index,
                    floats=devs[0].floats if devs else None))
            return b, tuple(devs)

        timer = Timer()
        timer.start()
        nb = 0
        n_ex = 0
        stats = None
        for batch, devs in prefetch_iter(dataset.batches(), do_prep,
                                         capacity=self.prefetch):
            n_ex += int((batch.show > 0).sum())
            self.global_step += 1
            rng = jax.random.fold_in(self._rng, self.global_step)
            self.state, stats = self.step_fn(self.state, devs, rng)
            nb += 1
            if FLAGS.check_nan_inf:
                loss = float(stats["loss"])
                if math.isnan(loss) or math.isinf(loss):
                    raise RuntimeError(
                        f"nan/inf loss at step {self.global_step}")
        timer.pause()
        self.sync_table()
        res = auc_compute(self.state.auc)
        out = res.as_dict()
        out.update(batches=nb, elapsed_sec=timer.elapsed_sec(),
                   examples_per_sec=n_ex / max(timer.elapsed_sec(), 1e-9))
        log.info("%smulti-mf pass done: %d batches, %.0f ex/s, auc=%.4f",
                 log_prefix, nb, out["examples_per_sec"], res.auc)
        return out

    def reset_metrics(self) -> None:
        self.state = self.state._replace(auc=init_auc_state())

    def sync_table(self) -> None:
        for t, st in zip(self.table.tables, self.state.table):
            t.state = st

    # ---- device-resident pass (BeginPass staging, multi-mf flavor) ----
    def build_resident_pass(self, dataset) -> "MultiMfResidentPass":
        return MultiMfResidentPass.build(dataset, self.table)

    def train_pass_resident(self, pass_or_dataset,
                            log_prefix: str = "") -> Dict[str, float]:
        """The whole pass staged to HBM and run as ONE lax.fori_loop —
        per-step host work and H2D hops are zero (the multi-mf analogue
        of Trainer.train_pass_resident)."""
        rp = (pass_or_dataset
              if isinstance(pass_or_dataset, MultiMfResidentPass)
              else self.build_resident_pass(pass_or_dataset))
        timer = Timer()
        timer.start()
        rp.upload()
        self.state = self.step_fn.run_resident(self.state, rp, self._rng)
        jax.block_until_ready(self.state.step)
        rp.mark_trained_rows(self.table)
        self.global_step += rp.num_batches
        timer.pause()
        self.sync_table()
        res = auc_compute(self.state.auc)
        out = res.as_dict()
        out.update(batches=rp.num_batches, elapsed_sec=timer.elapsed_sec(),
                   examples_per_sec=rp.num_records /
                   max(timer.elapsed_sec(), 1e-9))
        log.info("%smulti-mf resident pass: %d batches, %.0f ex/s, "
                 "auc=%.4f", log_prefix, rp.num_batches,
                 out["examples_per_sec"], res.auc)
        return out


class MultiMfResidentPass:
    """One pass's per-class DeviceBatch streams stacked on a leading step
    axis: per class ``ints_u [nb, U_c+2]`` and ``ints_k [nb, r, K_c]``,
    plus ONE shared float block ``[nb, B, Dd+3]`` (class sub-batches
    share their floats, as in the streaming path)."""

    def __init__(self, class_ints, floats: np.ndarray,
                 num_records: int) -> None:
        self.class_ints = class_ints      # [(iu, ik)] per class, host
        self.floats = floats
        self.num_records = num_records
        self.dev = None

    @property
    def num_batches(self) -> int:
        return self.floats.shape[0]

    @classmethod
    def build(cls, dataset, table: MultiMfEmbeddingTable
              ) -> "MultiMfResidentPass":
        from paddlebox_tpu.ps.table import fill_oob_pads
        from paddlebox_tpu.train.step import pack_floats
        per_class: List[List] = [[] for _ in range(table.num_classes)]
        floats = []
        n_rec = 0
        for b in dataset.batches():
            n_rec += int((b.show > 0).sum())
            floats.append(pack_floats(b.dense, b.label, b.show, b.clk))
            for c, cb in enumerate(table.prepare(b)):
                per_class[c].append(cb)
        if not floats:
            raise ValueError("empty pass")
        nb = len(floats)
        class_ints = []
        for c, cbs in enumerate(per_class):
            cap = table.tables[c].capacity
            u_max = max(cb.index.unique_rows.shape[0] for cb in cbs)
            k_max = max(cb.index.gather_idx.shape[0] for cb in cbs)
            trivial = all(cb.batch.segments_trivial for cb in cbs)
            iu = np.empty((nb, u_max + 2), np.int32)
            ik = np.empty((nb, 1 if trivial else 2, k_max), np.int32)
            for i, cb in enumerate(cbs):
                idx, sb = cb.index, cb.batch
                u = idx.num_unique
                iu[i, :idx.unique_rows.shape[0]] = idx.unique_rows
                fill_oob_pads(iu[i, :u_max], u, cap)
                iu[i, u_max] = sb.num_keys
                iu[i, u_max + 1] = sb.pad_segment
                ik[i, 0, :idx.gather_idx.shape[0]] = idx.gather_idx
                ik[i, 0, idx.gather_idx.shape[0]:] = u
                if not trivial:
                    k = min(sb.segments.shape[0], k_max)
                    ik[i, 1, :k] = sb.segments[:k]
                    ik[i, 1, k:] = sb.pad_segment
            class_ints.append((iu, ik))
        return cls(class_ints, np.stack(floats), n_rec)

    def upload(self) -> None:
        if self.dev is not None:
            return
        import jax.numpy as _jnp
        self.dev = (
            tuple((jax.device_put(_jnp.asarray(iu)),
                   jax.device_put(_jnp.asarray(ik)))
                  for iu, ik in self.class_ints),
            jax.device_put(_jnp.asarray(self.floats)))

    def mark_trained_rows(self, table: MultiMfEmbeddingTable) -> None:
        """Re-mark this pass's rows touched AFTER training: a delta save
        landing between build (prepare marks at build time) and training
        clears the flags and would otherwise drop the pass's updates from
        the next delta (the ResidentPass.mark_trained_rows rationale)."""
        with trace.span("pass.mark_trained",
                        rows=int(sum(iu[:, :-2].size
                                     for iu, _ik in self.class_ints))):
            for c, (iu, _ik) in enumerate(self.class_ints):
                t = table.tables[c]
                rows = np.unique(iu[:, :-2])  # last 2 cols = meta
                rows = rows[(rows >= 0) & (rows < t.capacity)]
                with t.host_lock:
                    t._touched[rows] = True


def _mmf_resident_runner(step: MultiMfTrainStep, n_steps: int):
    from paddlebox_tpu.train.step import DeviceBatch

    def run(state, class_wires, floats, start, rng):
        def body(i, carry):
            st, r = carry
            devs = tuple(
                DeviceBatch(ints_u=iu[i], ints_k=ik[i], floats=floats[i])
                for iu, ik in class_wires)
            st, _ = step._step(st, devs,
                               jax.random.fold_in(r, st.step + 1))
            return st, r

        state, _ = jax.lax.fori_loop(start, start + n_steps, body,
                                     (state, rng))
        return state

    return jax.jit(run, donate_argnums=(0,))
