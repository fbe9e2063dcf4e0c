"""Training metrics: bucketed AUC + error stats, cross-device reducible.

Reference: paddle/fluid/framework/fleet/metrics.{h,cc} —
``BasicAucCalculator`` (metrics.h:46): 1e6-bucket pos/neg tables keyed by
``int(pred * table_size)``, cross-worker allreduce_sum of the tables before
computing AUC/actual_ctr/predicted_ctr/MAE/RMSE (metrics.cc:288-304);
``Metric``/``MetricMsg`` name registry with phase filtering (metrics.h:198).

TPU-native redesign: the bucket tables are device arrays updated with one
``segment_sum`` per batch inside the jit train step (no host sync in the hot
loop); multi-chip reduction is a ``psum`` over the data axis (or host-side
np.sum over per-shard states) instead of MPI/Gloo allreduce. Final compute
is host numpy on the tiny [2, nbins] pull.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.config import FLAGS
from paddlebox_tpu.utils.logging import get_logger

log = get_logger(__name__)


class AucState(NamedTuple):
    pos: jax.Array        # f32 [nbins]
    neg: jax.Array        # f32 [nbins]
    abs_err: jax.Array    # f32 scalar
    sqr_err: jax.Array    # f32 scalar
    pred_sum: jax.Array   # f32 scalar
    label_sum: jax.Array  # f32 scalar
    ins_num: jax.Array    # f32 scalar


def init_auc_state(nbins: Optional[int] = None) -> AucState:
    n = nbins or FLAGS.auc_num_buckets
    # distinct buffers per field: StepState is donated in the jit step and
    # aliased leaves would be donated twice
    return AucState(jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32),
                    *(jnp.zeros((), jnp.float32) for _ in range(5)))


def auc_add_batch(state: AucState, pred: jax.Array, label: jax.Array,
                  weight: jax.Array) -> AucState:
    """Jittable accumulate (BasicAucCalculator::add_data, metrics.h:68).
    ``weight`` masks padding instances (0) and can carry show weights."""
    n = state.pos.shape[0]
    b = jnp.clip((pred * n).astype(jnp.int32), 0, n - 1)
    w = weight.astype(jnp.float32)
    lw = label.astype(jnp.float32) * w
    # ONE histogram scatter for both tables (TPU scatters carry a large
    # fixed per-call cost — measured ~20ms/call on v5p regardless of
    # update count): pos buckets at [0, n), neg at [n, 2n)
    both = jax.ops.segment_sum(
        jnp.concatenate([lw, w - lw]),
        jnp.concatenate([b, b + n]), num_segments=2 * n)
    pos = state.pos + both[:n]
    neg = state.neg + both[n:]
    err = (pred - label) * w
    return AucState(
        pos=pos, neg=neg,
        abs_err=state.abs_err + jnp.sum(jnp.abs(err)),
        sqr_err=state.sqr_err + jnp.sum(err * err),
        pred_sum=state.pred_sum + jnp.sum(pred * w),
        label_sum=state.label_sum + jnp.sum(label.astype(jnp.float32) * w),
        ins_num=state.ins_num + jnp.sum(w),
    )


def auc_compute_global(state: AucState, collective) -> AucResult:
    """Cross-worker AUC (BasicAucCalculator's MPI reduce,
    metrics.cc:288-304): allreduce the bucket tables and scalar error
    sums over the host collective (distributed.collective.TcpCollective)
    and compute ONE global AUC, identical on every rank. Uses the f64
    host compute path regardless of FLAGS.auc_device_reduce."""
    host = [np.asarray(jax.device_get(x)) for x in state]
    reduced = collective.allreduce_sum(host)
    return auc_compute(AucState(*reduced))


@dataclasses.dataclass
class AucResult:
    auc: float
    actual_ctr: float
    predicted_ctr: float
    mae: float
    rmse: float
    ins_num: float

    def as_dict(self) -> Dict[str, float]:
        return dataclasses.asdict(self)


@jax.jit
def _auc_reduce(state: AucState) -> jax.Array:
    """On-device scalar reduction of the bucket tables → [8] vector
    [area, tot_pos, tot_neg, abs_err, sqr_err, pred_sum, label_sum,
    ins_num]. XLA's tree reductions/scans keep f32 error ~log2(nbins)·eps,
    so AUC agrees with the f64 host path to ~1e-5."""
    pos, neg = state.pos, state.neg
    cum_neg_below = jnp.cumsum(neg) - neg
    area = jnp.sum(pos * (cum_neg_below + 0.5 * neg))
    return jnp.stack([area, jnp.sum(pos), jnp.sum(neg), state.abs_err,
                      state.sqr_err, state.pred_sum, state.label_sum,
                      state.ins_num])


def auc_compute(state: AucState) -> AucResult:
    """Final compute (BasicAucCalculator::compute, metrics.cc: bucket scan
    → area / (pos_total * neg_total)). Default = exact f64 host compute
    (pulls the full tables). Set FLAGS.auc_device_reduce=True to reduce on
    device and fetch 8 scalars instead (~1e-5 AUC drift in f32)."""
    if FLAGS.auc_device_reduce and isinstance(state.pos, jax.Array):
        (area, tot_pos, tot_neg, abs_err, sqr_err, pred_sum, label_sum,
         ins) = (float(x) for x in np.asarray(
             jax.device_get(_auc_reduce(state)), np.float64))
        auc = area / (tot_pos * tot_neg) if tot_pos > 0 and tot_neg > 0 \
            else 0.5
        ins_safe = max(ins, 1e-12)
        return AucResult(
            auc=auc, actual_ctr=label_sum / ins_safe,
            predicted_ctr=pred_sum / ins_safe, mae=abs_err / ins_safe,
            rmse=float(np.sqrt(sqr_err / ins_safe)), ins_num=ins)
    # ONE batched pull for all 7 leaves — per-leaf device_get costs a
    # host round-trip EACH
    h = AucState(*jax.device_get(tuple(state)))
    pos = np.asarray(h.pos, np.float64)
    neg = np.asarray(h.neg, np.float64)
    tot_pos, tot_neg = pos.sum(), neg.sum()
    cum_neg_below = np.concatenate([[0.0], np.cumsum(neg)[:-1]])
    # P(pos-bucket > neg-bucket) + 0.5 P(tie), summed per bucket
    area = np.sum(pos * (cum_neg_below + 0.5 * neg))
    auc = float(area / (tot_pos * tot_neg)) if tot_pos > 0 and tot_neg > 0 else 0.5
    ins = float(h.ins_num)
    ins_safe = max(ins, 1e-12)
    return AucResult(
        auc=auc,
        actual_ctr=float(h.label_sum) / ins_safe,
        predicted_ctr=float(h.pred_sum) / ins_safe,
        mae=float(h.abs_err) / ins_safe,
        rmse=float(np.sqrt(float(h.sqr_err) / ins_safe)),
        ins_num=ins,
    )


def auc_merge(states: Tuple[AucState, ...]) -> AucState:
    """Cross-worker table reduce (metrics.cc:288-304) — host-side merge of
    per-worker states (the in-jit path uses psum on the data axis instead)."""
    return AucState(*[
        jnp.sum(jnp.stack([getattr(s, f) for s in states]), axis=0)
        for f in AucState._fields
    ])


class Metric:
    """Named metric with phase filter (MetricMsg, metrics.h:198 /
    box_wrapper.h:265). method: 'auc' (others in metrics_ext)."""

    def __init__(self, name: str, label: str = "label", pred: str = "pred",
                 phase: int = -1, nbins: Optional[int] = None) -> None:
        self.name = name
        self.label_var = label
        self.pred_var = pred
        self.phase = phase  # -1: all phases (join/update)
        self.state = init_auc_state(nbins)

    def add(self, pred: jax.Array, label: jax.Array,
            weight: jax.Array) -> None:
        self.state = auc_add_batch(self.state, pred, label, weight)

    def compute(self) -> AucResult:
        return auc_compute(self.state)

    def reset(self) -> None:
        self.state = init_auc_state(self.state.pos.shape[0])


class MetricRegistry:
    """init_metric/get_metric_msg surface (pybind box_helper_py.cc:99-160).

    ``method`` selects the metric variant (metrics_ext.METRIC_METHODS):
    auc | cmatch_rank_auc | mask_auc | cmatch_rank_mask_auc |
    multi_task_auc | continue_value | nan_inf | wuauc."""

    def __init__(self) -> None:
        self._metrics: Dict[str, object] = {}
        self.phase = 1  # 1=join, 0=update (FlipPhase semantics)
        self._warned_missing: set = set()

    def init_metric(self, name: str, method: str = "auc", **kwargs):
        from paddlebox_tpu.metrics_ext import METRIC_METHODS
        try:
            cls = METRIC_METHODS[method]
        except KeyError:
            raise ValueError(
                f"unknown metric method {method!r}; "
                f"one of {sorted(METRIC_METHODS)}") from None
        m = cls(name, **kwargs)
        self._metrics[name] = m
        return m

    def get(self, name: str):
        return self._metrics[name]

    def get_metric_msg(self, name: str) -> Dict[str, float]:
        out = self._metrics[name].compute()
        return out.as_dict() if isinstance(out, AucResult) else out

    def add_batch(self, pred, label, weight=None, **inputs) -> None:
        """Feed every phase-active metric from one batch — the per-batch
        AddAucMonitor hook (boxps_worker.cc:1267). ``inputs`` carries the
        side channels (uid/rank/cmatch/mask…); None values are dropped so
        metrics that don't need them never see them. A metric whose
        REQUIRED side channels are absent from this feed is skipped (with
        a one-time warning) instead of crashing the pass."""
        kw = {k: v for k, v in inputs.items() if v is not None}
        for name, m in self.active().items():
            missing = [r for r in getattr(m, "REQUIRED", ()) if r not in kw]
            if missing:
                if name not in self._warned_missing:
                    self._warned_missing.add(name)
                    log.warning(
                        "metric %r skipped: feed lacks required side "
                        "channel(s) %s", name, missing)
                continue
            # keywords throughout: some variants take only (pred, **_)
            m.add(pred, label=label, weight=weight, **kw)

    def flip_phase(self) -> None:
        self.phase = 1 - self.phase

    def __len__(self) -> int:
        return len(self._metrics)

    def active(self) -> Dict[str, Metric]:
        return {k: m for k, m in self._metrics.items()
                if m.phase in (-1, self.phase)}

    def reset_all(self) -> None:
        for m in self._metrics.values():
            m.reset()
