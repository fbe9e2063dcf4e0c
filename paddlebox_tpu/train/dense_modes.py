"""Dense-parameter handling modes beyond per-step in-jit sync.

Reference (boxps_worker.cc):

- **sync mode** ``SyncParam`` (:1191): workers train on local replicas and
  every K steps allreduce the flattened param buffer, scaling by
  1/(ndev*nnode) — i.e. periodic parameter *averaging*, not per-step grad
  allreduce.
- **async mode** ``BoxPSAsynDenseTable`` (:61-370): a host-side flattened
  param table with Adam state; worker threads PullDense (copy latest
  params) and PushDense (enqueue grads) through a buffer queue while a
  background thread drains the queue and applies Adam on CPU. DataNorm
  "summary" params (batch_size/batch_sum/batch_square_sum) are
  accumulated directly instead of Adam-updated (:93-98).

TPU-native redesign: the per-step psum inside the jit step
(train/sharded.py) is the default; these modes exist for parity and for
host-offloaded experimentation. K-step averaging runs as one tiny jitted
pmean over the mesh (or a stacked-axis mean in the single-process
emulation); the async table is numpy + a Channel, with pull/push crossing
host↔device only at pass boundaries the caller chooses.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.utils.channel import Channel
from paddlebox_tpu.utils.logging import get_logger

log = get_logger(__name__)


# ---------------------------------------------------------------------------
# Per-param dense learning rates (lr_map)
# ---------------------------------------------------------------------------
# Reference: InitializeGPUAndLoadModel carries a param-name→lr map applied
# to .w_0/.b_0 names (box_wrapper.cc:1303-1335), consumed per parameter by
# the async dense table (boxps_worker.cc:199-204). TPU-native form: a
# per-leaf UPDATE multiplier (lr_name / base_lr) — applied after the
# optimizer's update (scaling the grad instead would be normalized away
# by Adam), so it composes with any optax tx, the in-jit psum mode,
# ZeRO-1 flat chunks, and the host async table.

def lr_pattern_matches(pat: str, keystr: str) -> bool:
    """Segment-boundary substring match — THE lr_map matching rule
    (build_lr_scales AND AsyncDenseTable use it): ``pat`` must occur in
    ``keystr`` with non-identifier characters (or string ends) on both
    sides, so ``"Dense_1"`` matches ``['Dense_1']['kernel']`` but NOT
    ``['Dense_10']`` (the reference's lr_map keys are exact param
    names; a bare substring test silently over-matched)."""
    import re
    for m in re.finditer(re.escape(pat), keystr):
        a = keystr[m.start() - 1] if m.start() else ""
        b = keystr[m.end()] if m.end() < len(keystr) else ""
        if not (a.isalnum() or a == "_") and not (b.isalnum() or b == "_"):
            return True
    return False


def build_lr_scales(params: Any, lr_map: dict, base_lr: float) -> Any:
    """Pytree of per-leaf multipliers matching ``params``: a leaf whose
    path (jax keystr, e.g. ``"['params']['Dense_0']['kernel']"``)
    matches a key of ``lr_map`` (segment-boundary rule,
    lr_pattern_matches) gets ``lr_map[key] / base_lr``; first match
    wins; unmatched leaves get 1.0 (the global lr)."""
    def scale_of(path, _leaf):
        ks = jax.tree_util.keystr(path)
        for pat, lr in lr_map.items():
            if lr_pattern_matches(pat, ks):
                return float(lr) / float(base_lr)
        return 1.0
    return jax.tree_util.tree_map_with_path(scale_of, params)


def lr_map_transform(scales: Any):
    """optax transform scaling each leaf's update by its multiplier —
    chain AFTER the optimizer: ``optax.chain(optax.adam(base_lr),
    lr_map_transform(build_lr_scales(params, lr_map, base_lr)))``."""
    import optax

    def init(params):
        del params
        return optax.EmptyState()

    def update(updates, state, params=None):
        del params
        return jax.tree.map(lambda u, s: u * s, updates, scales), state

    return optax.GradientTransformation(init, update)


# ---------------------------------------------------------------------------
# K-step periodic parameter averaging (SyncParam analogue)
# ---------------------------------------------------------------------------

class KStepParamSync:
    """Average param replicas every ``k`` steps.

    Replicas are a pytree whose leaves carry a leading replica axis
    (the single-process stand-in for one param copy per device/host; under
    a mesh the same pytree is sharded over ``axis`` and the mean lowers to
    one psum over ICI).
    """

    def __init__(self, k: int, mesh: Optional[Any] = None,
                 axis: str = "dp") -> None:
        if k <= 0:
            raise ValueError("k must be positive")
        self.k = k
        self._step = 0

        if mesh is None:
            def _avg(params):
                return jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        jnp.mean(x, axis=0, keepdims=True), x.shape),
                    params)
            self._avg = jax.jit(_avg)
        else:
            from jax.sharding import PartitionSpec as P

            def _avg(params):
                def body(p):
                    return jax.tree.map(
                        lambda x: jax.lax.pmean(x, axis), p)
                spec = jax.tree.map(lambda _: P(axis), params)
                return jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                                     out_specs=spec)(params)
            self._avg = jax.jit(_avg)

    def maybe_sync(self, params: Any) -> Tuple[Any, bool]:
        """Call once per train step; returns (params, did_sync)."""
        self._step += 1
        if self._step % self.k != 0:
            return params, False
        return self._avg(params), True


# ---------------------------------------------------------------------------
# Async host-side dense table (BoxPSAsynDenseTable analogue)
# ---------------------------------------------------------------------------

class _HostAdam:
    def __init__(self, n: int, lr, beta1: float, beta2: float,
                 eps: float) -> None:
        """``lr`` is a scalar or a per-element [n] vector (lr_map,
        boxps_worker.cc:199-204)."""
        self.m = np.zeros(n, np.float32)
        self.v = np.zeros(n, np.float32)
        self.t = 0
        self.lr, self.b1, self.b2, self.eps = lr, beta1, beta2, eps

    def update(self, p: np.ndarray, g: np.ndarray) -> None:
        self.t += 1
        self.m = self.b1 * self.m + (1 - self.b1) * g
        self.v = self.b2 * self.v + (1 - self.b2) * g * g
        mhat = self.m / (1 - self.b1 ** self.t)
        vhat = self.v / (1 - self.b2 ** self.t)
        p -= self.lr * mhat / (np.sqrt(vhat) + self.eps)

    def _lr_sel(self, sel: np.ndarray):
        return self.lr[sel] if isinstance(self.lr, np.ndarray) else self.lr


class AsyncDenseTable:
    """Host-resident dense params updated by a background Adam thread.

    ``pull()`` returns the latest params as a pytree (device transfer is
    the caller's jnp.asarray); ``push(grads)`` enqueues a gradient pytree
    and returns immediately. Leaves whose path matches ``is_summary``
    (DataNorm batch_size/batch_sum/batch_square_sum) are accumulated
    (ps += grad) instead of Adam-updated, mirroring boxps_worker.cc:93-98.
    """

    def __init__(self, params: Any, lr: float = 1e-3, beta1: float = 0.9,
                 beta2: float = 0.999, eps: float = 1e-8,
                 queue_capacity: int = 64,
                 is_summary: Optional[Callable[[str], bool]] = None,
                 lr_map: Optional[dict] = None) -> None:
        """``lr_map`` — param-name→lr overrides (path-substring match as
        in build_lr_scales); unmatched params use the global ``lr``
        (InitializeGPUAndLoadModel's per-param dense lr map,
        box_wrapper.cc:1303-1335, consumed boxps_worker.cc:199-204)."""
        from jax.flatten_util import ravel_pytree

        host = jax.tree.map(lambda x: np.asarray(x, np.float32), params)
        flat, self._unravel = ravel_pytree(host)
        self._ps = np.array(flat, np.float32)

        # summary mask over the flat vector
        leaves_with_path = jax.tree_util.tree_leaves_with_path(host)
        mask = np.zeros(self._ps.size, bool)
        off = 0
        pred = is_summary or (lambda name: "summary" in name.lower())
        for path, leaf in leaves_with_path:
            n = int(np.size(leaf))
            if pred(jax.tree_util.keystr(path)):
                mask[off:off + n] = True
            off += n
        self._summary_mask = mask

        # per-element lr through THE shared matcher (build_lr_scales):
        # ratios vs the global lr ravel exactly as params do
        lr_vec = None
        if lr_map:
            scales = build_lr_scales(host, lr_map, base_lr=lr)
            sflat, _ = ravel_pytree(jax.tree.map(
                lambda x, s: np.full(np.shape(x), s, np.float32),
                host, scales))
            lr_vec = (lr * np.asarray(sflat)).astype(np.float32)
        self._adam = _HostAdam(self._ps.size,
                               lr if lr_vec is None else lr_vec,
                               beta1, beta2, eps)
        self._q: Channel = Channel(capacity=queue_capacity)
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._applied = 0
        self._pushed = 0

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._q.close()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _loop(self) -> None:
        while True:
            batch = self._q.get_batch(max_items=1)
            if not batch:  # channel closed and drained
                return
            g = batch[0]
            with self._lock:
                s = self._summary_mask
                if s.any():
                    self._ps[s] += g[s]
                    self._adam_masked(~s, g)
                else:
                    self._adam.update(self._ps, g)
                self._applied += 1

    def _adam_masked(self, sel: np.ndarray, g: np.ndarray) -> None:
        a = self._adam
        a.t += 1
        a.m[sel] = a.b1 * a.m[sel] + (1 - a.b1) * g[sel]
        a.v[sel] = a.b2 * a.v[sel] + (1 - a.b2) * g[sel] ** 2
        mhat = a.m[sel] / (1 - a.b1 ** a.t)
        vhat = a.v[sel] / (1 - a.b2 ** a.t)
        self._ps[sel] -= a._lr_sel(sel) * mhat / (np.sqrt(vhat) + a.eps)

    # -- worker API ---------------------------------------------------------

    def pull(self) -> Any:
        with self._lock:
            snap = self._ps.copy()
        return self._unravel(snap)

    def push(self, grads: Any) -> None:
        from jax.flatten_util import ravel_pytree

        host = jax.tree.map(lambda x: np.asarray(x, np.float32), grads)
        flat, _ = ravel_pytree(host)
        with self._lock:
            self._pushed += 1
        self._q.put(np.asarray(flat, np.float32))

    def drain(self) -> int:
        """Block until every pushed grad has been applied (pass barrier);
        returns how many updates have been applied in total. Compares
        applied vs pushed counters — queue emptiness alone would race with
        the in-flight grad the worker has popped but not yet applied."""
        import time

        while True:
            with self._lock:
                if self._applied >= self._pushed:
                    return self._applied
            time.sleep(0.001)
