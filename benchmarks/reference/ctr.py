"""The plain reference of a CTR training pass, in float32 ``jax.numpy`` at
highest matmul precision, the roundings that the configuration states for
its net written out (``lowp_dense``). It imports nothing of the program and takes
nothing the program made: records come from ``traffic.py``, weights from
``models.<name>.init`` and the seed.

What a step is (PaddleBox's pull -> fused_seqpool_cvm -> net -> push):

* the table holds one row a feature: show, clk, delta_score, slot,
  embed_w, embed_g2sum, embedx_g2sum, mf_size, embedx_w[mf]. A key's row
  here is its place among the pass's sorted distinct keys, so the
  reference needs no index of the program's kind.
* pull: [show, clk, embed_w, embedx_w if mf_size > 0 else 0] a key.
* pool: sum over the keys of one (record, slot); then CVM:
  [log(show+1), log(clk+1) - log(show+1), embed_w, embedx_w...].
* loss: mean sigmoid cross-entropy of ``model(pooled, dense)``.
* push: a key's gradient is [record show, record clk, dL/d embed_w,
  dL/d embedx_w] (no gradient through the two CVM columns), summed over
  the key's occurrences in the batch, the embedding part times -batch.
* in-table Adagrad a touched row (g scaled by 1 / g_show):
  w += lr * sqrt(g0 / (g0 + g2sum)) * g, clipped; g2sum += mean(g^2);
  factors are created (at ``mf_initial_range`` 0: as zeros) once
  nonclk_coeff * (show - clk) + clk_coeff * clk reaches the threshold,
  and train from the step after.
* dense: Adam(b1 .9, b2 .999, eps 1e-8) on the mean-loss gradient.

``tower_dtype`` is the precision the net's layers compute in: the one the
configuration states for the reference proper, the one below it for the
control; ``fault`` plants one of the faults the harness has to catch.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

NUM_FIXED = 8
COLS = {"show": 0, "clk": 1, "delta_score": 2, "slot": 3, "embed_w": 4,
        "embed_g2sum": 5, "embedx_g2sum": 6, "mf_size": 7}


# ---- the dense wire (stated in the configuration: per-column affine u8) --

def q8_roundtrip(dense: np.ndarray) -> np.ndarray:
    """Encode a pass's dense block to the q8 wire and decode it again:
    q = rint((x - lo) / scale), scale = (hi - lo) / 255 a column over the
    pass; the range is the [0.1, 99.9] percentiles where the min..max
    range is over four times wider than that (outlier-dominated)."""
    d = dense.astype(np.float32, copy=False)
    lo, hi = d.min(axis=0), d.max(axis=0)
    if d.shape[0] >= 1000:
        p_lo, p_hi = np.percentile(d, [0.1, 99.9], axis=0)
        wild = (hi - lo) > 4.0 * np.maximum(p_hi - p_lo, 1e-30)
        lo, hi = np.where(wild, p_lo, lo), np.where(wild, p_hi, hi)
    scale = (hi - lo) / 255.0
    scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
    lo = lo.astype(np.float32)
    q = np.clip(np.rint((d - lo[None, :]) / scale[None, :]), 0, 255)
    return (q.astype(np.float32) * scale[None, :] + lo[None, :]
            ).astype(np.float32)


# ---- one step ------------------------------------------------------------

def _adagrad(w, g, g2sum, lr, g0, bound):
    ratio = lr * jnp.sqrt(g0 / (g0 + g2sum))
    if g.ndim == 2:
        return (jnp.clip(w + g * ratio[:, None], -bound, bound),
                jnp.mean(g * g, axis=-1))
    return jnp.clip(w + g * ratio, -bound, bound), g * g


def _step(forward, hyper, mf, batch, num_slots, tower_dtype, fault, seg,
          carry, xs):
    table, params, mu, nu, count = carry
    keys, dense, label = xs
    sp = hyper["sparse_optimizer"]
    b = batch
    show_rec = jnp.ones((b,), jnp.float32)
    if fault == "half_batch":
        # half of the batch left out, the mean taken over the rest
        show_rec = (jnp.arange(b) % 2 == 0).astype(jnp.float32)
    ins_w = (show_rec > 0).astype(jnp.float32)
    rec_of_key = seg // num_slots
    key_w = ins_w[rec_of_key]

    rows = table[keys]                                      # [K, F]
    gate = (rows[:, COLS["mf_size"]] > 0).astype(jnp.float32)
    pull = jnp.concatenate(
        [rows[:, 0:2], rows[:, 4:5],
         rows[:, NUM_FIXED:NUM_FIXED + mf] * gate[:, None]], axis=1)

    def loss_fn(params, pull):
        pooled = jax.ops.segment_sum(pull * key_w[:, None], seg,
                                     num_segments=b * num_slots)
        pooled = pooled.reshape(b, num_slots, 3 + mf)
        show_l = jnp.log1p(pooled[..., 0:1])
        ctr = jnp.log1p(pooled[..., 1:2]) - show_l
        out = jnp.concatenate(
            [jax.lax.stop_gradient(show_l), jax.lax.stop_gradient(ctr),
             pooled[..., 2:]], axis=-1)
        logits = forward(params, out, dense, tower_dtype)
        ls = (jnp.maximum(logits, 0) - logits * label
              + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return jnp.sum(ls * ins_w) / jnp.maximum(jnp.sum(ins_w), 1.0), \
            logits

    (loss, logits), (g_params, g_pull) = jax.value_and_grad(
        loss_fn, argnums=(0, 1), has_aux=True)(params, pull)

    clk_rec = label * ins_w
    g_key = jnp.concatenate(
        [(show_rec * ins_w)[rec_of_key][:, None],
         clk_rec[rec_of_key][:, None],
         g_pull[:, 2:] * (-1.0 * b)], axis=1) * key_w[:, None]
    merged = jnp.zeros((table.shape[0], 3 + mf), jnp.float32
                       ).at[keys].add(g_key)
    g_show, g_clk = merged[:, 0], merged[:, 1]
    touched = g_show > 0
    safe = jnp.maximum(g_show, 1e-20)

    t = table
    show = t[:, 0] + g_show
    clk = t[:, 1] + g_clk
    delta = t[:, 2] + sp["nonclk_coeff"] * (g_show - g_clk) \
        + sp["clk_coeff"] * g_clk
    embed_w, e_inc = _adagrad(t[:, 4], merged[:, 2] / safe, t[:, 5],
                              sp["learning_rate"], sp["initial_g2sum"],
                              sp["bound"])
    embedx, x_inc = _adagrad(t[:, NUM_FIXED:NUM_FIXED + mf],
                             merged[:, 3:] / safe[:, None], t[:, 6],
                             sp["mf_learning_rate"], sp["mf_initial_g2sum"],
                             sp["bound"])
    has_mf = t[:, 7] > 0
    score = sp["nonclk_coeff"] * (show - clk) + sp["clk_coeff"] * clk
    create = (~has_mf) & (score >= sp["mf_create_thresholds"])
    embedx_w = jnp.where(has_mf[:, None], embedx,
                         t[:, NUM_FIXED:NUM_FIXED + mf])  # created: zeros
    x_g2 = jnp.where(has_mf, t[:, 6] + x_inc, t[:, 6])
    mf_size = jnp.where(create, 1.0, t[:, 7])
    new = jnp.concatenate(
        [show[:, None], clk[:, None], delta[:, None], t[:, 3:4],
         embed_w[:, None], (t[:, 5] + e_inc)[:, None], x_g2[:, None],
         mf_size[:, None], embedx_w], axis=1)
    table = jnp.where(touched[:, None], new, t)

    lr = hyper["dense_optimizer"]["learning_rate"]
    b1, b2, eps = 0.9, 0.999, 1e-8
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, g_params)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, g_params)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    if fault == "state_unchanged":
        table, params, mu, nu = carry[0], carry[1], carry[2], carry[3]
    pred = jax.nn.sigmoid(logits)
    g_norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(g_params)))
    stats = (loss, jnp.sum(pred * ins_w), jnp.sum(ins_w),
             jnp.sum(((pred - label) * ins_w) ** 2), g_norm)
    return (table, params, mu, nu, count), stats


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _run(forward, hyper_items, mf, batch, num_slots, tower_dtype, fault,
         table, params, keys, seg, dense, label):
    hyper = {k: dict(v) for k, v in hyper_items}
    zeros = jax.tree.map(jnp.zeros_like, params)
    carry = (table, params, zeros, zeros, jnp.zeros((), jnp.int32))
    with jax.default_matmul_precision("highest"):
        carry, stats = jax.lax.scan(
            functools.partial(_step, forward, hyper, mf, batch, num_slots,
                              tower_dtype, fault, seg),
            carry, (keys, dense, label))
    return carry, stats


def run_pass(forward, config: dict, cols, batch: int, params,
             tower_dtype: Optional[str] = None,
             fault: Optional[str] = None) -> Dict:
    """Train one pass of ``cols`` (a ``traffic.PassColumns``) from a zero
    table and ``params`` in global batches of ``batch`` records; returns
    the trained state and each step's loss. The table holds the rows of
    the pass's own keys and no others (``keys``: sorted, row i is key
    ``keys[i]``), padded to a power of two so that every seed runs one
    program. The pass is one jitted scan, so the chip holds one step's
    temporaries at a time."""
    mf = int(config["mf_dim"])
    s = len(config["slot_sizes"])
    r = cols.num_records
    if r % batch:
        raise ValueError(f"{r} records do not fill batches of {batch}")
    nb = r // batch
    k = cols.keys.shape[1]
    uniq, rows = np.unique(cols.keys, return_inverse=True)
    table_rows = 1 << int(len(uniq) - 1).bit_length()
    keys = rows.astype(np.int32).reshape(nb, batch * k)
    seg = (np.arange(batch, dtype=np.int32)[:, None] * s
           + cols.key_slot[None, :]).reshape(-1)
    dense = q8_roundtrip(cols.dense).reshape(nb, batch, -1)
    label = cols.label.reshape(nb, batch)
    table = jnp.zeros((table_rows, NUM_FIXED + mf), jnp.float32)
    hyper_items = tuple(
        (name, tuple(sorted((k2, v2) for k2, v2 in config[name].items()
                            if not isinstance(v2, str))))
        for name in ("sparse_optimizer", "dense_optimizer"))
    carry, stats = _run(forward, hyper_items, mf, batch, s, tower_dtype,
                        fault, table, params, jnp.asarray(keys),
                        jnp.asarray(seg), jnp.asarray(dense),
                        jnp.asarray(label))
    table, new_params, mu, _, _ = carry
    loss, pred_sum, ins, sqr, g_norm = (np.asarray(x, np.float64)
                                        for x in stats)
    return {"table": table, "keys": uniq, "params": new_params, "mu": mu,
            "loss_steps": loss, "grad_norm_steps": g_norm,
            "loss": float(loss.mean()),
            "pred_mean": float(pred_sum.sum() / ins.sum()),
            "rmse": float(np.sqrt(sqr.sum() / ins.sum()))}


# ---- matmuls as the configuration states them ------------------------------
#
# A configuration states the precision of its net (``tower_dtype``): the
# program's layers take operands of that type, accumulate in float32, and
# hand on outputs and cotangents of that type. The reference does the same
# arithmetic in float32 with the roundings written out, so that the
# program's gap to it is the order of summation and nothing else. The
# control states one precision lower for operands and outputs and keeps
# the cotangents where they were (a lower-precision run scales its
# gradients so that they do not underflow).

def _rounder(dtype: Optional[str]):
    if dtype is None:
        return lambda a: a
    dt = jnp.dtype(dtype)
    return lambda a: a.astype(dt).astype(jnp.float32)


def straight_through(x, dtype: Optional[str]):
    """``x`` rounded to ``dtype``, the gradient passing unrounded."""
    if dtype is None:
        return x
    return x + jax.lax.stop_gradient(_rounder(dtype)(x) - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def lowp_dense(x, kernel, bias, dtype, grad_dtype):
    """A dense layer computed in ``dtype`` (flax ``Dense(dtype=...)``):
    operands rounded, float32 accumulation, the product and the biased
    sum each rounded once; backward likewise, cotangents of
    ``grad_dtype``. ``dtype`` None is plain float32."""
    return _lowp_fwd(x, kernel, bias, dtype, grad_dtype)[0]


def _lowp_fwd(x, kernel, bias, dtype, grad_dtype):
    r = _rounder(dtype)
    xr, kr = r(x), r(kernel)
    y = r(r(xr @ kr) + r(bias))
    return y, (xr, kr)


def _lowp_bwd(dtype, grad_dtype, res, g):
    xr, kr = res
    r = _rounder(grad_dtype if dtype is not None else None)
    g = r(g)
    return r(g @ kr.T), r(xr.T @ g), r(jnp.sum(g, axis=0))


lowp_dense.defvjp(_lowp_fwd, _lowp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def default_dense(x, kernel, bias, dtype):
    """A float32 dense layer whose products run at the TPU's default
    matmul precision, as a configuration's ``precision`` states for such
    layers: both operands of every product rounded to ``dtype``
    (bfloat16: one pass), float32 accumulation and output. ``dtype`` None
    is plain float32."""
    return _default_fwd(x, kernel, bias, dtype)[0]


def _default_fwd(x, kernel, bias, dtype):
    r = _rounder(dtype)
    xr, kr = r(x), r(kernel)
    return xr @ kr + bias, (xr, kr)


def _default_bwd(dtype, res, g):
    xr, kr = res
    gr = _rounder(dtype)(g)
    return gr @ kr.T, xr.T @ gr, jnp.sum(g, axis=0)


default_dense.defvjp(_default_fwd, _default_bwd)
