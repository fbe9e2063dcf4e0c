"""What the sequence models (``nemotron_h.py``, ``lfm2.py``,
``mellum.py``, ``ouro.py``, ``kimi_linear.py``, ``laguna.py``) share
beside the ops: the stated matrix product, RMSNorm, rotary attention
(queries and keys normed over the head where the layer has such norms,
the heads gated where it has a gate), the SwiGLU of a dense feed-forward
or a shared expert, the dense feed-forward a slab of positions at a time,
the gated-expert feed-forward (with a shared expert or none), the head
read a slab of positions at a time for one hidden state or
several, and the scalars an expert layer hands a step."""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from paddlebox_tpu.obs import trace
from paddlebox_tpu.ops.causal_attention import (LOOP_RESIDUALS,
                                                causal_gqa_attention,
                                                rotary_embedding)
from paddlebox_tpu.parallel.moe import route_top_k, routed_experts

_scope = jax.named_scope

#: positions of a block of attention, and positions the logits exist for
#: at a time: the largest divisors of the sequence (of the step's
#: positions) that these allow
ATTN_BLOCK = 512
HEAD_ROWS = 4096
#: positions the dense feed-forward's hidden activation exists for at a
#: time: the largest divisor of the step's positions that this allows
MLP_ROWS = 4096

#: what the ``jax.checkpoint`` around an attention (sub)layer keeps for
#: its backward pass beside the layer's input: the two results that only
#: the forward block loops can produce (the blocked output and each row's
#: log-sum-exp, float32), so that the recomputed forward is projections,
#: norms and rotations and the forward sweep runs once a step. Everything
#: else inside the layer is recomputed
KEEP_ATTN_LOOPS = jax.checkpoint_policies.save_only_these_names(
    *LOOP_RESIDUALS)

#: the scalars a model with routed experts hands out a step beside the
#: loss, and how a pass folds each over its steps (``SeqTrainStep`` and
#: ``Trainer`` pass them through by these names and know nothing of them)
MOE_STEP_SCALARS = {"moe_choices_held": "sum", "moe_rows_computed": "sum",
                    "moe_expert_load_max": "mean",
                    "moe_expert_load_mean": "mean"}


def matmul(x, w, dtype):
    """The stated matrix product: ``dtype`` operands, float32
    accumulation and result; contracts x's last axis with w's first."""
    return jax.lax.dot_general(
        x.astype(dtype), w.astype(dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rotary_attention(u, lay, heads, eps: float, dtype, rotary: Dict,
                     window: Optional[int] = None):
    """Grouped-query attention of the normed input ``u`` [S, T, hidden]
    with ``heads`` = (query heads, key/value heads, head size): q, k, v
    projections, queries and keys RMS-normed over the head where the
    layer has ``q_norm`` and ``k_norm``, then rotated (``rotary``: what
    ``rotary_embedding`` is told), causal blockwise attention over the
    whole sequence or a ``window``, each head's output times its gate
    ``sigmoid(u W_g)`` where the layer has ``attn_gate`` W_g [hidden,
    query heads] (one scalar a head and position, under
    ``pbox.attn_gate``), the ``o`` projection; the caller names the
    scope and adds the residual."""
    s, t, _ = u.shape
    qh, kvh, hd = heads
    q = matmul(u, lay["q"], dtype).reshape(s, t, qh, hd)
    k = matmul(u, lay["k"], dtype).reshape(s, t, kvh, hd)
    v = matmul(u, lay["v"], dtype).reshape(s, t, kvh, hd)

    def turned(x, norm: str):
        if norm in lay:
            x = rms_norm(x, lay[norm], eps)
        return rotary_embedding(x, **rotary)

    q, k = turned(q, "q_norm"), turned(k, "k_norm")
    o = causal_gqa_attention(q, k, v, block=ATTN_BLOCK, mm_dtype=dtype,
                             window=window)
    if "attn_gate" in lay:
        with _scope(trace.SCOPE_ATTN_GATE):
            o = o * jax.nn.sigmoid(matmul(u, lay["attn_gate"], dtype)
                                   )[..., None]
    return matmul(o.reshape(s, t, qh * hd), lay["o"], dtype)


def swiglu(u, w_gate, w_up, w_down, dtype):
    """``(silu(u W_gate) * (u W_up)) W_down``: a dense feed-forward's or a
    shared expert's body (the routed experts' is
    ``parallel/moe.routed_experts``')."""
    hid = jax.nn.silu(matmul(u, w_gate, dtype)) * matmul(u, w_up, dtype)
    return matmul(hid, w_down, dtype)


def _dense_slab(lay, x, eps: float, dtype):
    with _scope(trace.SCOPE_MLP):
        u = rms_norm(x, lay["ffn_norm"], eps)
        return x + swiglu(u, lay["w1"], lay["w3"], lay["w2"], dtype)


def dense_ffn(lay, x, eps: float, dtype):
    """``x + SwiGLU(RMSNorm(x))`` of a dense feed-forward layer (``w1``
    gate, ``w3`` up, ``w2`` down) under ``pbox.mlp``, over ``x`` [S, T,
    hidden]: its hidden activation is the widest value of a step, so it
    exists for ``MLP_ROWS`` positions at a time, each slab one
    ``jax.checkpoint`` that keeps its input."""
    shape = x.shape
    rows = math.gcd(shape[0] * shape[1], MLP_ROWS)
    one = jax.checkpoint(_dense_slab, static_argnums=(2, 3))
    return jax.lax.map(lambda xs: one(lay, xs, eps, dtype),
                       x.reshape(-1, rows, shape[-1])).reshape(shape)


def gated_experts(lay, x, eps: float, dtype, held: Tuple[int, int],
                  top_k: int, scale: float, bias=None, sum_eps: float = 0.0,
                  score=jax.nn.sigmoid):
    """``x + FFN(RMSNorm(x))`` of an expert layer over ``x`` [S, T,
    hidden] -> (that, the token-choices each held expert took [held], the
    rows its loops computed): the router over all its outputs
    (``route_top_k``'s ``bias``, ``scale``, ``sum_eps`` and ``score``)
    under ``pbox.moe_route``, the held gated experts' share under
    ``pbox.moe_experts``, and where the layer has one
    (``shared_gate``, ``shared_up``, ``shared_down``) a shared SwiGLU
    expert every token passes through under ``pbox.moe_shared``; the
    residual add closes the last of the two."""
    s, t, d = x.shape
    with _scope(trace.SCOPE_MOE_ROUTE):
        u = rms_norm(x, lay["ffn_norm"], eps).reshape(s * t, d)
        idx, w = route_top_k(u, lay["router"], bias, top_k, scale,
                             sum_eps=sum_eps, score=score)
    with _scope(trace.SCOPE_MOE_EXPERTS):
        y, stats = routed_experts(u, idx, w, lay["up"], lay["down"], held,
                                  mm_dtype=dtype, gate=lay["gate"])
        if "shared_up" not in lay:
            return x + y.reshape(s, t, d), stats["load"], stats["rows"]
    with _scope(trace.SCOPE_MOE_SHARED):
        y = y + swiglu(u, lay["shared_gate"], lay["shared_up"],
                       lay["shared_down"], dtype)
        return x + y.reshape(s, t, d), stats["load"], stats["rows"]


def _head_slabs(fold, carry, x, lab, beside, norm_weight, head, eps: float,
                dtype):
    """The one loop over the head: slab by slab of ``x`` [slabs, rows,
    hidden] (the logits exist for one slab at a time) the final norm
    (``norm_weight`` None: ``x`` comes normed), the untied ``head`` and
    the cross-entropy ``nll`` [rows] of the slab's labels ``lab`` [slabs,
    rows], handed with the slab's row of ``beside`` (or None) to
    ``fold(carry, nll, beside_r) -> (carry, out)``. Returns the last
    carry and the stacked outs, as ``jax.lax.scan`` does."""
    @jax.checkpoint
    def some_rows(carry, xs):
        x_r, lab_r, beside_r = xs
        with _scope(trace.SCOPE_HEAD):
            if norm_weight is not None:
                x_r = rms_norm(x_r, norm_weight, eps)
            z = matmul(x_r, head, dtype)
        with _scope(trace.SCOPE_LOSS):
            logp = jax.nn.log_softmax(z, axis=-1)
            nll = -jnp.take_along_axis(logp, lab_r[:, None], -1)[:, 0]
            return fold(carry, nll, beside_r)

    return jax.lax.scan(some_rows, carry, (x, lab, beside))


def head_nll(xs, norm_weight, head, labels, eps: float, dtype):
    """Cross-entropy of ``labels`` [S, T] at every position, once a
    hidden state of ``xs`` [exits, S, T, hidden], all through the one
    untied ``head``: float32 [exits, slabs, rows], the positions in
    ``labels``' order, ``HEAD_ROWS`` of one state at a time.
    ``norm_weight`` is the final norm's, or None where the states come
    normed."""
    exits, n = xs.shape[0], labels.size
    rows = math.gcd(n, HEAD_ROWS)
    x = xs.reshape(exits * n // rows, rows, xs.shape[-1])
    lab = jnp.tile(labels.reshape(n // rows, rows), (exits, 1))
    _, nll = _head_slabs(lambda carry, nll, _: (carry, nll), None, x, lab,
                         None, norm_weight, head, eps, dtype)
    return nll.reshape(exits, n // rows, rows)


def head_loss(x, norm_weight, head, labels, valid, eps: float, dtype):
    """Mean cross-entropy of ``labels`` [S, T] over the positions
    ``valid`` marks, from the last layer's output ``x`` [S, T, hidden]
    through the final norm and the untied ``head``: the loop's one-state
    case, folded into a sum as it goes. The logits exist for
    ``HEAD_ROWS`` positions at a time."""
    n = labels.size
    rows = math.gcd(n, HEAD_ROWS)
    x = x.reshape(n // rows, rows, x.shape[-1])
    lab = labels.reshape(n // rows, rows)
    ok = valid.reshape(n // rows, rows).astype(jnp.float32)
    total, _ = _head_slabs(
        lambda total, nll, ok_r: (total + jnp.sum(nll * ok_r), None),
        jnp.zeros((), jnp.float32), x, lab, ok, norm_weight, head, eps, dtype)
    with _scope(trace.SCOPE_LOSS):
        return total / jnp.maximum(jnp.sum(ok), 1.0)


def moe_load_scalars(loads: jax.Array,
                     computed: jax.Array) -> Dict[str, jax.Array]:
    """Of the token-choices each held expert took in each expert layer
    [expert layers, held]: their sum, beside the sum of the rows the
    layers' loops ``computed`` for them (the choices and each run's
    padding to whole blocks), and the layer under most load this step:
    its busiest held expert's choices and its mean."""
    loads = loads.astype(jnp.float32)
    if loads.shape[0]:
        worst = loads[jnp.argmax(jnp.max(loads, axis=1))]
        top, mean = jnp.max(worst), jnp.mean(worst)
    else:
        top = mean = jnp.zeros((), jnp.float32)
    return {"moe_choices_held": jnp.sum(loads),
            "moe_rows_computed": jnp.sum(computed.astype(jnp.float32)),
            "moe_expert_load_max": top, "moe_expert_load_mean": mean}
