"""Mixture-of-Experts with expert parallelism.

Reference: python/paddle/incubate/distributed/models/moe/ — ``NaiveGate``
(plain top-k), ``SwitchGate`` (top-1 + capacity), ``GShardGate`` (top-2 +
capacity + load-balance aux loss), and a MoELayer that all-to-alls tokens
to the device owning each expert.

TPU-native redesign: the classic GShard einsum formulation — gating
produces dense one-hot **dispatch** [T, E, C] and weighted **combine**
tensors, expert inputs are one einsum (MXU), and the token exchange is a
single ``jax.lax.all_to_all`` over the ``ep`` mesh axis inside
``shard_map`` (replaces the reference's NCCL Global_Scatter/Gather ops).
Shapes are fully static: capacity drops overflow tokens exactly like the
reference's capacity gates.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def _one_hot(idx: jax.Array, n: int) -> jax.Array:
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def top1_gating(logits: jax.Array, capacity: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, Any]]:
    """Switch-style top-1 routing.

    Returns (dispatch [T,E,C], combine [T,E,C], aux_loss, metrics).
    Tokens beyond an expert's capacity are dropped (zero rows), matching
    the reference SwitchGate's capacity clamp.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                      # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], 1)[:, 0]

    mask = _one_hot(expert, e)                               # [T, E]
    pos = jnp.cumsum(mask, axis=0) * mask - 1.0              # [T, E]
    pos_in_e = jnp.sum(pos * mask, axis=1)                   # [T]
    keep = pos_in_e < capacity
    gate = gate * keep

    # load-balance aux loss (Switch eq.4): E * Σ_e fraction_e * prob_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(mask, axis=0)
    aux = e * jnp.sum(me * ce)

    disp = mask[:, :, None] * _one_hot(
        jnp.clip(pos_in_e, 0, capacity - 1).astype(jnp.int32), capacity
    )[:, None, :] * keep[:, None, None]                      # [T, E, C]
    comb = disp * gate[:, None, None]
    metrics = {"dropped": jnp.sum(1.0 - keep), "load": ce}
    return disp, comb, aux, metrics


def top2_gating(logits: jax.Array, capacity: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, Any]]:
    """GShard-style top-2 routing with renormalized weights."""
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)

    e1 = jnp.argmax(probs, axis=-1)
    p1 = jnp.take_along_axis(probs, e1[:, None], 1)[:, 0]
    probs2 = probs * (1.0 - _one_hot(e1, e))
    e2 = jnp.argmax(probs2, axis=-1)
    p2 = jnp.take_along_axis(probs2, e2[:, None], 1)[:, 0]

    denom = jnp.maximum(p1 + p2, 1e-9)
    w1, w2 = p1 / denom, p2 / denom

    m1 = _one_hot(e1, e)
    m2 = _one_hot(e2, e)
    pos1 = jnp.sum((jnp.cumsum(m1, 0) - 1.0) * m1, axis=1)
    # second choices queue after every first choice of the same expert
    count1 = jnp.sum(m1, axis=0)                             # [E]
    pos2 = jnp.sum((jnp.cumsum(m2, 0) - 1.0) * m2, axis=1) \
        + jnp.sum(m2 * count1[None, :], axis=1)
    keep1 = pos1 < capacity
    keep2 = pos2 < capacity

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(m1, axis=0)
    aux = e * jnp.sum(me * ce)

    def build(mask, pos, keep, w):
        d = mask[:, :, None] * _one_hot(
            jnp.clip(pos, 0, capacity - 1).astype(jnp.int32), capacity
        )[:, None, :] * keep[:, None, None]
        return d, d * w[:, None, None]

    d1, c1 = build(m1, pos1, keep1, w1)
    d2, c2 = build(m2, pos2, keep2, w2)
    disp = jnp.maximum(d1, d2)
    comb = c1 + c2
    metrics = {"dropped": jnp.sum(2.0 - keep1.astype(jnp.float32)
                                  - keep2.astype(jnp.float32)),
               "load": ce}
    return disp, comb, aux, metrics


def naive_gating(logits: jax.Array, capacity: Optional[int] = None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, Any]]:
    """NaiveGate: top-2 without capacity pressure (capacity = T, nothing
    dropped) and no aux loss — the reference's baseline gate."""
    t = logits.shape[0]
    disp, comb, _, metrics = top2_gating(logits, capacity or t)
    return disp, comb, jnp.float32(0.0), metrics


GATES: Dict[str, Callable] = {
    "naive": naive_gating,
    "switch": top1_gating,
    "gshard": top2_gating,
}


def moe_forward_local(x: jax.Array, gate_w: jax.Array,
                      expert_fn: Callable[[jax.Array, Any], jax.Array],
                      expert_params: Any, capacity: int,
                      gate: str = "switch"
                      ) -> Tuple[jax.Array, jax.Array]:
    """Single-device MoE forward (no mesh): all experts local.

    expert_params leaves carry a leading E axis; expert_fn is vmapped.
    Returns (y [T, D], aux_loss).
    """
    logits = x @ gate_w                                      # [T, E]
    disp, comb, aux, _ = GATES[gate](logits, capacity)
    xin = jnp.einsum("tec,td->ecd", disp, x)                 # [E, C, D]
    yout = jax.vmap(expert_fn)(xin, expert_params)           # [E, C, D']
    y = jnp.einsum("tec,ecd->td", comb, yout)
    return y, aux


def moe_forward_sharded(mesh: Any, axis: str,
                        expert_fn: Callable[[jax.Array, Any], jax.Array],
                        capacity: int, gate: str = "switch"):
    """Build an expert-parallel MoE forward over ``mesh[axis]``.

    Tokens are sharded over the axis; expert params carry a leading
    E_local axis per shard. Dispatch einsum happens on the token owner,
    then one all_to_all moves each expert's token slice to the expert
    owner, experts run, and a second all_to_all brings results home.
    """
    from jax.sharding import PartitionSpec as P

    def body(x, gate_w, expert_params):
        logits = x @ gate_w                                   # [t, E_tot]
        disp, comb, aux, _ = GATES[gate](logits, capacity)
        xin = jnp.einsum("tec,td->ecd", disp, x)              # [E_tot, C, D]
        # → [E_loc, n*C, D]: every device contributes its slice of each
        # expert's capacity buffer to the expert's owner
        xin = jax.lax.all_to_all(xin, axis, split_axis=0, concat_axis=1,
                                 tiled=True)
        yout = jax.vmap(expert_fn)(xin, expert_params)        # [E_loc, n*C, D']
        yout = jax.lax.all_to_all(yout, axis, split_axis=1, concat_axis=0,
                                  tiled=True)                 # [E_tot, C, D']
        y = jnp.einsum("tec,ecd->td", comb, yout)
        return y, jax.lax.pmean(aux, axis)

    return _shard_map(
        body, mesh,
        in_specs=(P(axis), P(), P(axis)),
        out_specs=(P(axis), P()),
    )
