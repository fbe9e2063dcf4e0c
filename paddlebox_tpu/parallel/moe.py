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

``routed_experts`` is the other kind of expert layer: sigmoid top-k
routing that DROPS NOTHING, over a layer that is told which experts it
holds (one chip's share of an expert-parallel deployment), sorted
token-choices and grouped matrix products over the experts held.

Callers (ROADMAP D5 reads this): ``route_top_k`` and ``routed_experts``
run in the benchmark (``models/nemotron_h.py``'s ``E`` layers, cell
``nemotron3-nano-30b-a3b.train-packed-8k``). The capacity gates
(``top1_gating``, ``top2_gating``, ``naive_gating``),
``moe_forward_local`` and ``moe_forward_sharded`` (the expert
``all_to_all``) are test-only: no model of the benchmark calls them.
"""

from __future__ import annotations

import functools
import math
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


# ---- dropless routing over the experts held here ---------------------------

#: rows of the laid-out axis a chunk computes, and rows of it that belong
#: to one expert; fewer where the tokens are fewer
EXPERT_CHUNK = 8192
EXPERT_BLOCK = 512


def route_top_k(x: jax.Array, router: jax.Array, bias: jax.Array,
                top_k: int, scale: float
                ) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid top-k routing (DeepSeek-V3 / Nemotron-H style), float32 at
    the highest matmul precision: scores ``sigmoid(x W_r)``, the
    ``top_k`` experts by score + ``bias`` (a correction that only
    chooses, and gets no gradient), weights = the chosen scores over
    their sum, times ``scale``. x [N, D] -> (experts [N, k] int32,
    weights [N, k])."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), router,
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / jnp.sum(w, -1, keepdims=True) * scale


def routed_experts(x: jax.Array, idx: jax.Array, w: jax.Array,
                   up: jax.Array, down: jax.Array,
                   held: Tuple[int, int], mm_dtype=jnp.bfloat16
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """What the experts ``held = (lo, hi)`` give the tokens routed to
    them: ``sum_k w[n, k] * down_e(relu(up_e(x[n]))^2)`` over the choices
    k whose expert ``e = idx[n, k]`` is held; choices of experts that
    live elsewhere add nothing here. x [N, D]; idx, w [N, k] from
    ``route_top_k`` over ALL experts; up [hi-lo, D, F], down [hi-lo, F,
    D]. Returns (y [N, D] float32, {"choices": token-choices that fell
    on held experts, "load": [hi-lo] of them an expert}).

    No choice is dropped, whatever the imbalance. The token-choices are
    sorted by expert (held first) and every expert's run of rows is laid
    out from a multiple of ``EXPERT_BLOCK`` rows, so that a block of rows
    belongs to ONE expert: the grouped product is then a batched product
    of [block, D] row blocks with their experts' matrices (gathered a
    block), exact in its operations but for each expert's last, partly
    empty block. Shapes are static: the laid-out axis has ``N * min(k,
    hi-lo) + (hi-lo) * block`` rows, the most that can fall on the held
    experts, cut into chunks of ``min(N, EXPERT_CHUNK)`` rows (a block
    is the largest divisor of a chunk that ``EXPERT_BLOCK`` allows);
    a chunk that starts past the last row in use is skipped
    (``lax.cond``) and a chunk's work is recomputed in the backward
    pass, so time and memory follow the choices that are there and not
    the bound. (A skipped chunk still passes zeros through the scan's
    backward pass; PERF.md section 7.)"""
    n, d = x.shape
    k = idx.shape[1]
    lo, hi = held
    n_held = hi - lo
    bound = n * min(k, n_held)
    rows = min(n, EXPERT_CHUNK)
    blk = math.gcd(rows, EXPERT_BLOCK)
    n_chunks = -(-(bound + n_held * blk) // rows)
    r_pad = n_chunks * rows

    flat_e = idx.reshape(-1)
    is_held = (flat_e >= lo) & (flat_e < hi)
    group = jnp.where(is_held, flat_e - lo, n_held)
    order = jnp.argsort(group, stable=True)[:bound]
    load = jnp.sum(group[:, None] == jnp.arange(n_held)[None, :], axis=0,
                   dtype=jnp.int32)                            # [n_held]
    # where each expert's run starts: packed (in ``order``), and laid out
    # from a multiple of ``blk``
    starts = jnp.cumsum(load) - load
    laid = -(-load // blk) * blk
    laid_ends = jnp.cumsum(laid)
    e_sorted = group[order]
    live = e_sorted < n_held
    e_safe = jnp.minimum(e_sorted, n_held - 1)
    dest = jnp.where(
        live, jnp.arange(bound, dtype=jnp.int32) - starts[e_safe]
        + (laid_ends - laid)[e_safe], r_pad)                   # dead: dropped
    tok = jnp.zeros((r_pad,), jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")
    w_laid = jnp.zeros((r_pad,), w.dtype).at[dest].set(
        w.reshape(-1)[order], mode="drop")
    in_use = laid_ends[-1]
    expert_of_block = jnp.minimum(
        jnp.searchsorted(laid_ends, jnp.arange(r_pad // blk) * blk,
                         side="right"), n_held - 1)
    xm, upm, downm = (x.astype(mm_dtype), up.astype(mm_dtype),
                      down.astype(mm_dtype))
    f32 = jnp.float32

    def one_chunk(y, ci):
        c0 = ci * rows

        # recomputed in the backward pass: the scan then keeps nothing of
        # a chunk but its number
        @jax.checkpoint
        def work(y):
            t = jax.lax.dynamic_slice(tok, (c0,), (rows,))
            wt = jax.lax.dynamic_slice(w_laid, (c0,), (rows,))
            e = jax.lax.dynamic_slice(expert_of_block, (c0 // blk,),
                                      (rows // blk,))
            xb = xm[t].reshape(rows // blk, blk, d)
            hid = jnp.einsum("bmd,bdf->bmf", xb, upm[e],
                             preferred_element_type=f32)
            hid = jnp.square(jax.nn.relu(hid)).astype(mm_dtype)
            out = jnp.einsum("bmf,bfd->bmd", hid, downm[e],
                             preferred_element_type=f32)
            # a row that holds no choice has weight 0
            return y.at[t].add(out.reshape(rows, d) * wt[:, None])

        return jax.lax.cond(c0 < in_use, work, lambda y: y, y), None

    y, _ = jax.lax.scan(one_chunk, jnp.zeros((n, d), f32),
                        jnp.arange(n_chunks, dtype=jnp.int32))
    return y, {"choices": jnp.sum(load), "load": load}
