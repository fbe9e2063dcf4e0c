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

``routed_experts`` is the other kind of expert layer: top-k routing
(``route_top_k``: sigmoid or softmax scores) that DROPS NOTHING, over a
layer that is told which experts it holds (one chip's share of an
expert-parallel deployment): one sort of
the token-choices, then a grouped matrix product that is one loop an
expert over the blocks of rows that hold its choices, the trip count
read from the data, forward and (written by hand, ``jax.custom_vjp``)
backward.

Callers (ROADMAP D5 reads this): ``route_top_k`` and ``routed_experts``
run in the benchmark (``models/nemotron_h.py``'s ``E`` layers,
``models/lfm2.py``'s and ``models/mellum.py``'s feed-forwards: the three
``train-packed-8k`` cells). The capacity gates
(``top1_gating``, ``top2_gating``, ``naive_gating``),
``moe_forward_local`` and ``moe_forward_sharded`` (the expert
``all_to_all``) are test-only: no model of the benchmark calls them.
"""

from __future__ import annotations

import functools
import math
import operator
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

#: rows of an expert's run that one trip of the loops computes; the
#: largest divisor of the token count that this allows
EXPERT_BLOCK = 512


def route_top_k(x: jax.Array, router: jax.Array,
                bias: Optional[jax.Array], top_k: int, scale: float,
                sum_eps: float = 0.0,
                score: Callable[[jax.Array], jax.Array] = jax.nn.sigmoid
                ) -> Tuple[jax.Array, jax.Array]:
    """Top-k routing, float32 at the highest matmul precision: scores
    ``score(x W_r)`` over ALL the router's outputs (``sigmoid``:
    DeepSeek-V3 / Nemotron-H / LFM2 style; ``jax.nn.softmax``: the
    Qwen3-MoE key family's, ``models/mellum.py``), the ``top_k`` experts
    by score + ``bias`` (a correction that only chooses, and gets no
    gradient; None: there is none), weights = the chosen scores over
    their sum (+ ``sum_eps`` where a model's equations add one), times
    ``scale``. x [N, D] -> (experts [N, k] int32, weights [N, k])."""
    s = score(jnp.dot(x.astype(jnp.float32), router,
                      precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(
        s if bias is None else s + jax.lax.stop_gradient(bias), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    total = jnp.sum(w, -1, keepdims=True)
    if sum_eps:
        total = total + sum_eps
    return idx, w / total * scale


def routed_experts(x: jax.Array, idx: jax.Array, w: jax.Array,
                   up: jax.Array, down: jax.Array,
                   held: Tuple[int, int], mm_dtype=jnp.bfloat16,
                   gate: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """What the experts ``held = (lo, hi)`` give the tokens routed to
    them: ``sum_k w[n, k] * expert_e(x[n])`` over the choices k whose
    expert ``e = idx[n, k]`` is held; choices of experts that live
    elsewhere add nothing here. An expert's weights say what it is:
    ``down_e(relu(up_e(x))^2)`` with one up-projection,
    ``down_e(silu(gate_e(x)) * up_e(x))`` where it has a ``gate`` as well.
    x [N, D]; idx, w [N, k] from ``route_top_k`` over ALL experts (a
    token's k experts are distinct); up, gate [hi-lo, D, F], down
    [hi-lo, F, D]. Returns (y [N, D] float32, {"choices": token-choices
    that fell on held experts, "load": [hi-lo] of them an expert, "rows":
    the rows the loops computed}).

    No choice is dropped, whatever the imbalance. The layout is plain
    JAX: ONE sort of the choices by (expert, token) packs every held
    expert's tokens into a run (``tok``; ``starts``, ``load`` say where
    and how long), and ``wt`` [N, hi-lo] holds what weight a token gave
    each held expert (0 where it did not choose it), so ``w``'s gradient
    flows through ``wt``. The grouped product over the runs
    (``_grouped_product``) is then one loop an expert whose trip count is
    read from the data: ``ceil(load_e / block)`` blocks of ``block`` rows
    (the largest divisor of N that ``EXPERT_BLOCK`` allows), each a
    gather of its tokens' rows, the products with the expert's matrices
    read in place, and a scatter-add; the backward pass is written by
    hand as the same loop (reverse mode cannot differentiate a trip count
    that is data). Nothing is sized by, or walks, the ``N * k`` bound but
    the sort and its integers: time follows the choices that are there,
    and an expert without a choice runs no trip."""
    n, k = x.shape[0], idx.shape[1]
    lo, hi = held
    n_held = hi - lo
    blk = math.gcd(n, EXPERT_BLOCK)
    if (n_held + 1) * n * k >= 2 ** 31:
        raise ValueError(f"{n} tokens x {k} choices x {n_held} experts "
                         f"held do not fit the int32 sort key")

    mine = idx[:, :, None] == jnp.arange(lo, hi, dtype=idx.dtype)
    wt = jnp.sum(jnp.where(mine, w[:, :, None], 0), axis=1)   # [N, n_held]
    load = jnp.sum(mine, axis=(0, 1), dtype=jnp.int32)         # [n_held]
    starts = jnp.cumsum(load) - load
    # held choices first, by expert, a run's tokens ascending; the key is
    # unique, so one operand sorts and nothing is gathered
    flat_e = idx.reshape(-1)
    group = jnp.where((flat_e >= lo) & (flat_e < hi), flat_e - lo, n_held)
    key = group * (n * k) + jnp.arange(n * k, dtype=jnp.int32)
    tok = (jnp.sort(key) % (n * k)) // k
    # a run's last block reads up to ``blk`` entries past the run
    tok = jnp.concatenate([tok, jnp.zeros((blk,), jnp.int32)])

    ups = (up,) if gate is None else (gate, up)
    y = _grouped_product(x, wt, ups, down, tok, starts, load, blk, mm_dtype)
    return y, {"choices": jnp.sum(load), "load": load,
               "rows": jnp.sum(-(-load // blk)) * blk}


def _block_rows(tok, starts, load, e: int, i, blk: int):
    """Block ``i`` of expert ``e``'s run: its rows' tokens, and which of
    the rows hold a choice (the run's last block may be partly past it:
    those rows read another run's tokens and weigh 0)."""
    t = jax.lax.dynamic_slice(tok, (starts[e] + i * blk,), (blk,))
    return t, i * blk + jnp.arange(blk, dtype=jnp.int32) < load[e]


def _dot(a, b, axis_a: int, axis_b: int):
    """``a`` and ``b`` contracted over one axis each, float32 out."""
    return jax.lax.dot_general(a, b, (((axis_a,), (axis_b,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _activation(hids):
    """An expert's hidden activation, float32, from its up-projections'
    products ``hids``, and what the backward rule keeps of them: one
    product is ``relu^2``, two are ``silu(gate) * up``."""
    if len(hids) == 1:
        hid = jax.nn.relu(hids[0])
        return jnp.square(hid), hid
    g, u = hids
    sig = jax.nn.sigmoid(g)
    return g * sig * u, (g, u, sig)


def _activation_bwd(kept, dact):
    """The products' cotangents from the activation's ``dact``."""
    if not isinstance(kept, tuple):
        return (dact * 2 * kept,)
    g, u, sig = kept
    return dact * u * sig * (1 + g * (1 - sig)), dact * g * sig


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _grouped_product(x, wt, ups, down, tok, starts, load, blk, mm_dtype):
    """``y[n] = sum_e wt[n, e] * down_e(act(up_e(x[n]) for up in ups))``
    over the tokens of each expert's run (``routed_experts`` says what
    ``tok``, ``starts`` and ``load`` are, ``_activation`` what ``act``
    is). Products take ``mm_dtype`` operands and accumulate in float32;
    the activation is float32."""
    xm = x.astype(mm_dtype)
    upms = [up.astype(mm_dtype) for up in ups]
    downm = down.astype(mm_dtype)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(down.shape[0]):

        def one_block(i, y, e=e):
            t, live = _block_rows(tok, starts, load, e, i, blk)
            xb = xm[t]
            act, _ = _activation([_dot(xb, upm[e], 1, 0) for upm in upms])
            out = _dot(act.astype(mm_dtype), downm[e], 1, 0)
            wb = jnp.where(live, wt[:, e][t], 0)
            return y.at[t].add(out * wb[:, None])

        y = jax.lax.fori_loop(0, -(-load[e] // blk), one_block, y)
    return y


def _grouped_product_fwd(x, wt, ups, down, tok, starts, load, blk, mm_dtype):
    y = _grouped_product(x, wt, ups, down, tok, starts, load, blk, mm_dtype)
    return y, (x, wt, ups, down, tok, starts, load)


def _grouped_product_bwd(blk, mm_dtype, res, dy):
    """Nothing of a block is kept from the forward pass: a trip gathers
    its rows again and computes the hidden activation once more, then the
    cotangents of every product (cotangents are ``mm_dtype`` operands as
    the activations were). An expert's weight gradients accumulate in
    float32 through its loop and are written once."""
    x, wt, ups, down, tok, starts, load = res
    f32 = jnp.float32
    xm = x.astype(mm_dtype)
    upms = [up.astype(mm_dtype) for up in ups]
    downm = down.astype(mm_dtype)
    dx = jnp.zeros(x.shape, f32)
    dwt, dups, ddown = [], [], []
    for e in range(down.shape[0]):

        def one_block(i, carry, e=e):
            dx, dwt_e, dups_e, ddown_e = carry
            t, live = _block_rows(tok, starts, load, e, i, blk)
            wb = jnp.where(live, wt[:, e][t], 0)
            xb = xm[t]
            act, kept = _activation([_dot(xb, upm[e], 1, 0)
                                     for upm in upms])
            act = act.astype(mm_dtype)
            dyb = dy[t]
            # d out / d act, before the row's weight: the weight's own
            # gradient is sum(out * dy) = sum(act * (dy down^T))
            dact = _dot(dyb.astype(mm_dtype), downm[e], 1, 1)
            dwt_e = dwt_e.at[t].add(
                jnp.where(live, jnp.sum(act.astype(f32) * dact, -1), 0))
            ddown_e = ddown_e + _dot(
                act, (dyb * wb[:, None]).astype(mm_dtype), 0, 0)
            dhids = [dhid.astype(mm_dtype) for dhid in
                     _activation_bwd(kept, dact * wb[:, None])]
            dx = dx.at[t].add(functools.reduce(operator.add, [
                _dot(dhid, upm[e], 1, 1) for dhid, upm in zip(dhids, upms)]))
            dups_e = [dup_e + _dot(xb, dhid, 0, 0)
                      for dup_e, dhid in zip(dups_e, dhids)]
            return dx, dwt_e, dups_e, ddown_e

        dx, dwt_e, dups_e, ddown_e = jax.lax.fori_loop(
            0, -(-load[e] // blk), one_block,
            (dx, jnp.zeros(x.shape[:1], f32),
             [jnp.zeros(up.shape[1:], f32) for up in ups],
             jnp.zeros(down.shape[1:], f32)))
        dwt.append(dwt_e)
        dups.append(dups_e)
        ddown.append(ddown_e)
    return (dx.astype(x.dtype), jnp.stack(dwt, 1).astype(wt.dtype),
            tuple(jnp.stack(d).astype(up.dtype)
                  for d, up in zip(zip(*dups), ups)),
            jnp.stack(ddown).astype(down.dtype), None, None, None)


_grouped_product.defvjp(_grouped_product_fwd, _grouped_product_bwd)
