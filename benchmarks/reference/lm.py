"""The plain reference of a language model's training pass: float32
``jax.numpy`` at highest matmul precision, the roundings that the
configuration states written out by the model (``models.<name>.mm``).
It imports nothing of the program and takes nothing the program made:
tokens come from the family's generator, weights and token vectors from
``models.<name>.init`` / ``init_embedding`` and the seed.

What a step is (pull -> net -> loss -> push, a record is one token):

* the table holds one row a token id: show, clk, delta_score, slot,
  embed_w, embed_g2sum, embedx_g2sum, mf_size, embedx_w[hidden]; the row
  of id i is row i. A token's vector is its row's ``embedx_w``, pulled
  unpooled: position t of a sequence reads the row of token t.
* loss: mean cross-entropy of the next token over the step's positions.
* push: a row's gradient is dL/d(vector) summed over the row's
  occurrences in the step, times -positions; show counts occurrences.
* in-row Adagrad a touched row (g scaled by 1 / occurrences):
  w += lr * sqrt(g0 / (g0 + g2sum)) * g, clipped; g2sum += mean(g^2).
  The 1-wide ``embed_w`` gets no gradient from this model.
* dense: Adam(b1 .9, b2 .999, eps 1e-8) on the mean-loss gradient.

``precision`` is what the matrix products' operands are rounded to: the
one the configuration states for the reference proper, the one below it
for the control; ``fault`` plants one of the faults the harness has to
catch.
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


def initial_table(embedding) -> jax.Array:
    """The table before the first pass: every id's row holds its seeded
    vector, marked as made (``mf_size`` 1), all counters 0."""
    v = embedding.shape[0]
    fixed = jnp.zeros((v, NUM_FIXED), jnp.float32
                      ).at[:, COLS["mf_size"]].set(1.0)
    return jnp.concatenate([fixed, embedding.astype(jnp.float32)], axis=1)


def _step(model, config_items, sp, lr, precision, fault, carry, xs):
    table, params, mu, nu, count = carry
    tokens, labels = xs                                     # [S, T] each
    config = dict(config_items)
    positions = tokens.size
    emb = table[tokens, NUM_FIXED:]                         # [S, T, D]
    loss, (g_params, g_emb) = jax.value_and_grad(
        lambda p, e: model.loss(p, e, labels, config, precision, fault),
        argnums=(0, 1))(params, emb)

    # push: occurrences merged a row, the vector's part times -positions
    flat = tokens.reshape(-1)
    g_show = jnp.zeros((table.shape[0],), jnp.float32).at[flat].add(1.0)
    g_vec = jnp.zeros((table.shape[0], emb.shape[-1]), jnp.float32
                      ).at[flat].add(g_emb.reshape(positions, -1)
                                     * (-1.0 * positions))
    touched = g_show > 0
    safe = jnp.maximum(g_show, 1e-20)
    scaled = g_vec / safe[:, None]
    g2 = table[:, COLS["embedx_g2sum"]]
    ratio = sp["mf_learning_rate"] * jnp.sqrt(
        sp["mf_initial_g2sum"] / (sp["mf_initial_g2sum"] + g2))
    vec = jnp.clip(table[:, NUM_FIXED:] + scaled * ratio[:, None],
                   -sp["bound"], sp["bound"])
    new = table.at[:, COLS["show"]].add(g_show)
    new = new.at[:, COLS["delta_score"]].add(sp["nonclk_coeff"] * g_show)
    new = new.at[:, COLS["embedx_g2sum"]].add(jnp.mean(scaled * scaled, -1))
    new = new.at[:, NUM_FIXED:].set(vec)
    new_table = jnp.where(touched[:, None], new, table)

    b1, b2, eps = 0.9, 0.999, 1e-8
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, g_params)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, g_params)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    new_params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    if fault == "state_unchanged":
        new_table, new_params, mu, nu = table, params, carry[2], carry[3]
    return (new_table, new_params, mu, nu, count), loss


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5),
                   donate_argnums=(6, 7))
def _run(model, config_items, sp_items, lr, precision, fault, table, params,
         tokens, labels):
    zeros = jax.tree.map(jnp.zeros_like, params)
    carry = (table, params, zeros, zeros, jnp.zeros((), jnp.int32))
    with jax.default_matmul_precision("highest"):
        carry, losses = jax.lax.scan(
            functools.partial(_step, model, config_items, dict(sp_items),
                              lr, precision, fault),
            carry, (tokens, labels))
    return carry, losses


def _hashable(config: dict):
    return tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, str))))


def run_pass(model, config: dict, tokens: np.ndarray, labels: np.ndarray,
             seqs_per_step: int, params, embedding,
             precision: Optional[str] = None,
             fault: Optional[str] = None) -> Dict:
    """Train one pass: ``tokens`` / ``labels`` int32 [sequences, T], in
    steps of ``seqs_per_step`` sequences, from ``params`` and the seeded
    token vectors ``embedding`` [vocab, hidden]. Returns the trained
    state and each step's loss. The pass is one jitted scan, so the chip
    holds one step's temporaries at a time."""
    n, t = tokens.shape
    if n % seqs_per_step:
        raise ValueError(f"{n} sequences do not fill steps of "
                         f"{seqs_per_step}")
    shape = (n // seqs_per_step, seqs_per_step, t)
    sp = config["sparse_optimizer"]
    sp_items = tuple(sorted((k, v) for k, v in sp.items()
                            if not isinstance(v, str)))
    # own copies: the run donates its table and weights
    table = initial_table(jnp.array(embedding))
    params = jax.tree.map(jnp.array, params)
    carry, losses = _run(
        model, _hashable(config), sp_items,
        float(config["dense_optimizer"]["learning_rate"]), precision, fault,
        table, params,
        jnp.asarray(tokens.reshape(shape), jnp.int32),
        jnp.asarray(labels.reshape(shape), jnp.int32))
    table, new_params, mu, _, _ = carry
    return {"table": table, "params": new_params, "mu": mu,
            "loss_steps": np.asarray(losses, np.float64)}
