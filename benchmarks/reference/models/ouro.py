"""Ouro (``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741): ONE stack of identical layers run
``total_ut_steps`` = R times with the same weights. With token vectors
``e``, ``h_0 = e`` and r = 1..R:

    x = h_{r-1}
    for l = 1..L (the same weights at every r):
        x <- x + N2_l(Attn_l(N1_l(x)))         RMSNorm before AND after
        x <- x + N4_l(down_l(silu(gate_l(u)) * up_l(u))),  u = N3_l(x)
    h_r   = N(x)                    the final RMSNorm, inside the loop
    z_r   = h_r W_head              logits of exit r
    lam_r = sigmoid(h_r . w_gate + b_gate)            one number a position
    p_r   = lam_r prod_{j<r} (1 - lam_j)  for r < R
    p_R   = prod_{j<R} (1 - lam_j)
    loss  = mean over positions of [ sum_r p_r CE(z_r, label) - beta H(p) ],
            H(p) = - sum_r p_r log p_r

Plain ``jax.numpy`` in float32, written from the published configuration
(``configs/ouro-2.6b.json`` names it): hidden 2048, no biases, RMSNorm eps
1e-6. A Python loop over the runs and the layers.

* **Attention**, 16 query heads on 16 key/value heads of 128. q, k, v
  projections; NO norm on queries or keys; both rotated (rotate-half over
  the whole head, ``rope_theta`` 1e6, position = index in the sequence);
  ``a_t = sum_{s <= t} softmax_s(q_t . k_s / sqrt(128)) v_s``; ``o``
  projection. The softmax is dense and masked, a block of query rows at a
  time.
* **Feed-forward**: ``(silu(u G) * (u U)) D``, 2048 -> 5632 -> 2048.
* **The exits**: the head and the gate read ``h_r`` after every run; the
  gate is one linear unit with a bias. ``early_exit_threshold`` 1 means no
  position leaves early at inference; in training every exit is always
  computed, as here.

Departures from the config, each under ``assumed`` in the configuration
file: the sandwich norms, the final norm's place, the gate, the exit
distribution and the loss with ``exit_entropy_beta`` are the report's and
the public modelling code's, not keys of the config; positions count
through the packed sequence and documents are not masked from each other.

Precision, as the configuration states it: every matrix product (``mm``)
takes operands rounded to ``precision`` (bfloat16) and accumulates in
float32; the norms, the rotary embedding, the gate (product, sigmoid,
survival products, entropy), the softmax and the loss are float32. The
stated product and the norm are ``nemotron_h.py``'s, the rotation and the
gated feed-forward ``lfm2.py``'s: one statement of each for every language
model. ``jax.checkpoint`` changes where memory is spent and no arithmetic.

``fault="loop_short"`` plants a program that leaves a run of the loop
out: the stack is run R - 1 times and the exit before the last takes the
last one's mass (the distribution's own formula over R - 1 exits).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.models.lfm2 import rotary, swiglu_mlp
from benchmarks.reference.models.nemotron_h import HIGHEST, mm, rms_norm

#: what is computed a block of positions at a time, so that a pass at the
#: cell's size (8,192 positions, a vocabulary of 49,152) fits one chip
#: beside ``reference/lm.py``'s two copies of the weights and Adam's
#: moments: query rows of the dense [T, T] attention, positions of the
#: feed-forward, positions the logits of one exit exist for
Q_BLOCK = 128
MLP_ROWS = 1024
HEAD_ROWS = 1024


def dims(config: dict) -> dict:
    """The sizes the layers are written in, from the configuration's
    scalars (``reference/lm.py`` hands ``loss`` nothing else)."""
    return {
        "d": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "qh": int(config["num_attention_heads"]),
        "kvh": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "theta": float(config["rope_theta"]),
        "ff": int(config["intermediate_size"]),
        "eps": float(config["rms_norm_eps"]),
        "vocab": int(config["vocab_size"]),
    }


# ---- seeded weights ------------------------------------------------------

def init(key, config: dict):
    """Weights from one key: normal(0, 0.02) matrices and gate weight, the
    gate's bias 0, norms 1."""
    z = dims(config)
    d, f32 = z["d"], jnp.float32

    def normal(k, shape):
        return jax.random.normal(k, shape, f32) * 0.02

    layers = []
    for i in range(z["layers"]):
        ks = jax.random.split(jax.random.fold_in(key, i), 7)
        layers.append({
            "attn_norm": jnp.ones((d,), f32),
            "attn_out_norm": jnp.ones((d,), f32),
            "ffn_norm": jnp.ones((d,), f32),
            "ffn_out_norm": jnp.ones((d,), f32),
            "q": normal(ks[0], (d, z["qh"] * z["hd"])),
            "k": normal(ks[1], (d, z["kvh"] * z["hd"])),
            "v": normal(ks[2], (d, z["kvh"] * z["hd"])),
            "o": normal(ks[3], (z["qh"] * z["hd"], d)),
            "gate": normal(ks[4], (d, z["ff"])),
            "up": normal(ks[5], (d, z["ff"])),
            "down": normal(ks[6], (z["ff"], d))})
    kh, kg = jax.random.split(jax.random.fold_in(key, z["layers"]))
    return {"layers": layers, "norm": jnp.ones((d,), f32),
            "exit_w": normal(kg, (d,)), "exit_b": jnp.zeros((), f32),
            "head": normal(kh, (d, z["vocab"]))}


def init_embedding(key, config: dict):
    """The token vectors [vocab, hidden] that the table's rows start
    from: normal(0, 0.02), from the same seed as the weights."""
    z = dims(config)
    return jax.random.normal(jax.random.fold_in(key, 10 ** 6),
                             (z["vocab"], z["d"]), jnp.float32) * 0.02


# ---- a layer ----------------------------------------------------------------

def attention(lay, u, z: dict, precision):
    bsz, t, _ = u.shape
    qh, kvh, hd = z["qh"], z["kvh"], z["hd"]
    q = mm(u, lay["q"], "btd,de->bte", precision).reshape(
        bsz, t, kvh, qh // kvh, hd)
    k = mm(u, lay["k"], "btd,de->bte", precision).reshape(bsz, t, kvh, hd)
    v = mm(u, lay["v"], "btd,de->bte", precision).reshape(bsz, t, kvh, hd)
    q, k = rotary(q, z["theta"]), rotary(k, z["theta"])
    blk = math.gcd(t, Q_BLOCK)
    pos_k = jnp.arange(t)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        s = mm(qi, k, "bqkgd,bskd->bkgqs", precision) * hd ** -0.5
        mask = (i * blk + jnp.arange(blk))[:, None] >= pos_k[None, :]
        s = jnp.where(mask, s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v, "bkgqs,bskd->bqkgd",
                  precision)

    out = jax.lax.map(jax.checkpoint(rows), jnp.arange(t // blk))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, qh * hd)
    return mm(out, lay["o"], "bte,ed->btd", precision)


def feed_forward(lay, u, precision):
    """The SwiGLU feed-forward, ``MLP_ROWS`` positions at a time."""
    bsz, t, d = u.shape
    rows = math.gcd(bsz * t, MLP_ROWS)
    out = jax.lax.map(jax.checkpoint(lambda s: swiglu_mlp(
        s, lay["gate"], lay["up"], lay["down"], precision)),
        u.reshape(bsz * t // rows, 1, rows, d))
    return out.reshape(bsz, t, d)


def layer(x, lay, z: dict, precision):
    eps = z["eps"]
    x = x + rms_norm(attention(lay, rms_norm(x, lay["attn_norm"], eps), z,
                               precision), lay["attn_out_norm"], eps)
    u = rms_norm(x, lay["ffn_norm"], eps)
    return x + rms_norm(feed_forward(lay, u, precision),
                        lay["ffn_out_norm"], eps)


# ---- the loop and its exits -------------------------------------------------

def runs_of(config: dict, fault: Optional[str]) -> int:
    """How often the stack is run: the configuration's count, one fewer
    under the planted ``loop_short``."""
    runs = int(config["total_ut_steps"])
    return runs - 1 if fault == "loop_short" else runs


def hidden(params, emb, config: dict, precision: Optional[str] = None,
           fault: Optional[str] = None):
    """Token vectors ``emb`` [B,T,hidden] -> the normed output of every
    run of the stack, a list of [B,T,hidden]."""
    z = dims(config)
    one = jax.checkpoint(lambda x, lay: layer(x, lay, z, precision))
    h, out = emb, []
    for _ in range(runs_of(config, fault)):
        x = h
        for lay in params["layers"]:
            x = one(x, lay)
        h = rms_norm(x, params["norm"], z["eps"])
        out.append(h)
    return out


def forward(params, emb, config: dict, precision: Optional[str] = None,
            fault: Optional[str] = None):
    """Token vectors ``emb`` [B,T,hidden] -> every exit's logits, a list
    of [B,T,vocab]."""
    return [mm(h, params["head"], "btd,dv->btv", precision)
            for h in hidden(params, emb, config, precision, fault)]


def exit_distribution(params, hs):
    """Every run's output -> p [runs, B, T]: the probability of leaving
    at each exit, float32 at the highest precision."""
    lam = [jax.nn.sigmoid(jnp.einsum("btd,d->bt", h, params["exit_w"],
                                     precision=HIGHEST) + params["exit_b"])
           for h in hs]
    p, stayed = [], jnp.ones_like(lam[0])
    for lam_r in lam[:-1]:
        p.append(lam_r * stayed)
        stayed = stayed * (1.0 - lam_r)
    return jnp.stack(p + [stayed])


def exit_losses(params, hs, labels, precision):
    """Cross-entropy of the next token at every position, an exit:
    [runs, B, T]; the logits of ``HEAD_ROWS`` positions of one exit at a
    time."""
    bsz, t = labels.shape
    rows = math.gcd(bsz * t, HEAD_ROWS)

    @jax.checkpoint
    def some_rows(xs):
        h_r, lab = xs
        logits = mm(h_r, params["head"], "td,dv->tv", precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, lab[:, None], -1)[:, 0]

    lab = labels.reshape(-1, rows)
    return jnp.stack([
        jax.lax.map(some_rows, (h.reshape(-1, rows, h.shape[-1]), lab)
                    ).reshape(bsz, t) for h in hs])


def loss(params, emb, labels, config: dict,
         precision: Optional[str] = None, fault: Optional[str] = None):
    """The expected-exit loss with its entropy term, the mean over every
    position."""
    hs = hidden(params, emb, config, precision, fault)
    p = exit_distribution(params, hs)
    ce = exit_losses(params, hs, labels, precision)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    beta = float(config["exit_entropy_beta"])
    return jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy)
