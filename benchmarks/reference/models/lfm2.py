"""LFM2-MoE (``model_type`` ``lfm2_moe``): a pre-norm residual stack in
which every layer is TWO sublayers, an operator and a feed-forward,

    x <- x + Operator_l(RMSNorm(x));  x <- x + FFN_l(RMSNorm(x))   (eps 1e-5)

then ``embedding_norm`` and an untied head. Plain ``jax.numpy`` in
float32, written from the published configuration
(``configs/lfm2-24b-a2b.json`` names it). A layer's weights say what it
is (``init`` builds them from ``layer_types`` and ``num_dense_layers``):

* ``in_proj``: the **gated short convolution**. ``[B | C | v] = u W_in``
  (hidden -> 3 x hidden); ``z_t = sum_j w_j * (B * v)_{t-(L-1)+j}``, a
  causal depthwise convolution over ``conv_L_cache`` = L positions, zero
  before the sequence, no bias, written as L shifted products;
  ``out = (C * z) W_out``.
* ``q``: **causal grouped-query attention**. q, k, v projections; q and k
  RMS-normed over the head dimension with a learned weight, then rotated
  (rotate-half, ``rope_theta``, position = index in the sequence);
  softmax(q k^T * head_dim^-0.5) v under the causal mask, every key/value
  head shared by H / KV query heads; ``o`` projection.
* ``w1``: the **dense SwiGLU** feed-forward of the leading
  ``num_dense_layers`` layers, ``(silu(u W_1) * (u W_3)) W_2``.
* ``router``: the **expert** feed-forward. s = sigmoid(u W_r) in float32;
  the ``top_k`` experts with the largest s + ``expert_bias`` (the bias
  only chooses); weights = the chosen s over (their sum + 1e-6), times
  ``routed_scaling_factor``; an expert is ``(silu(u G_e) * (u U_e)) D_e``.
  This chip holds experts ``held = (lo, hi)`` and adds only what they
  give: a loop over the held experts, each computed for every token and
  weighted by what the token's choices gave it (0 where it was not
  chosen). There is no shared expert.

Precision, as the configuration states it: every matrix product (``mm``)
takes operands rounded to ``precision`` (bfloat16) and accumulates in
float32; the router, the convolution and its gates, the norms, the rotary
embedding, the softmax and the loss are float32. The stated product, the
norm and the planted fault are the sibling reference's
(``nemotron_h.py``): one statement of each for every language model.
``jax.checkpoint`` changes where memory is spent and no arithmetic.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference.models.nemotron_h import (HIGHEST,
                                                    drop_over_capacity, mm,
                                                    rms_norm)

#: query rows of the dense [T, T] attention computed at a time
Q_BLOCK = 512
#: positions of the dense feed-forward computed at a time
MLP_ROWS = 4096


def dims(config: dict) -> dict:
    """The sizes the layers are written in, from the configuration's
    scalars (``reference/lm.py`` hands ``loss`` nothing else: the rotary
    base is read from the top-level ``rope_theta``, the nested group's
    number again)."""
    d, qh = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {
        "d": d, "qh": qh, "kvh": int(config["num_key_value_heads"]),
        "hd": d // qh, "theta": float(config["rope_theta"]),
        "k": int(config["conv_L_cache"]),
        "ff": int(config["intermediate_size"]),
        "mff": int(config["moe_intermediate_size"]),
        "experts": int(config["router_outputs"]),
        "held": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "eps": float(config["norm_eps"]),
        "vocab": int(config["vocab_size"]),
    }


# ---- seeded weights ------------------------------------------------------

def init(key, config: dict):
    """Weights from one key: normal(0, 0.02) matrices, the projections
    that write to the residual stream divided by sqrt(2 x layers) (two
    sublayers a layer), norms 1, conv taps U(+-L^-0.5); the router's
    ``expert_bias`` a seeded constant (it gets no gradient)."""
    z = dims(config)
    d, kinds = z["d"], list(config["layer_types"])
    std, res = 0.02, 0.02 / math.sqrt(2 * len(kinds))
    f32 = jnp.float32

    def normal(k, shape, s):
        return jax.random.normal(k, shape, f32) * s

    layers = []
    for i, kind in enumerate(kinds):
        ks = jax.random.split(jax.random.fold_in(key, i), 10)
        lay = {"operator_norm": jnp.ones((d,), f32),
               "ffn_norm": jnp.ones((d,), f32)}
        if kind == "conv":
            bound = z["k"] ** -0.5
            lay.update(
                in_proj=normal(ks[0], (d, 3 * d), std),
                conv_w=jax.random.uniform(ks[1], (z["k"], d), f32, -bound,
                                          bound),
                out_proj=normal(ks[2], (d, d), res))
        elif kind == "full_attention":
            lay.update(
                q=normal(ks[0], (d, z["qh"] * z["hd"]), std),
                k=normal(ks[1], (d, z["kvh"] * z["hd"]), std),
                v=normal(ks[2], (d, z["kvh"] * z["hd"]), std),
                o=normal(ks[3], (z["qh"] * z["hd"], d), res),
                q_norm=jnp.ones((z["hd"],), f32),
                k_norm=jnp.ones((z["hd"],), f32))
        else:
            raise ValueError(f"layer type {kind!r} is not conv or "
                             f"full_attention")
        if i < int(config["num_dense_layers"]):
            lay.update(w1=normal(ks[4], (d, z["ff"]), std),
                       w3=normal(ks[5], (d, z["ff"]), std),
                       w2=normal(ks[6], (z["ff"], d), res))
        else:
            lay.update(
                router=normal(ks[4], (d, z["experts"]), std),
                expert_bias=normal(ks[5], (z["experts"],), 0.01),
                gate=normal(ks[6], (z["held"], d, z["mff"]), std),
                up=normal(ks[7], (z["held"], d, z["mff"]), std),
                down=normal(ks[8], (z["held"], z["mff"], d), res))
        layers.append(lay)
    kh = jax.random.fold_in(key, len(kinds))
    return {"layers": layers, "embedding_norm": jnp.ones((d,), f32),
            "head": normal(kh, (d, z["vocab"]), std)}


def init_embedding(key, config: dict):
    """The token vectors [vocab, hidden] that the table's rows start
    from: normal(0, 0.02), from the same seed as the weights."""
    z = dims(config)
    return jax.random.normal(jax.random.fold_in(key, 10 ** 6),
                             (z["vocab"], z["d"]), jnp.float32) * 0.02


# ---- operators -------------------------------------------------------------

def short_conv(lay, u, z: dict, precision):
    t = u.shape[1]
    b, c, v = jnp.split(mm(u, lay["in_proj"], "btd,de->bte", precision), 3,
                        axis=-1)
    # causal depthwise conv: position t reads t-L+1 .. t
    pad = jnp.pad(b * v, ((0, 0), (z["k"] - 1, 0), (0, 0)))
    conv = sum(pad[:, j:j + t] * lay["conv_w"][j] for j in range(z["k"]))
    return mm(c * conv, lay["out_proj"], "btd,de->bte", precision)


def rotary(x, theta: float):
    """Rotate-half position embedding of ``x`` [B, T, ..., D]: the pair
    (x_i, x_{i + D/2}) turned by the angle t * theta^(-2i / D)."""
    t, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1).reshape(
        (1, t) + (1,) * (x.ndim - 3) + (hd,))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention(lay, u, z: dict, precision):
    bsz, t, _ = u.shape
    qh, kvh, hd = z["qh"], z["kvh"], z["hd"]
    q = mm(u, lay["q"], "btd,de->bte", precision).reshape(
        bsz, t, kvh, qh // kvh, hd)
    k = mm(u, lay["k"], "btd,de->bte", precision).reshape(bsz, t, kvh, hd)
    v = mm(u, lay["v"], "btd,de->bte", precision).reshape(bsz, t, kvh, hd)
    q = rotary(rms_norm(q, lay["q_norm"], z["eps"]), z["theta"])
    k = rotary(rms_norm(k, lay["k_norm"], z["eps"]), z["theta"])
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


# ---- feed-forwards -----------------------------------------------------------

def swiglu_mlp(x, gate, up, down, precision):
    hid = jax.nn.silu(mm(x, gate, "btd,df->btf", precision)) \
        * mm(x, up, "btd,df->btf", precision)
    return mm(hid, down, "btf,fd->btd", precision)


def dense_mlp(lay, u, precision):
    """The leading layers' feed-forward, ``MLP_ROWS`` positions at a time
    (its hidden activation is 11,776 wide)."""
    bsz, t, d = u.shape
    rows = math.gcd(bsz * t, MLP_ROWS)
    slabs = u.reshape(bsz * t // rows, 1, rows, d)
    out = jax.lax.map(jax.checkpoint(lambda s: swiglu_mlp(
        s, lay["w1"], lay["w3"], lay["w2"], precision)), slabs)
    return out.reshape(bsz, t, d)


def route(lay, u, z: dict):
    """-> (experts chosen [.., top_k], their weights [.., top_k]), float32
    at the highest precision: ``sigmoid`` scores, choice by score +
    ``expert_bias``, weights the chosen scores over (their sum + 1e-6),
    times the scaling factor."""
    s = jax.nn.sigmoid(jnp.einsum("btd,de->bte", u, lay["router"],
                                  precision=HIGHEST))
    bias = jax.lax.stop_gradient(lay["expert_bias"])
    _, idx = jax.lax.top_k(s + bias, z["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / (jnp.sum(w, -1, keepdims=True) + 1e-6) * z["scale"]


def moe(lay, u, z: dict, precision, held: Tuple[int, int],
        fault: Optional[str] = None):
    idx, w = route(lay, u, z)
    if fault == "experts_dropped":
        w = drop_over_capacity(idx, w, z, 1.0)
    # one expert's 1,536-wide temporaries at a time
    expert = jax.checkpoint(swiglu_mlp, static_argnums=(4,))
    y = jnp.zeros_like(u)
    for j, e in enumerate(range(*held)):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1)       # [B,T]
        y = y + w_e[..., None] * expert(
            u, lay["gate"][j], lay["up"][j], lay["down"][j], precision)
    return y


# ---- the stack ---------------------------------------------------------------

def held_experts(config: dict) -> Tuple[int, int]:
    lo = int(config.get("first_expert_held", 0))
    return lo, lo + int(config["num_experts"])


def hidden(params, emb, config: dict, precision: Optional[str] = None,
           fault: Optional[str] = None):
    """Token vectors ``emb`` [B,T,hidden] -> the last layer's output."""
    z = dims(config)
    held = held_experts(config)

    def operator(x, lay):
        u = rms_norm(x, lay["operator_norm"], z["eps"])
        if "in_proj" in lay:
            return x + short_conv(lay, u, z, precision)
        return x + attention(lay, u, z, precision)

    def ffn(x, lay):
        u = rms_norm(x, lay["ffn_norm"], z["eps"])
        if "w1" in lay:
            return x + dense_mlp(lay, u, precision)
        return x + moe(lay, u, z, precision, held, fault)

    x = emb
    for lay in params["layers"]:
        x = jax.checkpoint(operator)(x, lay)
        x = jax.checkpoint(ffn)(x, lay)
    return x


def forward(params, emb, config: dict, precision: Optional[str] = None,
            fault: Optional[str] = None):
    """Token vectors ``emb`` [B,T,hidden] -> logits [B,T,vocab]."""
    x = hidden(params, emb, config, precision, fault)
    x = rms_norm(x, params["embedding_norm"], dims(config)["eps"])
    return mm(x, params["head"], "btd,dv->btv", precision)


def loss(params, emb, labels, config: dict,
         precision: Optional[str] = None, fault: Optional[str] = None):
    """Mean cross-entropy of the next token over every position; the
    logits of one sequence at a time."""
    z = dims(config)
    x = hidden(params, emb, config, precision, fault)

    @jax.checkpoint
    def a_sequence(xs):
        x_s, lab = xs
        logits = mm(rms_norm(x_s, params["embedding_norm"], z["eps"]),
                    params["head"], "td,dv->tv", precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lab[:, None], -1))

    return jnp.sum(jax.lax.map(a_sequence, (x, labels))) / labels.size
