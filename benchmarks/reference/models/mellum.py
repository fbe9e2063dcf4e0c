"""Mellum 2 (``model_type`` ``mellum``): a pre-norm residual stack in
which every layer is attention then routed experts,

    x <- x + Attention_l(RMSNorm(x));  x <- x + Experts_l(RMSNorm(x))   (eps 1e-6)

then the final RMSNorm and an untied head. Plain ``jax.numpy`` in float32,
written from the published configuration
(``configs/mellum2-12b-a2.5b.json`` names it). Hidden 2304, no biases.

* **Attention**, 32 query heads on 4 key/value heads of 128 (query head h
  reads key/value head h // 8). q, k, v projections; q and k RMS-normed
  over the head with a learned weight, then rotated (rotate-half, position
  = index in the sequence); ``a_t = sum_s softmax_s(q_t . k_s / sqrt(128))
  v_s``; ``o`` projection. A layer's kind (``layer_pattern``: ``F`` full,
  ``S`` sliding; the weights of the two kinds have the same shapes, so the
  pattern and not the weights says it) sets the keys and the rotation:

  - ``F``: s <= t. RoPE by YaRN: with c(n) = D ln(L / (2 pi n)) / (2 ln
    base), low = floor(c(beta_fast)), high = ceil(c(beta_slow)), ramp_i =
    clip((i - low) / (high - low), 0, 1), pair i turns by ``t *
    ((1 - ramp_i) base^(-2i/D) + ramp_i base^(-2i/D) / factor)``, and cos
    and sin are multiplied by ``attention_factor``.
  - ``S``: t - W < s <= t, the query itself among its W = 1,024 keys. Plain
    RoPE, ``t * base^(-2i/D)``, amplitude 1.

  The softmax is dense and masked, a block of query rows at a time (an
  ``S`` layer's block against the stretch of keys that covers its rows'
  windows, so that the pass takes minutes and not more); the mask ``0 <=
  t - s < W`` is written here from the equation.
* **Experts**. p = softmax(u W_r) over all 64, float32; E = the 8 largest;
  w_e = p_e / sum_E p (``norm_topk_prob``); no bias, no scaling factor, no
  shared expert; an expert is ``(silu(u G_e) * (u U_e)) D_e``, 2304 -> 896.
  This chip holds experts ``held = (lo, hi)`` and adds only what they
  give: a loop over the held experts, each computed for every token and
  weighted by what the token's choices gave it (0 where it was not
  chosen).

Departures from the published description, each under ``assumed`` in the
configuration file: the head norms and the softmax -> top-8 -> renormalise
order are the key family's convention (the config has no key for either);
no multi-token-prediction module; positions and the window count through
the packed sequence, documents are not masked from each other.

Precision, as the configuration states it: every matrix product (``mm``)
takes operands rounded to ``precision`` (bfloat16) and accumulates in
float32; the router, the norms, the rotary embedding, the softmax and the
loss are float32. The stated product, the norm and the planted fault are
``nemotron_h.py``'s, the gated feed-forward ``lfm2.py``'s: one statement
of each for every language model. ``reference/lm.py`` hands ``loss`` the
configuration's scalars and strings only, so the file carries top-level
copies of the nested ``rope_parameters`` and a ``layer_pattern`` string.
``jax.checkpoint`` changes where memory is spent and no arithmetic.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.models.lfm2 import swiglu_mlp
from benchmarks.reference.models.nemotron_h import (HIGHEST,
                                                    drop_over_capacity, mm,
                                                    rms_norm)

#: query rows of the dense [T, T] attention computed at a time
Q_BLOCK = 512
#: the seeded token vectors' standard deviation (``init_embedding`` says
#: where it is from and why it is not the matrices' 0.02)
TOKEN_VECTOR_STD = 1.0


def dims(config: dict) -> dict:
    """The sizes the layers are written in, from the configuration's
    scalars and strings."""
    return {
        "d": int(config["hidden_size"]),
        "qh": int(config["num_attention_heads"]),
        "kvh": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "pattern": str(config["layer_pattern"]),
        "window": int(config["sliding_window"]),
        "theta": float(config["rope_theta"]),
        "yarn": {"factor": float(config["yarn_factor"]),
                 "length": int(config[
                     "yarn_original_max_position_embeddings"]),
                 "beta_fast": float(config["yarn_beta_fast"]),
                 "beta_slow": float(config["yarn_beta_slow"]),
                 "amplitude": float(config["yarn_attention_factor"])},
        "mff": int(config["moe_intermediate_size"]),
        "experts": int(config["router_outputs"]),
        "held": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "eps": float(config["rms_norm_eps"]),
        "vocab": int(config["vocab_size"]),
    }


# ---- seeded weights ------------------------------------------------------

def init(key, config: dict):
    """Weights from one key: normal(0, 0.02) matrices, the projections
    that write to the residual stream divided by sqrt(2 x layers) (two
    sublayers a layer), norms 1."""
    z = dims(config)
    d, n = z["d"], len(z["pattern"])
    if set(z["pattern"]) - set("SF"):
        raise ValueError(f"layer pattern {z['pattern']!r} is not of S, F")
    std, res = 0.02, 0.02 / math.sqrt(2 * n)
    f32 = jnp.float32

    def normal(k, shape, s):
        return jax.random.normal(k, shape, f32) * s

    layers = []
    for i in range(n):
        ks = jax.random.split(jax.random.fold_in(key, i), 8)
        layers.append({
            "attn_norm": jnp.ones((d,), f32),
            "ffn_norm": jnp.ones((d,), f32),
            "q": normal(ks[0], (d, z["qh"] * z["hd"]), std),
            "k": normal(ks[1], (d, z["kvh"] * z["hd"]), std),
            "v": normal(ks[2], (d, z["kvh"] * z["hd"]), std),
            "o": normal(ks[3], (z["qh"] * z["hd"], d), res),
            "q_norm": jnp.ones((z["hd"],), f32),
            "k_norm": jnp.ones((z["hd"],), f32),
            "router": normal(ks[4], (d, z["experts"]), std),
            "gate": normal(ks[5], (z["held"], d, z["mff"]), std),
            "up": normal(ks[6], (z["held"], d, z["mff"]), std),
            "down": normal(ks[7], (z["held"], z["mff"], d), res)})
    return {"layers": layers, "norm": jnp.ones((d,), f32),
            "head": normal(jax.random.fold_in(key, n), (d, z["vocab"]), std)}


def init_embedding(key, config: dict):
    """The token vectors [vocab, hidden] that the table's rows start
    from: normal(0, 1), from the same seed as the weights. A choice made
    for the cell's load and stated under ``assumed`` (the model's config
    has no initializer key): ``torch.nn.Embedding``'s own start (PyTorch
    documentation: "initialized from N(0, 1)"), not the matrices' 0.02.
    Every sublayer reads the stream through an RMSNorm, so what a
    sublayer adds has one size whatever the vectors' is, and an attention
    sublayer adds every position nearly the same vector (the mean of its
    context's values, which the frequent tokens set). Beside vectors of
    0.02 that common part is 94% of the stream's norm from the second
    layer on; every token of a step then sends the router the same
    input, all 16,384 choose the same 8 experts of each layer, and
    whether this chip holds them is the seed's luck (the held share of
    the choices 6.4-17.7% by seed). Beside vectors of 1 it stays under a
    sixth, a token's experts follow from the token, as a trained router's
    do, and the held experts take about their eighth on every seed."""
    z = dims(config)
    return jax.random.normal(
        jax.random.fold_in(key, 10 ** 6), (z["vocab"], z["d"]),
        jnp.float32) * TOKEN_VECTOR_STD


# ---- attention -------------------------------------------------------------

def yarn_table(z: dict):
    """YaRN's inverse frequencies [D / 2] (the equations at the top)."""
    y, hd, base = z["yarn"], z["hd"], z["theta"]

    def c(turns):
        return hd * math.log(y["length"] / (2 * math.pi * turns)) \
            / (2 * math.log(base))
    low, high = math.floor(c(y["beta_fast"])), math.ceil(c(y["beta_slow"]))
    low, high = max(low, 0), min(high, hd - 1)
    i = np.arange(hd // 2, dtype=np.float64)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = base ** (-2 * i / hd)
    return ((1 - ramp) * plain + ramp * plain / y["factor"]
            ).astype(np.float32)


def rotary(x, kind: str, z: dict):
    """Rotate-half position embedding of ``x`` [B, T, ..., D] for a layer
    of ``kind``: the pair (x_i, x_{i + D/2}) of position t turned by
    ``t * inv_freq_i``, cos and sin times the kind's amplitude."""
    t, hd = x.shape[1], x.shape[-1]
    if kind == "F":
        inv, amp = jnp.asarray(yarn_table(z)), z["yarn"]["amplitude"]
    else:
        inv = z["theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        amp = 1.0
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1).reshape(
        (1, t) + (1,) * (x.ndim - 3) + (hd,))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * (jnp.cos(ang) * amp) \
        + jnp.concatenate([-x2, x1], -1) * (jnp.sin(ang) * amp)


def attention(lay, u, kind: str, z: dict, precision):
    bsz, t, _ = u.shape
    qh, kvh, hd = z["qh"], z["kvh"], z["hd"]
    q = mm(u, lay["q"], "btd,de->bte", precision).reshape(
        bsz, t, kvh, qh // kvh, hd)
    k = mm(u, lay["k"], "btd,de->bte", precision).reshape(bsz, t, kvh, hd)
    v = mm(u, lay["v"], "btd,de->bte", precision).reshape(bsz, t, kvh, hd)
    q = rotary(rms_norm(q, lay["q_norm"], z["eps"]), kind, z)
    k = rotary(rms_norm(k, lay["k_norm"], z["eps"]), kind, z)
    blk = math.gcd(t, Q_BLOCK)
    # the keys a block of query rows is held against: every one (F), or a
    # stretch that covers the windows of all its rows (S); what each row
    # reads of them is the mask's to say
    span = min(t, blk + z["window"] - 1) if kind == "S" else t

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        start = jnp.clip((i + 1) * blk - span, 0, t - span)
        ks = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        s = mm(qi, ks, "bqkgd,bskd->bkgqs", precision) * hd ** -0.5
        back = (i * blk + jnp.arange(blk))[:, None] \
            - (start + jnp.arange(span))[None, :]                 # t - s
        mask = back >= 0
        if kind == "S":
            mask = mask & (back < z["window"])
        s = jnp.where(mask, s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vs, "bkgqs,bskd->bqkgd",
                  precision)

    out = jax.lax.map(jax.checkpoint(rows), jnp.arange(t // blk))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, qh * hd)
    return mm(out, lay["o"], "bte,ed->btd", precision)


# ---- the experts ---------------------------------------------------------------

def route(lay, u, z: dict):
    """-> (experts chosen [.., top_k], their weights [.., top_k]), float32
    at the highest precision: softmax over every expert, the ``top_k``
    largest, weights the chosen probabilities over their sum."""
    p = jax.nn.softmax(jnp.einsum("btd,de->bte", u, lay["router"],
                                  precision=HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(p, z["top_k"])
    return idx, w / jnp.sum(w, -1, keepdims=True)


def moe(lay, u, z: dict, precision, held: Tuple[int, int],
        fault: Optional[str] = None):
    idx, w = route(lay, u, z)
    if fault == "experts_dropped":
        w = drop_over_capacity(idx, w, z, 1.0)
    # one expert's 896-wide temporaries at a time
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

    def attend(x, lay, kind):
        u = rms_norm(x, lay["attn_norm"], z["eps"])
        return x + attention(lay, u, kind, z, precision)

    def ffn(x, lay):
        u = rms_norm(x, lay["ffn_norm"], z["eps"])
        return x + moe(lay, u, z, precision, held, fault)

    x = emb
    for kind, lay in zip(z["pattern"], params["layers"]):
        x = jax.checkpoint(attend, static_argnums=(2,))(x, lay, kind)
        x = jax.checkpoint(ffn)(x, lay)
    return x


def forward(params, emb, config: dict, precision: Optional[str] = None,
            fault: Optional[str] = None):
    """Token vectors ``emb`` [B,T,hidden] -> logits [B,T,vocab]."""
    x = hidden(params, emb, config, precision, fault)
    x = rms_norm(x, params["norm"], dims(config)["eps"])
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
        logits = mm(rms_norm(x_s, params["norm"], z["eps"]),
                    params["head"], "td,dv->tv", precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lab[:, None], -1))

    return jnp.sum(jax.lax.map(a_sequence, (x, labels))) / labels.size
