"""Laguna (``model_type`` ``laguna``): a pre-norm residual stack in which
every layer is gated attention then a feed-forward,

    x <- x + Attention_l(RMSNorm(x));  x <- x + FFN_l(RMSNorm(x))    (eps 1e-6)

then the final RMSNorm and an untied head. Plain ``jax.numpy`` in float32,
written from the published configuration (``configs/laguna-s-2.1.json``
names it). Hidden 3072, no biases.

* **Attention**, h_l query heads on 8 key/value heads of 128 (``layer_heads``:
  48 in a full layer, 72 in a sliding one; query head j reads key/value
  head j // (h_l / 8)). u = RMSNorm(x); q, k, v projections; q and k
  RMS-normed over the head with a learned weight, then rotated (position
  = index in the sequence); ``a_t = sum_s softmax_s(q_t . k_s / sqrt(128))
  v_s``; each head's ``a`` times its gate ``sigmoid(u W_g)_j`` (W_g
  [3072, h_l]: one scalar a head and position); ``o`` projection. A
  layer's kind (``layer_pattern``: ``F`` full, ``S`` sliding) sets the
  keys and the rotation:

  - ``F``: s <= t. On the first R = 128 x ``partial_rotary_factor`` = 64
    dimensions of a head only, YaRN's rotate-half (the pair (x_i,
    x_{i+32}) turned by ``t * inv_freq_i``, i < 32) with the table of a
    head of R: c(n) = R ln(L / (2 pi n)) / (2 ln base), low =
    floor(c(beta_fast)), high = ceil(c(beta_slow)), ramp_i = clip((i -
    low) / (high - low), 0, 1), ``inv_freq_i = (1 - ramp_i) base^(-2i/R) +
    ramp_i base^(-2i/R) / factor``, cos and sin times ``attention_factor``;
    dimensions 64..127 pass as they are.
  - ``S``: t - W < s <= t, the query itself among its W = 512 keys. Plain
    rotate-half over the whole head, ``t * base^(-2i/128)``, base 10,000.

  The softmax is dense and masked, a block of query rows at a time (an
  ``S`` layer's block against the stretch of keys that covers its rows'
  windows); the mask ``0 <= t - s < W`` is written here from the equation.
* **Feed-forward** on u = RMSNorm(x), by ``mlp_layer_pattern``: ``D`` a
  dense SwiGLU 3072 -> 12288; ``E`` p = softmax(u W_r) over all 256,
  float32; E = the 10 largest; w_e = 2.5 p_e / sum_E p
  (``norm_topk_prob``, ``moe_routed_scaling_factor``); an expert is
  ``(silu(u G_e) * (u U_e)) D_e``, 3072 -> 1024; plus ONE shared expert
  of the same form and width for every token, weight 1. This chip holds
  experts ``held = (lo, hi)`` and adds only what they give: a loop over
  the held experts, each computed for every token and weighted by what
  the token's choices gave it (0 where it was not chosen).

Departures from the published description, each under ``assumed`` in the
configuration file: the softmax -> top-10 -> renormalise -> x 2.5 router
(the config has no key for the scoring function); the gate's form and
input (head-wise, ``sigmoid`` of the normed layer input: the gated
attention paper's, arXiv:2505.06708); an ungated shared expert; the
head norms on q and k; positions and the window count through the
packed sequence, documents are not masked from each other.

Precision, as the configuration states it: every matrix product (``mm``)
takes operands rounded to ``precision`` (bfloat16) and accumulates in
float32; the router, the norms, the rotary embedding, the gate's sigmoid,
the softmax and the loss are float32. The stated product, the norm and
the planted fault are ``nemotron_h.py``'s, the gated feed-forward and the
dense layer ``lfm2.py``'s, YaRN's table ``mellum.py``'s: one statement of
each for every language model. ``reference/lm.py`` hands ``loss`` the
configuration's scalars and strings only, so the file carries top-level
copies of the nested ``rope_parameters``, a ``layer_pattern``, a
``mlp_layer_pattern`` and a ``layer_heads`` string. ``jax.checkpoint``
changes where memory is spent and no arithmetic.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference.models.lfm2 import dense_mlp, swiglu_mlp
from benchmarks.reference.models.mellum import yarn_table
from benchmarks.reference.models.nemotron_h import (HIGHEST,
                                                    drop_over_capacity, mm,
                                                    rms_norm)

#: query rows of the dense [T, T] attention computed at a time
Q_BLOCK = 512
#: the seeded token vectors' standard deviation (as ``mellum.py``'s, for
#: the reason its ``init_embedding`` gives)
TOKEN_VECTOR_STD = 1.0


def dims(config: dict) -> dict:
    """The sizes the layers are written in, from the configuration's
    scalars and strings."""
    return {
        "d": int(config["hidden_size"]),
        "heads": [int(h) for h in str(config["layer_heads"]).split(",")],
        "kvh": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "pattern": str(config["layer_pattern"]),
        "mlp": str(config["mlp_layer_pattern"]),
        "window": int(config["sliding_window"]),
        "theta": float(config["sliding_rope_theta"]),
        "yarn": {"theta": float(config["yarn_rope_theta"]),
                 "factor": float(config["yarn_factor"]),
                 "length": int(config[
                     "yarn_original_max_position_embeddings"]),
                 "beta_fast": float(config["yarn_beta_fast"]),
                 "beta_slow": float(config["yarn_beta_slow"]),
                 "amplitude": float(config["yarn_attention_factor"]),
                 "partial": float(config["yarn_partial_rotary_factor"])},
        "ff": int(config["intermediate_size"]),
        "mff": int(config["moe_intermediate_size"]),
        "sff": int(config["shared_expert_intermediate_size"]),
        "experts": int(config["router_outputs"]),
        "held": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["moe_routed_scaling_factor"]),
        "eps": float(config["rms_norm_eps"]),
        "vocab": int(config["vocab_size"]),
    }


# ---- seeded weights ------------------------------------------------------

def init(key, config: dict):
    """Weights from one key: normal(0, 0.02) matrices (the gate's too),
    the projections that write to the residual stream divided by sqrt(2 x
    layers) (two sublayers a layer), norms 1."""
    z = dims(config)
    d, hd, pat = z["d"], z["hd"], z["pattern"]
    if set(pat) - set("SF") or set(z["mlp"]) - set("DE"):
        raise ValueError(f"layer patterns {pat!r}, {z['mlp']!r} are not of "
                         f"S, F and D, E")
    if not len(pat) == len(z["mlp"]) == len(z["heads"]):
        raise ValueError("one kind, one feed-forward and one head count a "
                         "layer")
    std, res = 0.02, 0.02 / math.sqrt(2 * len(pat))
    f32 = jnp.float32

    def normal(k, shape, s):
        return jax.random.normal(k, shape, f32) * s

    layers = []
    for i, (qh, mlp) in enumerate(zip(z["heads"], z["mlp"])):
        ks = jax.random.split(jax.random.fold_in(key, i), 16)
        lay = {"attn_norm": jnp.ones((d,), f32),
               "ffn_norm": jnp.ones((d,), f32),
               "q": normal(ks[0], (d, qh * hd), std),
               "k": normal(ks[1], (d, z["kvh"] * hd), std),
               "v": normal(ks[2], (d, z["kvh"] * hd), std),
               "o": normal(ks[3], (qh * hd, d), res),
               "q_norm": jnp.ones((hd,), f32),
               "k_norm": jnp.ones((hd,), f32),
               "attn_gate": normal(ks[4], (d, qh), std)}
        if mlp == "D":
            lay.update({"w1": normal(ks[5], (d, z["ff"]), std),
                        "w3": normal(ks[6], (d, z["ff"]), std),
                        "w2": normal(ks[7], (z["ff"], d), res)})
        else:
            lay.update({
                "router": normal(ks[8], (d, z["experts"]), std),
                "gate": normal(ks[9], (z["held"], d, z["mff"]), std),
                "up": normal(ks[10], (z["held"], d, z["mff"]), std),
                "down": normal(ks[11], (z["held"], z["mff"], d), res),
                "shared_gate": normal(ks[12], (d, z["sff"]), std),
                "shared_up": normal(ks[13], (d, z["sff"]), std),
                "shared_down": normal(ks[14], (z["sff"], d), res)})
        layers.append(lay)
    return {"layers": layers, "norm": jnp.ones((d,), f32),
            "head": normal(jax.random.fold_in(key, len(pat)),
                           (d, z["vocab"]), std)}


def init_embedding(key, config: dict):
    """The token vectors [vocab, hidden] that the table's rows start
    from: normal(0, 1), from the same seed as the weights (``mellum.py``'s
    ``init_embedding`` says why not the matrices' 0.02)."""
    z = dims(config)
    return jax.random.normal(
        jax.random.fold_in(key, 10 ** 6), (z["vocab"], z["d"]),
        jnp.float32) * TOKEN_VECTOR_STD


# ---- attention -------------------------------------------------------------

def rotary(x, kind: str, z: dict):
    """The position embedding of ``x`` [B, T, ..., D] for a layer of
    ``kind`` (the equations at the top): rotate-half over the first R
    dimensions, cos and sin times the kind's amplitude, the rest as it
    is."""
    t, hd = x.shape[1], x.shape[-1]
    if kind == "F":
        y = z["yarn"]
        rot = int(hd * y["partial"])
        inv = jnp.asarray(yarn_table({"hd": rot, "theta": y["theta"],
                                      "yarn": y}))
        amp = y["amplitude"]
    else:
        rot = hd
        inv = z["theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        amp = 1.0
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1).reshape(
        (1, t) + (1,) * (x.ndim - 3) + (rot,))
    xr, rest = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    turned = xr * (jnp.cos(ang) * amp) \
        + jnp.concatenate([-x2, x1], -1) * (jnp.sin(ang) * amp)
    return jnp.concatenate([turned, rest], -1)


def attention(lay, u, kind: str, qh: int, z: dict, precision):
    bsz, t, _ = u.shape
    kvh, hd = z["kvh"], z["hd"]
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
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, qh, hd)
    gate = jax.nn.sigmoid(mm(u, lay["attn_gate"], "btd,dh->bth", precision))
    out = (out * gate[..., None]).reshape(bsz, t, qh * hd)
    return mm(out, lay["o"], "bte,ed->btd", precision)


# ---- the experts: routed (the chip's share) and shared -------------------------

def route(lay, u, z: dict):
    """-> (experts chosen [.., top_k], their weights [.., top_k]), float32
    at the highest precision: softmax over every expert, the ``top_k``
    largest, weights the chosen probabilities over their sum, times the
    scaling factor."""
    p = jax.nn.softmax(jnp.einsum("btd,de->bte", u, lay["router"],
                                  precision=HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(p, z["top_k"])
    return idx, w / jnp.sum(w, -1, keepdims=True) * z["scale"]


def moe(lay, u, z: dict, precision, held: Tuple[int, int],
        fault: Optional[str] = None):
    idx, w = route(lay, u, z)
    if fault == "experts_dropped":
        w = drop_over_capacity(idx, w, z, 1.0)
    # one expert's 1024-wide temporaries at a time
    expert = jax.checkpoint(swiglu_mlp, static_argnums=(4,))
    y = expert(u, lay["shared_gate"], lay["shared_up"], lay["shared_down"],
               precision)
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

    def attend(x, lay, kind, qh):
        u = rms_norm(x, lay["attn_norm"], z["eps"])
        return x + attention(lay, u, kind, qh, z, precision)

    def ffn(x, lay, dense):
        u = rms_norm(x, lay["ffn_norm"], z["eps"])
        if dense:
            return x + dense_mlp(lay, u, precision)
        return x + moe(lay, u, z, precision, held, fault)

    x = emb
    for kind, qh, mlp, lay in zip(z["pattern"], z["heads"], z["mlp"],
                                  params["layers"]):
        x = jax.checkpoint(attend, static_argnums=(2, 3))(x, lay, kind, qh)
        x = jax.checkpoint(ffn, static_argnums=(2,))(x, lay, mlp == "D")
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
