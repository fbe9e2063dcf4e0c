"""Kimi Linear (``model_type`` ``kimi_linear``): a pre-norm residual stack
in which every layer is a mixer then a feed-forward,

    x <- x + Mixer_l(RMSNorm(x));  x <- x + FFN_l(RMSNorm(x))    (eps 1e-5)

then the final RMSNorm and an untied head. Plain ``jax.numpy`` in float32,
written from the published configuration
(``configs/kimi-linear-48b-a3b.json`` names it), the Kimi Linear report
(arXiv:2510.26692) and the public ``flash-linear-attention`` KDA layer.
Hidden 2304, no biases but the output gate's.

* **KDA** (``layer_pattern`` ``K``), 32 heads of 128: q, k, v = SiLU(causal
  depthwise conv of 4 taps(x W)), one conv each, no bias; q and k divided
  by their L2 norm over the head (``sqrt(sum x^2 + 1e-6)``), q times
  128^-0.5; the log decay of every key channel ``g = -exp(A_log_h)
  softplus(x W_f1 W_f2 + dt_bias)`` (2304 -> 128 -> 4096); ``beta =
  sigmoid(x W_b)`` a head; then per head, STEP BY STEP over t,

      S_t = Diag(exp g_t) S_{t-1};  S_t += beta_t k_t (v_t - S_t^T k_t)^T;  o_t = S_t^T q_t

  from S = 0 at the sequence's start; o is RMS-normed over the head,
  times its weight and ``sigmoid(x W_g1 W_g2 + b_g)`` (2304 -> 128 ->
  4096), then ``o`` (4096 -> 2304).
* **MLA** (``M``), 32 heads, no query latent, no rotary part: ``q = x
  W_q`` (heads of 128 + 64); ``[c | k_pe] = x W_kva`` (512 + 64), c
  RMS-normed; ``[k_nope | v] = c W_kvb`` (heads of 128 + 128); a head's
  key is ``[k_nope ; k_pe]``, k_pe the same for every head; ``a_t =
  sum_{s<=t} softmax_s(q_t . k_s / sqrt(192)) v_s``; ``o`` (4096 -> 2304).
* **FFN**: layer 0 (``first_k_dense_replace`` 1) a dense SwiGLU 2304 ->
  9216; every other layer s = sigmoid(u W_r) over all 256 experts in
  float32, the 8 largest by s + bias (the correction, which only
  chooses), weights the chosen s over their sum times 2.446; an expert is
  ``(silu(u G_e) * (u U_e)) D_e``, 2304 -> 1024; plus ONE shared expert
  of the same form and width for every token. This chip holds experts
  ``held = (lo, hi)`` and adds only what they give: a loop over the held
  experts, each computed for every token and weighted by what the
  token's choices gave it (0 where it was not chosen).

Departures and choices, each under ``assumed`` in the configuration
file: the initial ``A_log``, ``dt_bias`` and correction bias; the output
gate's bias; the latent norm's eps; no document mask and no state reset
at a document's start.

Precision, as the configuration states it: every matrix product (``mm``)
takes operands rounded to ``precision`` (bfloat16) and accumulates in
float32; the router, the convs, the decay, beta, the recurrence, the
norms, the softmax and the loss are float32. The stated product, the norm
and the planted fault are ``nemotron_h.py``'s, the dense feed-forward
and the gated expert ``lfm2.py``'s: one statement of each for every
language model. ``reference/lm.py`` hands ``loss`` the configuration's
scalars and strings only, so the file carries top-level copies of
``linear_attn_config``'s numbers and a ``layer_pattern`` string.
``jax.checkpoint`` changes where memory is spent and no arithmetic.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference.models.lfm2 import dense_mlp, swiglu_mlp
from benchmarks.reference.models.nemotron_h import (HIGHEST,
                                                    drop_over_capacity, mm,
                                                    rms_norm)

#: query rows of the dense [T, T] attention computed at a time
Q_BLOCK = 512
#: steps of the recurrence whose states are kept at a time
SCAN_STRETCH = 64
#: the seeded token vectors' standard deviation (as ``mellum.py``'s, for
#: the reason its ``init_embedding`` gives)
TOKEN_VECTOR_STD = 1.0


def dims(config: dict) -> dict:
    """The sizes the layers are written in, from the configuration's
    scalars and strings."""
    return {
        "d": int(config["hidden_size"]),
        "pattern": str(config["layer_pattern"]),
        "kh": int(config["linear_attn_num_heads"]),
        "kd": int(config["linear_attn_head_dim"]),
        "conv": int(config["linear_attn_short_conv_kernel_size"]),
        "qh": int(config["num_attention_heads"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "vd": int(config["v_head_dim"]),
        "rank": int(config["kv_lora_rank"]),
        "dense": int(config["first_k_dense_replace"]),
        "ff": int(config["intermediate_size"]),
        "mff": int(config["moe_intermediate_size"]),
        "sff": int(config["moe_intermediate_size"])
        * int(config["num_shared_experts"]),
        "experts": int(config["router_outputs"]),
        "held": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_token"]),
        "scale": float(config["routed_scaling_factor"]),
        "eps": float(config["rms_norm_eps"]),
        "vocab": int(config["vocab_size"]),
    }


# ---- seeded weights ------------------------------------------------------

def init(key, config: dict):
    """Weights from one key: normal(0, 0.02) matrices, the projections
    that write to the residual stream divided by sqrt(2 x layers) (two
    sublayers a layer), norms 1, conv taps U(+-4^-0.5) (a depthwise
    ``Conv1d``'s own), ``A_log`` = log U(1, 16) and ``dt_bias`` =
    softplus^-1 of a log-uniform step in [0.001, 0.1] floored at 1e-4
    (the public KDA layer's starts), the output gate's bias 0, the
    router's correction bias a seeded constant normal(0, 0.01) (it gets
    no gradient)."""
    z = dims(config)
    d, pat = z["d"], z["pattern"]
    if set(pat) - set("KM"):
        raise ValueError(f"layer pattern {pat!r} is not of K, M")
    std, res = 0.02, 0.02 / math.sqrt(2 * len(pat))
    f32 = jnp.float32
    hk = z["kh"] * z["kd"]

    def normal(k, shape, s):
        return jax.random.normal(k, shape, f32) * s

    layers = []
    for i, kind in enumerate(pat):
        ks = jax.random.split(jax.random.fold_in(key, i), 16)
        lay = {"attn_norm": jnp.ones((d,), f32),
               "ffn_norm": jnp.ones((d,), f32)}
        if kind == "K":
            bound = z["conv"] ** -0.5
            dt = jnp.exp(jax.random.uniform(ks[9], (hk,), f32)
                         * (math.log(0.1) - math.log(0.001))
                         + math.log(0.001))
            dt = jnp.maximum(dt, 1e-4)
            lay.update({
                "q": normal(ks[0], (d, hk), std),
                "k": normal(ks[1], (d, hk), std),
                "v": normal(ks[2], (d, hk), std),
                "q_conv": jax.random.uniform(ks[3], (z["conv"], hk), f32,
                                             -bound, bound),
                "k_conv": jax.random.uniform(ks[4], (z["conv"], hk), f32,
                                             -bound, bound),
                "v_conv": jax.random.uniform(ks[5], (z["conv"], hk), f32,
                                             -bound, bound),
                "f_a": normal(ks[6], (d, z["kd"]), std),
                "f_b": normal(ks[7], (z["kd"], hk), std),
                "A_log": jnp.log(jax.random.uniform(ks[8], (z["kh"],), f32,
                                                    1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "b": normal(ks[10], (d, z["kh"]), std),
                "g_a": normal(ks[11], (d, z["kd"]), std),
                "g_b": normal(ks[12], (z["kd"], hk), std),
                "g_bias": jnp.zeros((hk,), f32),
                "o_norm": jnp.ones((z["kd"],), f32),
                "o": normal(ks[13], (hk, d), res)})
        else:
            lay.update({
                "q": normal(ks[0], (d, z["qh"] * (z["nope"] + z["rope"])),
                            std),
                "kv_a": normal(ks[1], (d, z["rank"] + z["rope"]), std),
                "kv_norm": jnp.ones((z["rank"],), f32),
                "kv_b": normal(ks[2], (z["rank"],
                                       z["qh"] * (z["nope"] + z["vd"])), std),
                "o": normal(ks[3], (z["qh"] * z["vd"], d), res)})
        ks = jax.random.split(jax.random.fold_in(key, 1000 + i), 8)
        if i < z["dense"]:
            lay.update({"w1": normal(ks[0], (d, z["ff"]), std),
                        "w3": normal(ks[1], (d, z["ff"]), std),
                        "w2": normal(ks[2], (z["ff"], d), res)})
        else:
            lay.update({
                "router": normal(ks[0], (d, z["experts"]), std),
                "router_bias": normal(ks[7], (z["experts"],), 0.01),
                "gate": normal(ks[1], (z["held"], d, z["mff"]), std),
                "up": normal(ks[2], (z["held"], d, z["mff"]), std),
                "down": normal(ks[3], (z["held"], z["mff"], d), res),
                "shared_gate": normal(ks[4], (d, z["sff"]), std),
                "shared_up": normal(ks[5], (d, z["sff"]), std),
                "shared_down": normal(ks[6], (z["sff"], d), res)})
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


# ---- K: Kimi Delta Attention ------------------------------------------------

def recurrence(q, k, v, g, beta):
    """The gated delta rule, one step at a time, float32 at the highest
    precision: q, k, g [B,T,H,K], v [B,T,H,V], beta [B,T,H] -> o
    [B,T,H,V]."""
    bsz, t, h, kd = q.shape

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s                     # [B,H,K,V]
        pred = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=HIGHEST)
        s = s + (b_t[..., None] * k_t)[..., :, None] \
            * (v_t - pred)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HIGHEST)

    stretch = math.gcd(t, SCAN_STRETCH)

    @jax.checkpoint
    def run_stretch(s, xs):
        return jax.lax.scan(step, s, xs)

    def time_major(x):
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((t // stretch, stretch) + x.shape[1:])

    s0 = jnp.zeros((bsz, h, kd, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(run_stretch, s0,
                        tuple(time_major(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def kda_mixer(lay, u, z: dict, precision):
    bsz, t, _ = u.shape
    h, kd = z["kh"], z["kd"]

    def proj(name):
        return mm(u, lay[name], "btd,de->bte", precision)

    def conv(x, taps):
        # causal depthwise conv: position t reads t-3 .. t
        pad = jnp.pad(x, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
        y = sum(pad[:, j:j + t] * taps[j] for j in range(taps.shape[0]))
        return jax.nn.silu(y).reshape(bsz, t, h, kd)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2(conv(proj("q"), lay["q_conv"])) * kd ** -0.5
    k = l2(conv(proj("k"), lay["k_conv"]))
    v = conv(proj("v"), lay["v_conv"])
    f = mm(mm(u, lay["f_a"], "btd,dr->btr", precision), lay["f_b"],
           "btr,re->bte", precision)
    g = -jnp.exp(lay["A_log"])[:, None] * jax.nn.softplus(
        (f + lay["dt_bias"]).reshape(bsz, t, h, kd))
    beta = jax.nn.sigmoid(mm(u, lay["b"], "btd,dh->bth", precision))
    o = recurrence(q, k, v, g, beta)
    gate = mm(mm(u, lay["g_a"], "btd,dr->btr", precision), lay["g_b"],
              "btr,re->bte", precision) + lay["g_bias"]
    o = rms_norm(o, lay["o_norm"], z["eps"]) \
        * jax.nn.sigmoid(gate.reshape(bsz, t, h, kd))
    return mm(o.reshape(bsz, t, h * kd), lay["o"], "bte,ed->btd", precision)


# ---- M: latent attention without positions --------------------------------

def mla(lay, u, z: dict, precision):
    bsz, t, _ = u.shape
    qh, nope, rope, vd = z["qh"], z["nope"], z["rope"], z["vd"]
    q = mm(u, lay["q"], "btd,de->bte", precision).reshape(
        bsz, t, qh, nope + rope)
    ckv = mm(u, lay["kv_a"], "btd,de->bte", precision)
    c, k_pe = ckv[..., :z["rank"]], ckv[..., z["rank"]:]
    kv = mm(rms_norm(c, lay["kv_norm"], z["eps"]), lay["kv_b"],
            "btr,re->bte", precision).reshape(bsz, t, qh, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    blk = math.gcd(t, Q_BLOCK)
    pos_k = jnp.arange(t)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        # q . [k_nope ; k_pe] = q_nope . k_nope + q_pe . k_pe
        s = mm(qi[..., :nope], k_nope, "bqhd,bshd->bhqs", precision) \
            + mm(qi[..., nope:], k_pe, "bqhd,bsd->bhqs", precision)
        s = s * (nope + rope) ** -0.5
        mask = (i * blk + jnp.arange(blk))[:, None] >= pos_k[None, :]
        s = jnp.where(mask, s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v, "bhqs,bshd->bqhd",
                  precision)

    out = jax.lax.map(jax.checkpoint(rows), jnp.arange(t // blk))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, t, qh * vd)
    return mm(out, lay["o"], "bte,ed->btd", precision)


# ---- the experts: routed (the chip's share) and shared -------------------------

def route(lay, u, z: dict):
    """-> (experts chosen [.., top_k], their weights [.., top_k]), float32
    at the highest precision: ``sigmoid`` scores, choice by score +
    bias, weights the chosen scores over their sum, times the scaling
    factor."""
    s = jax.nn.sigmoid(jnp.einsum("btd,de->bte", u, lay["router"],
                                  precision=HIGHEST))
    bias = jax.lax.stop_gradient(lay["router_bias"])
    _, idx = jax.lax.top_k(s + bias, z["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
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

    def mix(x, lay, kind):
        u = rms_norm(x, lay["attn_norm"], z["eps"])
        if kind == "M":
            return x + mla(lay, u, z, precision)
        # one sequence at a time: the mixer's temporaries are the widest
        one = jax.checkpoint(
            lambda u_s: kda_mixer(lay, u_s[None], z, precision)[0])
        return x + jax.lax.map(one, u)

    def ffn(x, lay, dense):
        u = rms_norm(x, lay["ffn_norm"], z["eps"])
        if dense:
            return x + dense_mlp(lay, u, precision)
        return x + moe(lay, u, z, precision, held, fault)

    x = emb
    for i, (kind, lay) in enumerate(zip(z["pattern"], params["layers"])):
        x = jax.checkpoint(mix, static_argnums=(2,))(x, lay, kind)
        x = jax.checkpoint(ffn, static_argnums=(2,))(x, lay, i < z["dense"])
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
