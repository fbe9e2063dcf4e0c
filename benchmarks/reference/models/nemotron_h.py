"""Nemotron-H (``model_type`` ``nemotron_h``): a pre-norm residual stack
in which every layer is ONE mixer, chosen by a pattern string: ``M`` a
Mamba-2 mixer, ``*`` causal grouped-query attention, ``E`` a mixture of
routed experts beside one shared expert; then a final RMSNorm and an
untied head. Plain ``jax.numpy`` in float32, written from the published
configuration (``configs/nemotron3-nano-30b-a3b.json`` names it):

    x <- x + Mixer_l(RMSNorm_l(x))          (eps 1e-5)

* ``M``: ``in_proj`` -> z | xBC | dt; xBC <- silu(causal depthwise
  conv1d(xBC) + bias), split into x [T,H,P], B [T,G,N], C [T,G,N] (head
  h reads group h // (H / G)); dt <- softplus(dt + dt_bias); A =
  -exp(A_log); the recurrence STEP BY STEP over t,
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D
  x_t``; y <- RMSNorm over each group of d_inner / G channels of
  (y * silu(z)), times a weight; ``out_proj``.
* ``*``: q, k, v projections, causal softmax(q k^T * head_dim^-0.5) v
  with every key/value head shared by H / KV query heads, ``o``
  projection. No position embedding (the configuration's ``assumed``).
* ``E``: s = sigmoid(x W_r) in float32; the ``top_k`` experts with the
  largest s + bias; weights = the chosen s over their sum, times
  ``routed_scaling_factor``; an expert is down(relu(up(x))^2). This chip
  holds experts ``held = (lo, hi)`` and adds only what they give: a
  loop over the held experts, each computed for every token and weighted
  by what the token's choices gave it (0 where it was not chosen). The
  shared expert is added for every token.

Precision, as the configuration states it: every matrix product (``mm``)
takes operands rounded to ``precision`` (bfloat16) and accumulates in
float32; the router, the recurrence, the norms, the softmax and the loss
are float32. The control states one precision lower for the operands
and keeps the cotangents where they were.
``jax.checkpoint`` around a layer, and around a stretch of the
recurrence, changes where memory is spent and no arithmetic.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: cotangents stay in the precision the configuration states
GRAD_DTYPE = "bfloat16"
#: query rows of the dense [T, T] attention computed at a time
Q_BLOCK = 512
#: steps of the recurrence whose states are kept at a time
SCAN_STRETCH = 128


def dims(config: dict) -> dict:
    """The sizes the layers are written in, from the configuration."""
    h, p = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    g, n = int(config["n_groups"]), int(config["ssm_state_size"])
    return {
        "d": int(config["hidden_size"]), "h": h, "p": p, "g": g, "n": n,
        "di": h * p, "conv": h * p + 2 * g * n,
        "k": int(config["conv_kernel"]),
        "qh": int(config["num_attention_heads"]),
        "kvh": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "ff": int(config["moe_intermediate_size"]),
        "sff": int(config["moe_shared_expert_intermediate_size"]),
        "experts": int(config["router_outputs"]),
        "held": int(config["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "eps": float(config["layer_norm_epsilon"]),
        "vocab": int(config["vocab_size"]),
        "pattern": str(config["hybrid_override_pattern"]),
    }


# ---- seeded weights ------------------------------------------------------

def init(key, config: dict):
    """Weights from one key: normal(0, 0.02) matrices, the projections
    that write to the residual stream divided by sqrt(layers)
    (``rescale_prenorm_residual``), norms 1, Mamba-2's own ``A_log`` =
    log U(1, 16), ``dt_bias`` = softplus^-1 of a log-uniform step in
    [time_step_min, time_step_max], ``D`` 1, conv U(+-k^-0.5); the
    router's correction bias a seeded constant (it gets no gradient)."""
    z = dims(config)
    d, pat = z["d"], z["pattern"]
    std, res = 0.02, 0.02 / math.sqrt(len(pat))
    f32 = jnp.float32

    def normal(k, shape, s):
        return jax.random.normal(k, shape, f32) * s

    layers = []
    for i, kind in enumerate(pat):
        ks = jax.random.split(jax.random.fold_in(key, i), 8)
        if kind == "M":
            lo, hi = float(config["time_step_min"]), \
                float(config["time_step_max"])
            dt = jnp.exp(jax.random.uniform(ks[4], (z["h"],), f32)
                         * (math.log(hi) - math.log(lo)) + math.log(lo))
            dt = jnp.maximum(dt, float(config["time_step_floor"]))
            bound = z["k"] ** -0.5
            lay = {
                "norm": jnp.ones((d,), f32),
                "in_proj": normal(ks[0], (d, z["di"] + z["conv"] + z["h"]),
                                  std),
                "conv_w": jax.random.uniform(
                    ks[1], (z["k"], z["conv"]), f32, -bound, bound),
                "conv_b": jax.random.uniform(
                    ks[2], (z["conv"],), f32, -bound, bound),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    ks[3], (z["h"],), f32, 1.0, 16.0)),
                "D": jnp.ones((z["h"],), f32),
                "gate_norm": jnp.ones((z["di"],), f32),
                "out_proj": normal(ks[5], (z["di"], d), res),
            }
        elif kind == "*":
            lay = {
                "norm": jnp.ones((d,), f32),
                "q": normal(ks[0], (d, z["qh"] * z["hd"]), std),
                "k": normal(ks[1], (d, z["kvh"] * z["hd"]), std),
                "v": normal(ks[2], (d, z["kvh"] * z["hd"]), std),
                "o": normal(ks[3], (z["qh"] * z["hd"], d), res),
            }
        elif kind == "E":
            lay = {
                "norm": jnp.ones((d,), f32),
                "router": normal(ks[0], (d, z["experts"]), std),
                "router_bias": normal(ks[1], (z["experts"],), 0.01),
                "up": normal(ks[2], (z["held"], d, z["ff"]), std),
                "down": normal(ks[3], (z["held"], z["ff"], d), res),
                "shared_up": normal(ks[4], (d, z["sff"]), std),
                "shared_down": normal(ks[5], (z["sff"], d), res),
            }
        else:
            raise ValueError(f"layer kind {kind!r} is not M, * or E")
        layers.append(lay)
    kh = jax.random.fold_in(key, len(pat))
    return {"layers": layers, "final_norm": jnp.ones((d,), f32),
            "head": normal(kh, (d, z["vocab"]), std)}


def init_embedding(key, config: dict):
    """The token vectors [vocab, hidden] that the table's rows start
    from: normal(0, 0.02), from the same seed as the weights."""
    z = dims(config)
    return jax.random.normal(jax.random.fold_in(key, 10 ** 6),
                             (z["vocab"], z["d"]), jnp.float32) * 0.02


# ---- the stated matrix product --------------------------------------------

def _round(a, precision: Optional[str]):
    if precision is None:
        return a
    return a.astype(jnp.dtype(precision)).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mm(x, w, spec: str, precision: Optional[str]):
    return jnp.einsum(spec, _round(x, precision), _round(w, precision),
                      precision=HIGHEST)


def _mm_fwd(x, w, spec, precision):
    return _mm(x, w, spec, precision), (x, w)


def _mm_bwd(spec, precision, res, g):
    """The backward products are matrix products too: the cotangent is an
    operand of both and is rounded to ``GRAD_DTYPE``, whatever the
    operands' precision (a run in a lower precision scales its gradients
    so that they do not underflow: float8 would flush every cotangent of
    a mean loss to zero, and the control would train nothing)."""
    x, w = res
    lhs, out = spec.split("->")
    a, b = lhs.split(",")
    gr = _round(g, None if precision is None else GRAD_DTYPE)
    xr, wr = _round(x, precision), _round(w, precision)
    dx = jnp.einsum(f"{out},{b}->{a}", gr, wr, precision=HIGHEST)
    dw = jnp.einsum(f"{a},{out}->{b}", xr, gr, precision=HIGHEST)
    return dx, dw


_mm.defvjp(_mm_fwd, _mm_bwd)


def mm(x, w, spec: str, precision: Optional[str]):
    """A matrix product as the configuration states it: operands (and, in
    the backward pass, cotangents) rounded to ``precision``, float32
    accumulation and result. ``precision`` None is plain float32."""
    return _mm(x, w, spec, precision)


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * weight


# ---- M: the Mamba-2 mixer ---------------------------------------------------

def recurrence(x, dt, a, b, c):
    """The state-space recurrence, one step at a time, float32.
    x [B,T,G,K,P] (head g * K + k reads group g), dt [B,T,G,K], a [G,K],
    b, c [B,T,G,N] -> y [B,T,G,K,P] and the last state [B,G,K,P,N]."""
    bsz, t, g, k, p = x.shape
    n = b.shape[-1]

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        decay = jnp.exp(dt_t * a)[..., None, None]           # [B,G,K,1,1]
        s = decay * s + (dt_t[..., None] * x_t)[..., None] \
            * b_t[:, :, None, None, :]
        return s, jnp.sum(s * c_t[:, :, None, None, :], -1)

    stretch = math.gcd(t, SCAN_STRETCH)

    @jax.checkpoint
    def run_stretch(s, xs):
        return jax.lax.scan(step, s, xs)

    def time_major(v):
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((t // stretch, stretch) + v.shape[1:])

    s0 = jnp.zeros((bsz, g, k, p, n), jnp.float32)
    s, y = jax.lax.scan(run_stretch, s0,
                        tuple(time_major(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1), s


def mamba_mixer(lay, u, z: dict, precision):
    bsz, t, _ = u.shape
    h, p, g, n, di = z["h"], z["p"], z["g"], z["n"], z["di"]
    proj = mm(u, lay["in_proj"], "btd,de->bte", precision)
    gate, xbc, dt = jnp.split(proj, [di, di + z["conv"]], axis=-1)
    # causal depthwise conv: position t reads t-k+1 .. t
    pad = jnp.pad(xbc, ((0, 0), (z["k"] - 1, 0), (0, 0)))
    conv = sum(pad[:, j:j + t] * lay["conv_w"][j] for j in range(z["k"]))
    xbc = jax.nn.silu(conv + lay["conv_b"])
    x, b, c = jnp.split(xbc, [di, di + g * n], axis=-1)
    x = x.reshape(bsz, t, g, h // g, p)
    dt = jax.nn.softplus(dt + lay["dt_bias"]).reshape(bsz, t, g, h // g)
    y, _ = recurrence(x, dt, -jnp.exp(lay["A_log"]).reshape(g, h // g),
                      b.reshape(bsz, t, g, n), c.reshape(bsz, t, g, n))
    y = y + lay["D"].reshape(g, h // g, 1) * x
    y = y.reshape(bsz, t, di) * jax.nn.silu(gate)
    yg = y.reshape(bsz, t, g, di // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + z["eps"])
    y = yg.reshape(bsz, t, di) * lay["gate_norm"]
    return mm(y, lay["out_proj"], "bte,ed->btd", precision)


# ---- *: causal grouped-query attention ------------------------------------

def attention(lay, u, z: dict, precision):
    bsz, t, _ = u.shape
    qh, kvh, hd = z["qh"], z["kvh"], z["hd"]
    q = mm(u, lay["q"], "btd,de->bte", precision).reshape(
        bsz, t, kvh, qh // kvh, hd)
    k = mm(u, lay["k"], "btd,de->bte", precision).reshape(bsz, t, kvh, hd)
    v = mm(u, lay["v"], "btd,de->bte", precision).reshape(bsz, t, kvh, hd)
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


# ---- E: routed experts (the chip's share) + the shared expert -------------

def route(lay, u, z: dict):
    """-> (experts chosen [.., top_k], their weights [.., top_k]), float32
    at the highest precision: ``sigmoid`` scores, choice by score + bias,
    weights the chosen scores over their sum, times the scaling factor."""
    s = jax.nn.sigmoid(jnp.einsum("btd,de->bte", u, lay["router"],
                                  precision=HIGHEST))
    bias = jax.lax.stop_gradient(lay["router_bias"])
    _, idx = jax.lax.top_k(s + bias, z["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / jnp.sum(w, -1, keepdims=True) * z["scale"]


def relu2_mlp(x, up, down, precision):
    hid = jnp.square(jax.nn.relu(mm(x, up, "btd,df->btf", precision)))
    return mm(hid, down, "btf,fd->btd", precision)


def drop_over_capacity(idx, w, z: dict, capacity_factor: float):
    """The planted fault: an expert takes ``capacity_factor`` x its even
    share of the step's token-choices, in token order, and the rest of its
    choices are dropped (their weight set to 0)."""
    flat = idx.reshape(-1)
    cap = int(capacity_factor * flat.shape[0] / z["experts"])
    onehot = jax.nn.one_hot(flat, z["experts"], dtype=jnp.int32)
    place = jnp.take_along_axis(jnp.cumsum(onehot, 0), flat[:, None],
                                1)[:, 0]
    return w * (place <= cap).reshape(w.shape)


def moe(lay, u, z: dict, precision, held: Tuple[int, int],
        fault: Optional[str] = None):
    idx, w = route(lay, u, z)
    if fault == "experts_dropped":
        w = drop_over_capacity(idx, w, z, 1.0)
    y = relu2_mlp(u, lay["shared_up"], lay["shared_down"], precision)
    for j, e in enumerate(range(*held)):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1)       # [B,T]
        y = y + w_e[..., None] * relu2_mlp(u, lay["up"][j], lay["down"][j],
                                           precision)
    return y


# ---- the stack ---------------------------------------------------------------

def held_experts(config: dict) -> Tuple[int, int]:
    lo = int(config.get("first_expert_held", 0))
    return lo, lo + int(config["n_routed_experts"])


def hidden(params, emb, config: dict, precision: Optional[str] = None,
           fault: Optional[str] = None):
    """Token vectors ``emb`` [B,T,hidden] -> the last layer's output."""
    z = dims(config)
    held = held_experts(config)
    x = emb
    for kind, lay in zip(z["pattern"], params["layers"]):
        def layer(x, lay, kind=kind):
            u = rms_norm(x, lay["norm"], z["eps"])
            if kind == "M":
                return x + mamba_mixer(lay, u, z, precision)
            if kind == "*":
                return x + attention(lay, u, z, precision)
            return x + moe(lay, u, z, precision, held, fault)
        x = jax.checkpoint(layer)(x, lay)
    return x


def forward(params, emb, config: dict, precision: Optional[str] = None,
            fault: Optional[str] = None):
    """Token vectors ``emb`` [B,T,hidden] -> logits [B,T,vocab]."""
    x = hidden(params, emb, config, precision, fault)
    x = rms_norm(x, params["final_norm"], dims(config)["eps"])
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
        logits = mm(rms_norm(x_s, params["final_norm"], z["eps"]),
                    params["head"], "td,dv->tv", precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lab[:, None], -1))

    return jnp.sum(jax.lax.map(a_sequence, (x, labels))) / labels.size
