"""Kimi Linear (``model_type`` ``kimi_linear``): a language-model backbone
in which every layer is a mixer then a feed-forward, behind pre-norm
residuals:

    x <- x + mixer(attn_norm(x));  x <- x + ffn(ffn_norm(x))     (eps 1e-5)

The mixer's kind is read from ``linear_attn_config`` (1-based layer
numbers):

    ``kda_layers``       Kimi Delta Attention: q, k, v projections, each
                         through a causal depthwise conv of 4 taps and
                         SiLU, q and k L2-normed over the head (q then
                         scaled by K^-0.5); a log decay a key channel
                         ``g = -exp(A_log) softplus(W_f2 W_f1 x +
                         dt_bias)``, ``beta = sigmoid(W_b x)`` a head; the
                         gated delta rule (ops/kda.py); the output
                         RMS-normed over the head, times its weight and
                         ``sigmoid(W_g2 W_g1 x + b_g)``, then ``o``
    ``full_attn_layers`` latent attention without positions (MLA,
                         ``q_lora_rank`` null, ``mla_use_nope``):
                         ``q = W_q x`` (heads of 128 + 64); ``[c | k_pe] =
                         W_kva x``, c RMS-normed; ``[k_nope | v] = W_kvb
                         c``; a head's key is ``[k_nope ; k_pe]``, k_pe
                         shared by every head; causal softmax at scale
                         (128 + 64)^-0.5 over values of 128
                         (ops/causal_attention.py, one query head a
                         key/value head), then ``o``

and the feed-forward's from the layer's place: the leading
``first_k_dense_replace`` layers a dense SwiGLU, the others
``num_experts_per_token`` of the router's experts by sigmoid, with a
correction bias that only chooses, weights over their sum times
``routed_scaling_factor`` (parallel/moe.py: dropless routing over the
experts this chip holds), beside ``num_shared_experts`` shared SwiGLU
expert(s) every token passes through. Then the final norm and an untied
head. As with ``models/mellum.py``, the embedding is NOT here (a token's
vector is a row of the table, ``train/step.SeqTrainStep``), the
parameters are a plain tree, and ``config`` is the model's published
``config.json`` by its own keys
(``benchmarks/configs/kimi-linear-48b-a3b.json``); ``num_experts``
counts the experts HELD here, ``router_outputs`` all the experts the
router chooses among, ``first_expert_held`` where this chip's run starts.

Precision: parameters, router, the convs and their SiLU, the decay and
its gates, norms, the delta rule's state and the softmax and loss
float32; matrix products with ``compute_dtype`` (bfloat16) operands and
float32 accumulation (the delta rule's own: ops/kda.py). Every op sits
under one ``pbox.*`` scope of ``obs/trace``'s catalog
(``KDA_SEQ_STEP_SCOPES``: a KDA mixer's projections under
``pbox.kda_proj``, its convs and their SiLU under ``pbox.kda_conv``, the
rule with the L2 norms of q and k and the output's gated norm under
``pbox.kda_scan``; a latent attention mixer, its latent
projections and norm with it, under ``pbox.attn``); every sublayer is one
``jax.checkpoint`` that keeps its input (the dense feed-forward one a
slab of positions), a latent attention mixer's also its forward block
loops' two results (``lm_parts.KEEP_ATTN_LOOPS``), a KDA mixer one a
sequence (``nemotron_h.py``'s Mamba mixer's form).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from paddlebox_tpu.models.lm_parts import (ATTN_BLOCK, KEEP_ATTN_LOOPS,
                                           MOE_STEP_SCALARS, head_loss,
                                           matmul, moe_load_scalars,
                                           rms_norm)
from paddlebox_tpu.obs import trace
from paddlebox_tpu.ops.causal_attention import causal_gqa_attention
from paddlebox_tpu.ops.kda import kda_normed
from paddlebox_tpu.ops.short_conv import causal_depthwise_conv
from paddlebox_tpu.parallel.moe import route_top_k, routed_experts

_scope = jax.named_scope

#: positions the dense feed-forward's hidden activation exists for at a
#: time: the largest divisor of the step's positions that this allows
MLP_ROWS = 4096
#: steps of a chunk of the delta rule
KDA_CHUNK = 64


def layer_kinds(config: Dict[str, Any]) -> str:
    """A letter a layer from ``linear_attn_config``: ``K`` a KDA mixer,
    ``M`` latent attention, for layers 1 .. ``num_hidden_layers``."""
    lin = config["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    kinds = ""
    for i in range(1, int(config["num_hidden_layers"]) + 1):
        if (i in kda) == (i in full):
            which = "both" if i in kda else "neither"
            raise ValueError(f"layer {i} is in {which} of kda_layers and "
                             f"full_attn_layers")
        kinds += "K" if i in kda else "M"
    return kinds


class KimiLinear:
    #: ``Trainer`` builds ``SeqTrainStep`` for such a model
    sequence_model = True
    #: the scalars ``loss`` hands out a step beside the loss: the expert
    #: layers', and every choice the routers made, held here or not (what
    #: ``moe_choices_held`` is a share of)
    step_scalars = dict(MOE_STEP_SCALARS, moe_choices="sum")

    def __init__(self, config: Dict[str, Any],
                 compute_dtype=jnp.bfloat16) -> None:
        c = config
        if c.get("q_lora_rank") is not None or not c.get("mla_use_nope"):
            raise ValueError("latent attention here has no query latent and "
                             "no rotary part (q_lora_rank null, "
                             "mla_use_nope true)")
        self.kinds = layer_kinds(c)
        self.d = int(c["hidden_size"])
        self.vocab = int(c["vocab_size"])
        self.eps = float(c["rms_norm_eps"])
        lin = c["linear_attn_config"]
        self.kh, self.kd = int(lin["num_heads"]), int(lin["head_dim"])
        self.conv_k = int(lin["short_conv_kernel_size"])
        self.qh = int(c["num_attention_heads"])
        self.nope = int(c["qk_nope_head_dim"])
        self.rope = int(c["qk_rope_head_dim"])
        self.vd = int(c["v_head_dim"])
        self.kv_rank = int(c["kv_lora_rank"])
        self.n_dense = int(c["first_k_dense_replace"])
        if int(c["moe_layer_freq"]) != 1:
            raise ValueError("every layer after the dense ones has experts "
                             "here (moe_layer_freq 1)")
        self.ff = int(c["intermediate_size"])
        self.mff = int(c["moe_intermediate_size"])
        self.sff = self.mff * int(c["num_shared_experts"])
        self.experts = int(c["router_outputs"])
        lo = int(c.get("first_expert_held", 0))
        self.held = (lo, lo + int(c["num_experts"]))
        self.top_k = int(c["num_experts_per_token"])
        self.route_scale = float(c["routed_scaling_factor"])
        self.dtype = compute_dtype

    # ---- parameters ----
    def init(self, key: jax.Array):
        """normal(0, 0.02) matrices, the projections that write to the
        residual stream divided by sqrt(2 x layers), norms 1, conv taps
        U(+-K^-0.5), KDA's ``A_log`` = log U(1, 16) and ``dt_bias`` =
        softplus^-1 of a log-uniform step in [0.001, 0.1], the gate's bias
        and the router's correction bias 0."""
        d, f32 = self.d, jnp.float32
        std, res = 0.02, 0.02 / math.sqrt(2 * len(self.kinds))
        n_held = self.held[1] - self.held[0]
        hk = self.kh * self.kd

        def normal(k, shape, s):
            return jax.random.normal(k, shape, f32) * s

        layers = []
        for i, kind in enumerate(self.kinds):
            ks = jax.random.split(jax.random.fold_in(key, i), 16)
            lay = {"attn_norm": jnp.ones((d,), f32),
                   "ffn_norm": jnp.ones((d,), f32)}
            if kind == "K":
                bound = self.conv_k ** -0.5
                dt = jnp.exp(jax.random.uniform(ks[9], (hk,), f32)
                             * (math.log(0.1) - math.log(0.001))
                             + math.log(0.001))
                dt = jnp.maximum(dt, 1e-4)
                lay.update(
                    q=normal(ks[0], (d, hk), std),
                    k=normal(ks[1], (d, hk), std),
                    v=normal(ks[2], (d, hk), std),
                    q_conv=jax.random.uniform(ks[3], (self.conv_k, hk), f32,
                                              -bound, bound),
                    k_conv=jax.random.uniform(ks[4], (self.conv_k, hk), f32,
                                              -bound, bound),
                    v_conv=jax.random.uniform(ks[5], (self.conv_k, hk), f32,
                                              -bound, bound),
                    f_a=normal(ks[6], (d, self.kd), std),
                    f_b=normal(ks[7], (self.kd, hk), std),
                    A_log=jnp.log(jax.random.uniform(ks[8], (self.kh,), f32,
                                                     1.0, 16.0)),
                    dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                    b=normal(ks[10], (d, self.kh), std),
                    g_a=normal(ks[11], (d, self.kd), std),
                    g_b=normal(ks[12], (self.kd, hk), std),
                    g_bias=jnp.zeros((hk,), f32),
                    o_norm=jnp.ones((self.kd,), f32),
                    o=normal(ks[13], (hk, d), res))
            else:
                qk = self.nope + self.rope
                lay.update(
                    q=normal(ks[0], (d, self.qh * qk), std),
                    kv_a=normal(ks[1], (d, self.kv_rank + self.rope), std),
                    kv_norm=jnp.ones((self.kv_rank,), f32),
                    kv_b=normal(ks[2], (self.kv_rank,
                                        self.qh * (self.nope + self.vd)),
                                std),
                    o=normal(ks[3], (self.qh * self.vd, d), res))
            ks = jax.random.split(jax.random.fold_in(key, 1000 + i), 8)
            if i < self.n_dense:
                lay.update(w1=normal(ks[0], (d, self.ff), std),
                           w3=normal(ks[1], (d, self.ff), std),
                           w2=normal(ks[2], (self.ff, d), res))
            else:
                lay.update(
                    router=normal(ks[0], (d, self.experts), std),
                    router_bias=jnp.zeros((self.experts,), f32),
                    gate=normal(ks[1], (n_held, d, self.mff), std),
                    up=normal(ks[2], (n_held, d, self.mff), std),
                    down=normal(ks[3], (n_held, self.mff, d), res),
                    shared_gate=normal(ks[4], (d, self.sff), std),
                    shared_up=normal(ks[5], (d, self.sff), std),
                    shared_down=normal(ks[6], (self.sff, d), res))
            layers.append(lay)
        return {"layers": layers, "norm": jnp.ones((d,), f32),
                "head": normal(jax.random.fold_in(key, len(self.kinds)),
                               (d, self.vocab), std)}

    # ---- pieces ----
    def _mm(self, x, w):
        return matmul(x, w, self.dtype)

    def _norm(self, x, weight):
        return rms_norm(x, weight, self.eps)

    def _kda(self, lay, x):
        """Every array between the projections and ``o`` stays a flat row
        [S, T, H K]: the rule's kernels take and give such rows and do the
        per-head norms themselves (ops/kda.py)."""
        kd = self.kd
        with _scope(trace.SCOPE_KDA_PROJ):
            u = self._norm(x, lay["attn_norm"])
            q, k, v = (self._mm(u, lay[n]) for n in ("q", "k", "v"))
            f = self._mm(self._mm(u, lay["f_a"]), lay["f_b"])
            g = -jnp.repeat(jnp.exp(lay["A_log"]), kd) * jax.nn.softplus(
                f + lay["dt_bias"])
            beta = jax.nn.sigmoid(self._mm(u, lay["b"]))
            gate = self._mm(self._mm(u, lay["g_a"]), lay["g_b"]) \
                + lay["g_bias"]
        with _scope(trace.SCOPE_KDA_CONV):
            q, k, v = (jax.nn.silu(causal_depthwise_conv(y, lay[n]))
                       for y, n in ((q, "q_conv"), (k, "k_conv"),
                                    (v, "v_conv")))
        with _scope(trace.SCOPE_KDA_SCAN):
            y = kda_normed(q, k, v, g, beta, gate, lay["o_norm"],
                           eps=self.eps, chunk=KDA_CHUNK, mm_dtype=self.dtype)
        with _scope(trace.SCOPE_KDA_PROJ):
            return x + self._mm(y, lay["o"])

    def _mla(self, lay, x):
        s, t, _ = x.shape
        qh, nope, rope, vd = self.qh, self.nope, self.rope, self.vd
        with _scope(trace.SCOPE_ATTN):
            u = self._norm(x, lay["attn_norm"])
            q = self._mm(u, lay["q"]).reshape(s, t, qh, nope + rope)
            c, k_pe = jnp.split(self._mm(u, lay["kv_a"]), [self.kv_rank], -1)
            kv = self._mm(self._norm(c, lay["kv_norm"]), lay["kv_b"]
                          ).reshape(s, t, qh, nope + vd)
            k_nope, v = jnp.split(kv, [nope], -1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_pe[:, :, None, :], (s, t, qh, rope))], -1)
            o = causal_gqa_attention(q, k, v, block=ATTN_BLOCK,
                                     sm_scale=(nope + rope) ** -0.5,
                                     mm_dtype=self.dtype)
            return x + self._mm(o.reshape(s, t, qh * vd), lay["o"])

    def _mlp(self, lay, x):
        """The dense SwiGLU feed-forward of ``MLP_ROWS`` positions
        [rows, hidden]."""
        with _scope(trace.SCOPE_MLP):
            u = self._norm(x, lay["ffn_norm"])
            hid = jax.nn.silu(self._mm(u, lay["w1"])) \
                * self._mm(u, lay["w3"])
            return x + self._mm(hid, lay["w2"])

    def _moe(self, lay, x) -> Tuple[jax.Array, jax.Array, jax.Array]:
        s, t, d = x.shape
        with _scope(trace.SCOPE_MOE_ROUTE):
            u = self._norm(x, lay["ffn_norm"]).reshape(s * t, d)
            idx, w = route_top_k(u, lay["router"], lay["router_bias"],
                                 self.top_k, self.route_scale)
        with _scope(trace.SCOPE_MOE_EXPERTS):
            y, stats = routed_experts(u, idx, w, lay["up"], lay["down"],
                                      self.held, mm_dtype=self.dtype,
                                      gate=lay["gate"])
        with _scope(trace.SCOPE_MOE_SHARED):
            hid = jax.nn.silu(self._mm(u, lay["shared_gate"])) \
                * self._mm(u, lay["shared_up"])
            y = y + self._mm(hid, lay["shared_down"])
            return x + y.reshape(s, t, d), stats["load"], stats["rows"]

    # ---- the stack, the head and the loss ----
    def hidden(self, params, emb: jax.Array):
        """Token vectors [S, T, hidden] -> (the last layer's output, the
        token-choices each held expert took in each expert layer, int32
        [expert layers, held], the rows each expert layer's loops
        computed, int32 [expert layers])."""
        x, loads, rows = emb, [], []
        shape = x.shape
        mlp_rows = math.gcd(x.shape[0] * x.shape[1], MLP_ROWS)
        for i, (kind, lay) in enumerate(zip(self.kinds, params["layers"])):
            if kind == "K":
                # sequences are independent in a mixer over time: one at
                # a time, so that the mixer's temporaries (the step's
                # widest) are one sequence's
                one = jax.checkpoint(
                    lambda lay, xs: self._kda(lay, xs[None])[0])
                x = jax.lax.map(lambda xs, lay=lay: one(lay, xs), x)
            else:
                x = jax.checkpoint(self._mla, policy=KEEP_ATTN_LOOPS)(lay, x)
            if i < self.n_dense:
                # the hidden activation is the widest value of the step:
                # a slab of positions at a time
                one = jax.checkpoint(self._mlp)
                x = jax.lax.map(lambda xs, lay=lay: one(lay, xs),
                                x.reshape(-1, mlp_rows, self.d)
                                ).reshape(shape)
            else:
                x, load, computed = jax.checkpoint(self._moe)(lay, x)
                loads.append(load)
                rows.append(computed)
        n_held = self.held[1] - self.held[0]
        if not loads:
            return (x, jnp.zeros((0, n_held), jnp.int32),
                    jnp.zeros((0,), jnp.int32))
        return x, jnp.stack(loads), jnp.stack(rows)

    def logits(self, params, emb: jax.Array) -> jax.Array:
        x, _, _ = self.hidden(params, emb)
        with _scope(trace.SCOPE_HEAD):
            return self._mm(self._norm(x, params["norm"]), params["head"])

    def loss(self, params, emb: jax.Array, labels: jax.Array,
             valid: jax.Array):
        """Mean cross-entropy of ``labels`` [S, T] over the positions
        ``valid`` marks -> (loss, the step's ``step_scalars``)."""
        x, loads, computed = self.hidden(params, emb)
        expert_layers = len(self.kinds) - self.n_dense
        scalars = dict(moe_load_scalars(loads, computed),
                       moe_choices=jnp.float32(
                           labels.size * self.top_k * expert_layers))
        return (head_loss(x, params["norm"], params["head"], labels, valid,
                          self.eps, self.dtype), scalars)
