"""LFM2-MoE: a hybrid language-model backbone in which every layer is TWO
sublayers behind pre-norm residuals, an operator and a feed-forward:

    x <- x + operator(operator_norm(x));  x <- x + ffn(ffn_norm(x))

The operator's kind is read from ``layer_types``:

    ``conv``            gated short convolution: ``[B | C | v] = in_proj``,
                        ``out_proj(C * causal_conv_3(B * v))``
                                   (ops/short_conv.py)
    ``full_attention``  causal grouped-query attention, queries and keys
                        RMS-normed over the head and rotated (rotate-half)
                                   (ops/causal_attention.py)

and the feed-forward's from the layer's place: the leading
``num_dense_layers`` layers carry a dense SwiGLU MLP, the others routed
GATED experts without a shared one (parallel/moe.py: dropless routing
over the experts this chip holds). Then ``embedding_norm`` and an untied
head. As with ``models/nemotron_h.py``, the embedding is NOT here (a
token's vector is a row of the table, ``train/step.SeqTrainStep``), the
parameters are a plain tree, and ``config`` is the model's published
``config.json`` by its own keys (``benchmarks/configs/lfm2-24b-a2b.json``);
``num_experts`` counts the experts HELD here, ``router_outputs`` all the
experts the router chooses among, ``first_expert_held`` where this chip's
run starts.

Precision: parameters, router, the convolution and its gates, norms,
rotary embedding, softmax and loss float32; matrix products with
``compute_dtype`` (bfloat16) operands and float32 accumulation. Every op
sits under one ``pbox.*`` scope of ``obs/trace``'s catalog; every sublayer
is one ``jax.checkpoint`` (the dense MLP one a slab of positions) that
keeps its input, an attention operator's also its forward block loops'
two results (``lm_parts.KEEP_ATTN_LOOPS``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from paddlebox_tpu.models.lm_parts import (KEEP_ATTN_LOOPS,
                                           MOE_STEP_SCALARS, head_loss,
                                           matmul, moe_load_scalars,
                                           rms_norm, rotary_attention)
from paddlebox_tpu.obs import trace
from paddlebox_tpu.ops.short_conv import gated_short_conv
from paddlebox_tpu.parallel.moe import route_top_k, routed_experts

_scope = jax.named_scope

#: positions the dense MLP's hidden activation exists for at a time: the
#: largest divisor of the step's positions that this allows
MLP_ROWS = 4096
#: what the published router adds to the chosen scores' sum
ROUTE_SUM_EPS = 1e-6

KINDS = ("conv", "full_attention")


class Lfm2Moe:
    #: ``Trainer`` builds ``SeqTrainStep`` for such a model
    sequence_model = True
    #: the scalars ``loss`` hands out a step beside the loss
    step_scalars = MOE_STEP_SCALARS

    def __init__(self, config: Dict[str, Any],
                 compute_dtype=jnp.bfloat16) -> None:
        c = config
        self.kinds = tuple(c["layer_types"])
        if set(self.kinds) - set(KINDS):
            raise ValueError(f"layer types {self.kinds} are not all of "
                             f"{KINDS}")
        if len(self.kinds) != int(c["num_hidden_layers"]):
            raise ValueError(f"{len(self.kinds)} layer types for "
                             f"{c['num_hidden_layers']} layers")
        self.n_dense = int(c["num_dense_layers"])
        self.d = int(c["hidden_size"])
        self.vocab = int(c["vocab_size"])
        self.eps = float(c["norm_eps"])
        self.conv_k = int(c["conv_L_cache"])
        self.qh = int(c["num_attention_heads"])
        self.kvh = int(c["num_key_value_heads"])
        self.hd = self.d // self.qh
        self.theta = float(c["rope_parameters"]["rope_theta"])
        self.ff = int(c["intermediate_size"])
        self.mff = int(c["moe_intermediate_size"])
        self.experts = int(c["router_outputs"])
        lo = int(c.get("first_expert_held", 0))
        self.held = (lo, lo + int(c["num_experts"]))
        self.top_k = int(c["num_experts_per_tok"])
        self.route_scale = float(c["routed_scaling_factor"])
        self.dtype = compute_dtype

    # ---- parameters ----
    def init(self, key: jax.Array):
        """normal(0, 0.02) matrices, the projections that write to the
        residual stream divided by sqrt(2 x layers), norms 1, conv taps
        U(+-L^-0.5), ``expert_bias`` 0."""
        d, f32 = self.d, jnp.float32
        std, res = 0.02, 0.02 / math.sqrt(2 * len(self.kinds))
        n_held = self.held[1] - self.held[0]

        def normal(k, shape, s):
            return jax.random.normal(k, shape, f32) * s

        layers = []
        for i, kind in enumerate(self.kinds):
            ks = jax.random.split(jax.random.fold_in(key, i), 10)
            lay = {"operator_norm": jnp.ones((d,), f32),
                   "ffn_norm": jnp.ones((d,), f32)}
            if kind == "conv":
                bound = self.conv_k ** -0.5
                lay.update(
                    in_proj=normal(ks[0], (d, 3 * d), std),
                    conv_w=jax.random.uniform(ks[1], (self.conv_k, d), f32,
                                              -bound, bound),
                    out_proj=normal(ks[2], (d, d), res))
            else:
                lay.update(
                    q=normal(ks[0], (d, self.qh * self.hd), std),
                    k=normal(ks[1], (d, self.kvh * self.hd), std),
                    v=normal(ks[2], (d, self.kvh * self.hd), std),
                    o=normal(ks[3], (self.qh * self.hd, d), res),
                    q_norm=jnp.ones((self.hd,), f32),
                    k_norm=jnp.ones((self.hd,), f32))
            if i < self.n_dense:
                lay.update(w1=normal(ks[4], (d, self.ff), std),
                           w3=normal(ks[5], (d, self.ff), std),
                           w2=normal(ks[6], (self.ff, d), res))
            else:
                lay.update(
                    router=normal(ks[4], (d, self.experts), std),
                    expert_bias=jnp.zeros((self.experts,), f32),
                    gate=normal(ks[6], (n_held, d, self.mff), std),
                    up=normal(ks[7], (n_held, d, self.mff), std),
                    down=normal(ks[8], (n_held, self.mff, d), res))
            layers.append(lay)
        return {"layers": layers, "embedding_norm": jnp.ones((d,), f32),
                "head": normal(jax.random.fold_in(key, len(self.kinds)),
                               (d, self.vocab), std)}

    # ---- pieces ----
    def _mm(self, x, w):
        return matmul(x, w, self.dtype)

    def _norm(self, x, weight):
        return rms_norm(x, weight, self.eps)

    def _conv(self, lay, x):
        with _scope(trace.SCOPE_CONV_PROJ):
            bcv = self._mm(self._norm(x, lay["operator_norm"]),
                           lay["in_proj"])
        with _scope(trace.SCOPE_CONV_MIX):
            y = gated_short_conv(bcv, lay["conv_w"])
        with _scope(trace.SCOPE_CONV_PROJ):
            return x + self._mm(y, lay["out_proj"])

    def _attention(self, lay, x):
        with _scope(trace.SCOPE_ATTN):
            return x + rotary_attention(
                self._norm(x, lay["operator_norm"]), lay,
                (self.qh, self.kvh, self.hd), self.eps, self.dtype,
                {"theta": self.theta})

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
            idx, w = route_top_k(u, lay["router"], lay["expert_bias"],
                                 self.top_k, self.route_scale,
                                 sum_eps=ROUTE_SUM_EPS)
        with _scope(trace.SCOPE_MOE_EXPERTS):
            y, stats = routed_experts(u, idx, w, lay["up"], lay["down"],
                                      self.held, mm_dtype=self.dtype,
                                      gate=lay["gate"])
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
            operator = self._conv if kind == "conv" else self._attention
            x = jax.checkpoint(operator, policy=KEEP_ATTN_LOOPS)(lay, x)
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
            return self._mm(self._norm(x, params["embedding_norm"]),
                            params["head"])

    def loss(self, params, emb: jax.Array, labels: jax.Array,
             valid: jax.Array):
        """Mean cross-entropy of ``labels`` [S, T] over the positions
        ``valid`` marks -> (loss, the step's ``step_scalars``)."""
        x, loads, computed = self.hidden(params, emb)
        return (head_loss(x, params["embedding_norm"], params["head"],
                          labels, valid, self.eps, self.dtype),
                moe_load_scalars(loads, computed))
