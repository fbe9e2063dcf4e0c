"""Laguna (``model_type`` ``laguna``): a language-model backbone in which
every layer is gated attention then a feed-forward, behind pre-norm
residuals:

    x <- x + attention(attn_norm(x));  x <- x + ffn(ffn_norm(x))   (eps 1e-6)

The attention's kind is read from ``layer_types`` and its query heads
from ``num_attention_heads_per_layer`` (48 in a full layer, 72 in a
sliding one at the published widths; every layer has the same key/value
heads):

    ``full_attention``     causal grouped-query attention over the whole
                           sequence; YaRN's rotary table on the first
                           ``partial_rotary_factor`` of each head, the
                           rest unturned
    ``sliding_attention``  the same over the ``sliding_window`` keys that
                           end at the query; the plain table on the whole
                           head

(ops/causal_attention.py; queries and keys are RMS-normed over the head
before the rotation), each head's output times its own gate
``sigmoid(u W_g)`` before ``o`` (``gating`` ``per-head``: one scalar a head
and position). The feed-forward's kind is read from ``mlp_layer_types``:
``dense`` a SwiGLU of ``intermediate_size``; ``sparse``
``num_experts_per_tok`` of the router's experts by softmax, weights over
their sum times ``moe_routed_scaling_factor`` (parallel/moe.py: dropless
routing over the experts this chip holds), beside one shared SwiGLU
expert every token passes through. Then the final norm and an untied
head. As with ``models/mellum.py``, the embedding is NOT here (a token's
vector is a row of the table, ``train/step.SeqTrainStep``), the
parameters are a plain tree, and ``config`` is the model's published
``config.json`` by its own keys (``benchmarks/configs/laguna-s-2.1.json``);
``num_experts`` counts the experts HELD here, ``router_outputs`` all the
experts the router chooses among, ``first_expert_held`` where this chip's
run starts.

Precision: parameters, router, norms, rotary embedding, the gate's
sigmoid, softmax and loss float32; matrix products (the gate's projection
among them) with ``compute_dtype`` (bfloat16) operands and float32
accumulation. Every op sits under one ``pbox.*`` scope of ``obs/trace``'s
catalog (``STEP_SCOPES``: a sliding layer's attention
under ``pbox.attn_window``, a full layer's under ``pbox.attn``, the gate
of either under ``pbox.attn_gate`` inside it); every sublayer is one
``jax.checkpoint`` that keeps its input (the dense feed-forward one a slab
of positions), an attention sublayer's also its forward block loops' two
results (``lm_parts.KEEP_ATTN_LOOPS``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from paddlebox_tpu.models.lm_parts import (KEEP_ATTN_LOOPS,
                                           MOE_STEP_SCALARS, dense_ffn,
                                           gated_experts, head_loss,
                                           matmul, moe_load_scalars,
                                           rms_norm, rotary_attention)
from paddlebox_tpu.models.mellum import KINDS, rotary_table
from paddlebox_tpu.obs import trace

_scope = jax.named_scope

MLP_KINDS = ("dense", "sparse")

#: the scopes a step of this model runs: sliding layers under
#: ``pbox.attn_window`` and full ones under ``pbox.attn``, each with its
#: gate under ``pbox.attn_gate``; a leading dense feed-forward
#: (``pbox.mlp``), routed and shared experts after it
STEP_SCOPES = (trace.SCOPE_DECODE, trace.SCOPE_DEDUP, trace.SCOPE_PULL,
               trace.SCOPE_ATTN, trace.SCOPE_ATTN_WINDOW,
               trace.SCOPE_ATTN_GATE, trace.SCOPE_MLP, trace.SCOPE_MOE_ROUTE,
               trace.SCOPE_MOE_EXPERTS, trace.SCOPE_MOE_SHARED,
               trace.SCOPE_HEAD, trace.SCOPE_LOSS, trace.SCOPE_PUSH,
               trace.SCOPE_DENSE_OPT)


class LagunaMoe:
    #: ``Trainer`` builds ``SeqTrainStep`` for such a model
    sequence_model = True
    #: the scalars ``loss`` hands out a step beside the loss: the expert
    #: layers', and every choice the routers made, held here or not (what
    #: ``moe_choices_held`` is a share of)
    step_scalars = dict(MOE_STEP_SCALARS, moe_choices="sum")

    def __init__(self, config: Dict[str, Any],
                 compute_dtype=jnp.bfloat16) -> None:
        c = config
        n = int(c["num_hidden_layers"])
        self.kinds = tuple(c["layer_types"])
        self.mlp_kinds = tuple(c["mlp_layer_types"])
        self.heads = tuple(int(h) for h in c["num_attention_heads_per_layer"])
        if set(self.kinds) - set(KINDS):
            raise ValueError(f"layer types {self.kinds} are not all of "
                             f"{KINDS}")
        if set(self.mlp_kinds) - set(MLP_KINDS):
            raise ValueError(f"mlp layer types {self.mlp_kinds} are not all "
                             f"of {MLP_KINDS}")
        for name, got in (("layer_types", self.kinds),
                          ("mlp_layer_types", self.mlp_kinds),
                          ("num_attention_heads_per_layer", self.heads)):
            if len(got) != n:
                raise ValueError(f"{len(got)} {name} for {n} layers")
        if set(c.get("gating_types", ("per_head",))) != {"per_head"}:
            raise ValueError("every layer's gate is one scalar a head here "
                             "(gating_types per_head)")
        self.d = int(c["hidden_size"])
        self.vocab = int(c["vocab_size"])
        self.eps = float(c["rms_norm_eps"])
        self.kvh = int(c["num_key_value_heads"])
        self.hd = int(c["head_dim"])
        if any(h % self.kvh for h in self.heads):
            raise ValueError(f"query heads {self.heads} are not groups of "
                             f"the {self.kvh} key/value heads")
        self.window = int(c["sliding_window"])
        #: a rotary table a layer kind, built once
        self.rotary = {kind: rotary_table(self.hd, c["rope_parameters"][kind])
                       for kind in KINDS}
        self.ff = int(c["intermediate_size"])
        self.mff = int(c["moe_intermediate_size"])
        self.sff = int(c["shared_expert_intermediate_size"])
        self.experts = int(c["router_outputs"])
        lo = int(c.get("first_expert_held", 0))
        self.held = (lo, lo + int(c["num_experts"]))
        self.top_k = int(c["num_experts_per_tok"])
        self.route_scale = float(c["moe_routed_scaling_factor"])
        self.dtype = compute_dtype

    # ---- parameters ----
    def init(self, key: jax.Array):
        """normal(0, 0.02) matrices, the projections that write to the
        residual stream divided by sqrt(2 x layers), norms 1."""
        d, hd, f32 = self.d, self.hd, jnp.float32
        std, res = 0.02, 0.02 / math.sqrt(2 * len(self.kinds))
        n_held = self.held[1] - self.held[0]

        def normal(k, shape, s):
            return jax.random.normal(k, shape, f32) * s

        layers = []
        for i, (qh, mlp) in enumerate(zip(self.heads, self.mlp_kinds)):
            ks = jax.random.split(jax.random.fold_in(key, i), 16)
            lay = {"attn_norm": jnp.ones((d,), f32),
                   "ffn_norm": jnp.ones((d,), f32),
                   "q": normal(ks[0], (d, qh * hd), std),
                   "k": normal(ks[1], (d, self.kvh * hd), std),
                   "v": normal(ks[2], (d, self.kvh * hd), std),
                   "o": normal(ks[3], (qh * hd, d), res),
                   "q_norm": jnp.ones((hd,), f32),
                   "k_norm": jnp.ones((hd,), f32),
                   "attn_gate": normal(ks[4], (d, qh), std)}
            if mlp == "dense":
                lay.update(w1=normal(ks[5], (d, self.ff), std),
                           w3=normal(ks[6], (d, self.ff), std),
                           w2=normal(ks[7], (self.ff, d), res))
            else:
                lay.update(
                    router=normal(ks[8], (d, self.experts), std),
                    gate=normal(ks[9], (n_held, d, self.mff), std),
                    up=normal(ks[10], (n_held, d, self.mff), std),
                    down=normal(ks[11], (n_held, self.mff, d), res),
                    shared_gate=normal(ks[12], (d, self.sff), std),
                    shared_up=normal(ks[13], (d, self.sff), std),
                    shared_down=normal(ks[14], (self.sff, d), res))
            layers.append(lay)
        return {"layers": layers, "norm": jnp.ones((d,), f32),
                "head": normal(jax.random.fold_in(key, len(self.kinds)),
                               (d, self.vocab), std)}

    # ---- pieces ----
    def _norm(self, x, weight):
        return rms_norm(x, weight, self.eps)

    def _attention(self, kind: str, qh: int, lay, x):
        sliding = kind == "sliding_attention"
        with _scope(trace.SCOPE_ATTN_WINDOW if sliding
                    else trace.SCOPE_ATTN):
            return x + rotary_attention(
                self._norm(x, lay["attn_norm"]), lay,
                (qh, self.kvh, self.hd), self.eps, self.dtype,
                self.rotary[kind], window=self.window if sliding else None)

    def _moe(self, lay, x) -> Tuple[jax.Array, jax.Array, jax.Array]:
        return gated_experts(lay, x, self.eps, self.dtype, self.held,
                             self.top_k, self.route_scale,
                             score=jax.nn.softmax)

    # ---- the stack, the head and the loss ----
    def hidden(self, params, emb: jax.Array):
        """Token vectors [S, T, hidden] -> (the last layer's output, the
        token-choices each held expert took in each expert layer, int32
        [expert layers, held], the rows each expert layer's loops
        computed, int32 [expert layers])."""
        x, loads, rows = emb, [], []
        for kind, qh, mlp, lay in zip(self.kinds, self.heads, self.mlp_kinds,
                                      params["layers"]):
            x = jax.checkpoint(self._attention, static_argnums=(0, 1),
                               policy=KEEP_ATTN_LOOPS)(kind, qh, lay, x)
            if mlp == "dense":
                x = dense_ffn(lay, x, self.eps, self.dtype)
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
            return matmul(self._norm(x, params["norm"]), params["head"],
                          self.dtype)

    def loss(self, params, emb: jax.Array, labels: jax.Array,
             valid: jax.Array):
        """Mean cross-entropy of ``labels`` [S, T] over the positions
        ``valid`` marks -> (loss, the step's ``step_scalars``)."""
        x, loads, computed = self.hidden(params, emb)
        scalars = dict(moe_load_scalars(loads, computed),
                       moe_choices=jnp.float32(
                           labels.size * self.top_k
                           * self.mlp_kinds.count("sparse")))
        return (head_loss(x, params["norm"], params["head"], labels, valid,
                          self.eps, self.dtype), scalars)
