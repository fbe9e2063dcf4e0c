"""Mellum 2 (``model_type`` ``mellum``): a language-model backbone in
which every layer is attention then routed experts, behind pre-norm
residuals:

    x <- x + attention(attn_norm(x));  x <- x + experts(ffn_norm(x))

The attention's kind is read from ``layer_types``:

    ``full_attention``     causal grouped-query attention over the whole
                           sequence, YaRN's rotary table
    ``sliding_attention``  the same over the ``sliding_window`` keys that
                           end at the query, the plain rotary table

(ops/causal_attention.py: one op, ``window=`` says which; queries and keys
are RMS-normed over the head before the rotation), and the feed-forward
is always ``num_experts_per_tok`` of the router's experts by softmax,
gated, none shared and no dense layer anywhere (parallel/moe.py: dropless
routing over the experts this chip holds). Then the final norm and an
untied head. As with ``models/lfm2.py``, the embedding is NOT here (a
token's vector is a row of the table, ``train/step.SeqTrainStep``), the
parameters are a plain tree, and ``config`` is the model's published
``config.json`` by its own keys
(``benchmarks/configs/mellum2-12b-a2.5b.json``); ``num_experts`` counts
the experts HELD here, ``router_outputs`` all the experts the router
chooses among, ``first_expert_held`` where this chip's run starts.

Precision: parameters, router, norms, rotary embedding, softmax and loss
float32; matrix products with ``compute_dtype`` (bfloat16) operands and
float32 accumulation. Every op sits under one ``pbox.*`` scope of
``obs/trace``'s catalog (a sliding layer's attention under
``pbox.attn_window``, a full layer's under ``pbox.attn``); every sublayer
is one ``jax.checkpoint`` that keeps its input, an attention sublayer's
also its forward block loops' two results (``lm_parts.KEEP_ATTN_LOOPS``).
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
from paddlebox_tpu.ops.causal_attention import yarn_inv_freq
from paddlebox_tpu.parallel.moe import route_top_k, routed_experts

_scope = jax.named_scope

KINDS = ("full_attention", "sliding_attention")


def rotary_table(dim: int, rope: Dict[str, Any]) -> Dict[str, Any]:
    """What ``rotary_embedding`` is told for one group of the published
    ``rope_parameters``: the base alone (``default``), or YaRN's inverse
    frequencies and the amplitude on cos and sin (``yarn``)."""
    base = float(rope["rope_theta"])
    if rope["rope_type"] == "default":
        return {"theta": base}
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not default "
                         f"or yarn")
    factor = float(rope["factor"])
    return {"inv_freq": yarn_inv_freq(
                dim, base, factor,
                int(rope["original_max_position_embeddings"]),
                float(rope["beta_fast"]), float(rope["beta_slow"])),
            "amplitude": float(rope.get("attention_factor")
                               or 0.1 * math.log(factor) + 1)}


class MellumMoe:
    #: ``Trainer`` builds ``SeqTrainStep`` for such a model
    sequence_model = True
    #: the scalars ``loss`` hands out a step beside the loss: the expert
    #: layers', and every choice the routers made, held here or not (what
    #: ``moe_choices_held`` is a share of)
    step_scalars = dict(MOE_STEP_SCALARS, moe_choices="sum")

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
        self.d = int(c["hidden_size"])
        self.vocab = int(c["vocab_size"])
        self.eps = float(c["rms_norm_eps"])
        self.qh = int(c["num_attention_heads"])
        self.kvh = int(c["num_key_value_heads"])
        self.hd = int(c["head_dim"])
        self.window = int(c["sliding_window"])
        #: a rotary table a layer kind, built once
        self.rotary = {kind: rotary_table(self.hd, c["rope_parameters"][kind])
                       for kind in KINDS}
        self.mff = int(c["moe_intermediate_size"])
        self.experts = int(c["router_outputs"])
        lo = int(c.get("first_expert_held", 0))
        self.held = (lo, lo + int(c["num_experts"]))
        self.top_k = int(c["num_experts_per_tok"])
        self.dtype = compute_dtype

    # ---- parameters ----
    def init(self, key: jax.Array):
        """normal(0, 0.02) matrices, the projections that write to the
        residual stream divided by sqrt(2 x layers), norms 1."""
        d, f32 = self.d, jnp.float32
        std, res = 0.02, 0.02 / math.sqrt(2 * len(self.kinds))
        n_held = self.held[1] - self.held[0]

        def normal(k, shape, s):
            return jax.random.normal(k, shape, f32) * s

        layers = []
        for i in range(len(self.kinds)):
            ks = jax.random.split(jax.random.fold_in(key, i), 8)
            layers.append({
                "attn_norm": jnp.ones((d,), f32),
                "ffn_norm": jnp.ones((d,), f32),
                "q": normal(ks[0], (d, self.qh * self.hd), std),
                "k": normal(ks[1], (d, self.kvh * self.hd), std),
                "v": normal(ks[2], (d, self.kvh * self.hd), std),
                "o": normal(ks[3], (self.qh * self.hd, d), res),
                "q_norm": jnp.ones((self.hd,), f32),
                "k_norm": jnp.ones((self.hd,), f32),
                "router": normal(ks[4], (d, self.experts), std),
                "gate": normal(ks[5], (n_held, d, self.mff), std),
                "up": normal(ks[6], (n_held, d, self.mff), std),
                "down": normal(ks[7], (n_held, self.mff, d), res)})
        return {"layers": layers, "norm": jnp.ones((d,), f32),
                "head": normal(jax.random.fold_in(key, len(self.kinds)),
                               (d, self.vocab), std)}

    # ---- pieces ----
    def _mm(self, x, w):
        return matmul(x, w, self.dtype)

    def _norm(self, x, weight):
        return rms_norm(x, weight, self.eps)

    def _attention(self, kind: str, lay, x):
        sliding = kind == "sliding_attention"
        with _scope(trace.SCOPE_ATTN_WINDOW if sliding
                    else trace.SCOPE_ATTN):
            return x + rotary_attention(
                self._norm(x, lay["attn_norm"]), lay,
                (self.qh, self.kvh, self.hd), self.eps, self.dtype,
                self.rotary[kind], window=self.window if sliding else None)

    def _moe(self, lay, x) -> Tuple[jax.Array, jax.Array, jax.Array]:
        s, t, d = x.shape
        with _scope(trace.SCOPE_MOE_ROUTE):
            u = self._norm(x, lay["ffn_norm"]).reshape(s * t, d)
            idx, w = route_top_k(u, lay["router"], None, self.top_k, 1.0,
                                 score=jax.nn.softmax)
        with _scope(trace.SCOPE_MOE_EXPERTS):
            y, stats = routed_experts(u, idx, w, lay["up"], lay["down"],
                                      self.held, mm_dtype=self.dtype,
                                      gate=lay["gate"])
            return x + y.reshape(s, t, d), stats["load"], stats["rows"]

    # ---- the stack, the head and the loss ----
    def hidden(self, params, emb: jax.Array):
        """Token vectors [S, T, hidden] -> (the last layer's output, the
        token-choices each held expert took in each layer, int32 [layers,
        held], the rows each layer's expert loops computed, int32
        [layers])."""
        x, loads, rows = emb, [], []
        for kind, lay in zip(self.kinds, params["layers"]):
            x = jax.checkpoint(self._attention, static_argnums=(0,),
                               policy=KEEP_ATTN_LOOPS)(kind, lay, x)
            x, load, computed = jax.checkpoint(self._moe)(lay, x)
            loads.append(load)
            rows.append(computed)
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
        scalars = dict(moe_load_scalars(loads, computed),
                       moe_choices=jnp.float32(
                           labels.size * self.top_k * len(self.kinds)))
        return (head_loss(x, params["norm"], params["head"], labels, valid,
                          self.eps, self.dtype), scalars)
