"""Nemotron-H: a hybrid language-model backbone in which every layer is
ONE mixer behind a pre-norm residual, its kind read from a pattern string:

    ``M``  Mamba-2 mixer           (ops/ssd.py: the chunked scan)
    ``*``  causal grouped-query attention, no position embedding
                                   (ops/causal_attention.py)
    ``E``  routed experts + one shared expert, relu^2, no gate branch
                                   (parallel/moe.py: dropless routing over
                                   the experts this chip holds)

then a final RMSNorm and an untied head. The embedding is NOT here: a
token's vector is a row of the table, pulled unpooled by the train step
(``train/step.SeqTrainStep``), which hands ``loss`` the vectors [S, T,
hidden] and takes back their gradient for the in-row rule.

Not a flax module: parameters are a plain tree (``init``), so that a
seeded tree of the same shape can be put in their place leaf for leaf.
``config`` is the model's published ``config.json`` by its own keys (see
``benchmarks/configs/nemotron3-nano-30b-a3b.json``); ``n_routed_experts``
counts the experts HELD here, ``router_outputs`` all the experts the
router chooses among, ``first_expert_held`` where this chip's run starts.

Precision: parameters, router, scan state, norms, softmax and loss
float32; matrix products with ``compute_dtype`` (bfloat16) operands and
float32 accumulation. Every op sits under one ``pbox.*`` scope of
``obs/trace``'s catalog; every layer is one ``jax.checkpoint`` that keeps
its input, an attention layer's also its forward block loops' two results
(``lm_parts.KEEP_ATTN_LOOPS``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from paddlebox_tpu.models.lm_parts import (ATTN_BLOCK, KEEP_ATTN_LOOPS,
                                           MOE_STEP_SCALARS, head_loss,
                                           matmul, moe_load_scalars,
                                           rms_norm)
from paddlebox_tpu.obs import trace
from paddlebox_tpu.ops.causal_attention import causal_gqa_attention
from paddlebox_tpu.ops.short_conv import causal_depthwise_conv
from paddlebox_tpu.ops.ssd import ssd_scan
from paddlebox_tpu.parallel.moe import route_top_k, routed_experts

_scope = jax.named_scope


class NemotronH:
    #: ``Trainer`` builds ``SeqTrainStep`` for such a model
    sequence_model = True
    #: the scalars ``loss`` hands out a step beside the loss
    step_scalars = MOE_STEP_SCALARS

    def __init__(self, config: Dict[str, Any],
                 compute_dtype=jnp.bfloat16) -> None:
        c = config
        self.pattern = str(c["hybrid_override_pattern"])
        if set(self.pattern) - set("M*E"):
            raise ValueError(f"layer kinds of {self.pattern!r} are not all "
                             f"M, * or E")
        self.d = int(c["hidden_size"])
        self.vocab = int(c["vocab_size"])
        self.eps = float(c["layer_norm_epsilon"])
        self.h, self.p = int(c["mamba_num_heads"]), int(c["mamba_head_dim"])
        self.g, self.n = int(c["n_groups"]), int(c["ssm_state_size"])
        self.conv_k, self.chunk = int(c["conv_kernel"]), int(c["chunk_size"])
        self.di = self.h * self.p
        self.conv_dim = self.di + 2 * self.g * self.n
        self.qh = int(c["num_attention_heads"])
        self.kvh = int(c["num_key_value_heads"])
        self.hd = int(c["head_dim"])
        self.ff = int(c["moe_intermediate_size"])
        self.sff = int(c["moe_shared_expert_intermediate_size"])
        self.experts = int(c["router_outputs"])
        lo = int(c.get("first_expert_held", 0))
        self.held = (lo, lo + int(c["n_routed_experts"]))
        self.top_k = int(c["num_experts_per_tok"])
        self.route_scale = float(c["routed_scaling_factor"])
        self.dt_range = (float(c["time_step_min"]), float(c["time_step_max"]),
                         float(c["time_step_floor"]))
        self.dtype = compute_dtype

    # ---- parameters ----
    def init(self, key: jax.Array):
        """normal(0, 0.02) matrices, the projections that write to the
        residual stream divided by sqrt(layers), norms 1, Mamba-2's own
        starts for ``A_log``, ``dt_bias`` and ``D``."""
        d, f32 = self.d, jnp.float32
        std, res = 0.02, 0.02 / math.sqrt(len(self.pattern))

        def normal(k, shape, s):
            return jax.random.normal(k, shape, f32) * s

        layers = []
        for i, kind in enumerate(self.pattern):
            ks = jax.random.split(jax.random.fold_in(key, i), 6)
            lay = {"norm": jnp.ones((d,), f32)}
            if kind == "M":
                lo, hi, floor = self.dt_range
                dt = jnp.exp(jax.random.uniform(ks[4], (self.h,), f32)
                             * (math.log(hi) - math.log(lo)) + math.log(lo))
                dt = jnp.maximum(dt, floor)
                bound = self.conv_k ** -0.5
                lay.update(
                    in_proj=normal(ks[0], (d, self.di + self.conv_dim
                                           + self.h), std),
                    conv_w=jax.random.uniform(
                        ks[1], (self.conv_k, self.conv_dim), f32, -bound,
                        bound),
                    conv_b=jax.random.uniform(ks[2], (self.conv_dim,), f32,
                                              -bound, bound),
                    dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                    A_log=jnp.log(jax.random.uniform(ks[3], (self.h,), f32,
                                                     1.0, 16.0)),
                    D=jnp.ones((self.h,), f32),
                    gate_norm=jnp.ones((self.di,), f32),
                    out_proj=normal(ks[5], (self.di, d), res))
            elif kind == "*":
                lay.update(
                    q=normal(ks[0], (d, self.qh * self.hd), std),
                    k=normal(ks[1], (d, self.kvh * self.hd), std),
                    v=normal(ks[2], (d, self.kvh * self.hd), std),
                    o=normal(ks[3], (self.qh * self.hd, d), res))
            else:
                n_held = self.held[1] - self.held[0]
                lay.update(
                    router=normal(ks[0], (d, self.experts), std),
                    router_bias=jnp.zeros((self.experts,), f32),
                    up=normal(ks[2], (n_held, d, self.ff), std),
                    down=normal(ks[3], (n_held, self.ff, d), res),
                    shared_up=normal(ks[4], (d, self.sff), std),
                    shared_down=normal(ks[5], (self.sff, d), res))
            layers.append(lay)
        return {"layers": layers, "final_norm": jnp.ones((d,), f32),
                "head": normal(jax.random.fold_in(key, len(self.pattern)),
                               (d, self.vocab), std)}

    # ---- pieces ----
    def _mm(self, x, w):
        return matmul(x, w, self.dtype)

    def _norm(self, x, weight):
        return rms_norm(x, weight, self.eps)

    def _mamba(self, lay, x):
        s, t, _ = x.shape
        h, p, g, n, di = self.h, self.p, self.g, self.n, self.di
        with _scope(trace.SCOPE_SSM_PROJ):
            # a step's channels side by side in memory, as the scan's
            # kernels take them: left to itself XLA lays the forward
            # pass's conv out with time in the lanes and copies into and
            # out of the kernels (8 ms a step; PERF.md section 6, PR 32)
            proj = with_layout_constraint(
                self._mm(self._norm(x, lay["norm"]), lay["in_proj"]),
                Layout(major_to_minor=(0, 1, 2)))
            gate, xbc, dt = jnp.split(proj, [di, di + self.conv_dim], -1)
        with _scope(trace.SCOPE_SSM_CONV):
            xbc = jax.nn.silu(causal_depthwise_conv(xbc, lay["conv_w"])
                              + lay["conv_b"])
        with _scope(trace.SCOPE_SSM_SCAN):
            xs, b, c = jnp.split(xbc, [di, di + g * n], -1)
            xs = xs.reshape(s, t, h, p)
            y = ssd_scan(xs, jax.nn.softplus(dt + lay["dt_bias"]),
                         -jnp.exp(lay["A_log"]), b.reshape(s, t, g, n),
                         c.reshape(s, t, g, n), chunk=self.chunk,
                         mm_dtype=self.dtype, skip=lay["D"])
            y = y.reshape(s, t, di)
        with _scope(trace.SCOPE_SSM_PROJ):
            y = (y * jax.nn.silu(gate)).reshape(s, t, g, di // g)
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, -1, keepdims=True) + self.eps)
            y = y.reshape(s, t, di) * lay["gate_norm"]
            return x + self._mm(y, lay["out_proj"])

    def _attention(self, lay, x):
        s, t, _ = x.shape
        with _scope(trace.SCOPE_ATTN):
            u = self._norm(x, lay["norm"])
            q = self._mm(u, lay["q"]).reshape(s, t, self.qh, self.hd)
            k = self._mm(u, lay["k"]).reshape(s, t, self.kvh, self.hd)
            v = self._mm(u, lay["v"]).reshape(s, t, self.kvh, self.hd)
            o = causal_gqa_attention(q, k, v, block=ATTN_BLOCK,
                                     mm_dtype=self.dtype)
            return x + self._mm(o.reshape(s, t, self.qh * self.hd),
                                lay["o"])

    def _moe(self, lay, x) -> Tuple[jax.Array, jax.Array, jax.Array]:
        s, t, d = x.shape
        with _scope(trace.SCOPE_MOE_ROUTE):
            u = self._norm(x, lay["norm"]).reshape(s * t, d)
            idx, w = route_top_k(u, lay["router"], lay["router_bias"],
                                 self.top_k, self.route_scale)
        with _scope(trace.SCOPE_MOE_EXPERTS):
            y, stats = routed_experts(u, idx, w, lay["up"], lay["down"],
                                      self.held, mm_dtype=self.dtype)
        with _scope(trace.SCOPE_MOE_SHARED):
            hid = jnp.square(jax.nn.relu(self._mm(u, lay["shared_up"])))
            y = y + self._mm(hid, lay["shared_down"])
            return x + y.reshape(s, t, d), stats["load"], stats["rows"]

    # ---- the stack, the head and the loss ----
    def hidden(self, params, emb: jax.Array):
        """Token vectors [S, T, hidden] -> (the last layer's output, the
        token-choices each held expert took in each ``E`` layer, int32
        [E layers, held], the rows each ``E`` layer's loops computed,
        int32 [E layers])."""
        x, loads, rows = emb, [], []
        for kind, lay in zip(self.pattern, params["layers"]):
            if kind == "M":
                # sequences are independent in a mixer over time: one at
                # a time, so that the layer's temporaries (the widest of
                # the three kinds') are one sequence's
                one = jax.checkpoint(
                    lambda lay, xs: self._mamba(lay, xs[None])[0])
                x = jax.lax.map(lambda xs, lay=lay: one(lay, xs), x)
            elif kind == "*":
                x = jax.checkpoint(self._attention,
                                   policy=KEEP_ATTN_LOOPS)(lay, x)
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
            return self._mm(self._norm(x, params["final_norm"]),
                            params["head"])

    def loss(self, params, emb: jax.Array, labels: jax.Array,
             valid: jax.Array):
        """Mean cross-entropy of ``labels`` [S, T] over the positions
        ``valid`` marks -> (loss, the step's ``step_scalars``)."""
        x, loads, computed = self.hidden(params, emb)
        return (head_loss(x, params["final_norm"], params["head"], labels,
                          valid, self.eps, self.dtype),
                moe_load_scalars(loads, computed))
