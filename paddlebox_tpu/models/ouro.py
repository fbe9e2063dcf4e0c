"""Ouro (``model_type`` ``ouro``): a looped language model. ONE stack of
identical layers is run ``total_ut_steps`` times with the same weights;
the normed output of a run is the input of the next, and the head and a
learned exit gate read it after every run. With token vectors ``e``,
``h_0 = e`` and r = 1..R:

    x = h_{r-1}
    for every layer (the same weights at every r), norms before AND
    after each sublayer:
        x <- x + attn_out_norm(attention(attn_norm(x)))
        x <- x + ffn_out_norm(down(silu(gate(u)) * up(u))),  u = ffn_norm(x)
    h_r   = norm(x)                      the final norm, inside the loop
    z_r   = h_r head                     logits of exit r
    lam_r = sigmoid(h_r . exit_w + exit_b)         one number a position
    p_r   = lam_r prod_{j<r} (1 - lam_j)  for r < R
    p_R   = prod_{j<R} (1 - lam_j)
    loss  = mean over positions of  sum_r p_r CE(z_r, label) - beta H(p)

Attention is causal multi-head attention over the packed sequence,
rotate-half rotary over the whole head, no bias and no norm on queries or
keys (ops/causal_attention.py through ``lm_parts.rotary_attention``). As
with ``models/mellum.py``, the embedding is NOT here (a token's vector is
a row of the table, ``train/step.SeqTrainStep``), the parameters are a
plain tree, and ``config`` is the model's published ``config.json`` by its
own keys (``benchmarks/configs/ouro-2.6b.json``) with the loss's
``exit_entropy_beta`` beside them.

The program holds ONE copy of the stack: the runs are a ``jax.lax.scan``
whose body is the layers, the weights closed over, so a weight's
cotangent sums over the runs inside the scan's backward pass. **Kept**
across the step, a run: each layer's input, the stack's output and its
normed form ``h_r`` (layers + 2 values of [S, T, hidden] a run, and the
gate's logits), and of every layer application the two results that
only attention's forward block loops can produce (the blocked output and
each row's log-sum-exp, ``lm_parts.KEEP_ATTN_LOOPS``: one more value of
[S, T, hidden] a layer and a run, so that the forward sweep runs once a
step). **Recomputed** in the backward pass: everything else inside a
layer (a layer is one ``jax.checkpoint``: both norm pairs, projections,
rotary, the feed-forward's hidden activation), and every slab of logits
(``lm_parts.head_nll``). A checkpoint a sublayer would keep one value
more a layer for the same operations.

Precision: parameters, norms, rotary embedding, the gate (its product,
sigmoid, the distribution, the entropy, the mixing), softmax and loss
float32; matrix products with ``compute_dtype`` (bfloat16) operands and
float32 accumulation. Every op sits under one ``pbox.*`` scope of
``obs/trace``'s catalog (``LOOP_SEQ_STEP_SCOPES``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from paddlebox_tpu.models.lm_parts import (KEEP_ATTN_LOOPS, head_nll,
                                           matmul, rms_norm,
                                           rotary_attention)
from paddlebox_tpu.obs import trace

_scope = jax.named_scope


def exit_distribution(gate_logits: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Gate logits [runs, ...] -> (p, log p) [runs, ...], float32: the
    probability of leaving at each run, the last run taking what is left.
    Worked in logarithms (``log lam = log_sigmoid(a)``, ``log (1 - lam) =
    log_sigmoid(-a)``), so that a gate that saturates gives p = 0 and
    p log p = 0, never a NaN."""
    leave = jax.nn.log_sigmoid(gate_logits)
    stay = jax.nn.log_sigmoid(-gate_logits)
    stayed = jnp.cumsum(stay, axis=0) - stay            # over the runs before
    log_p = jnp.concatenate([(leave + stayed)[:-1], stayed[-1:]], axis=0)
    return jnp.exp(log_p), log_p


class OuroLoop:
    #: ``Trainer`` builds ``SeqTrainStep`` for such a model
    sequence_model = True
    #: the scalars ``loss`` hands out a step beside the loss: the
    #: positions it averaged over, over them the sums of the expected
    #: exit ``sum_r r p_r`` and of the exit distribution's entropy, and
    #: the last exit's plain mean cross-entropy (what a reader holds
    #: against a model that is not looped)
    step_scalars = {"loop_positions": "sum", "loop_exit_step_sum": "sum",
                    "loop_exit_entropy_sum": "sum",
                    "loop_last_exit_loss": "mean"}

    def __init__(self, config: Dict[str, Any],
                 compute_dtype=jnp.bfloat16) -> None:
        c = config
        kinds = tuple(c["layer_types"])
        if set(kinds) != {"full_attention"}:
            raise ValueError(f"layer types {kinds} are not all "
                             f"full_attention")
        if len(kinds) != int(c["num_hidden_layers"]):
            raise ValueError(f"{len(kinds)} layer types for "
                             f"{c['num_hidden_layers']} layers")
        self.layers = len(kinds)
        self.runs = int(c["total_ut_steps"])
        if self.runs < 1:
            raise ValueError(f"a loop of {self.runs} runs has no exit")
        self.beta = float(c["exit_entropy_beta"])
        self.d = int(c["hidden_size"])
        self.vocab = int(c["vocab_size"])
        self.eps = float(c["rms_norm_eps"])
        self.heads = (int(c["num_attention_heads"]),
                      int(c["num_key_value_heads"]), int(c["head_dim"]))
        self.rotary = {"theta": float(c["rope_theta"])}
        self.ff = int(c["intermediate_size"])
        self.dtype = compute_dtype

    # ---- parameters ----
    def init(self, key: jax.Array):
        """normal(0, 0.02) matrices and gate weight, the gate's bias 0,
        norms 1. No projection is scaled down by the depth: a sublayer's
        output passes a norm before it joins the stream."""
        d, f32 = self.d, jnp.float32
        qh, kvh, hd = self.heads

        def normal(k, shape):
            return jax.random.normal(k, shape, f32) * 0.02

        layers = []
        for i in range(self.layers):
            ks = jax.random.split(jax.random.fold_in(key, i), 7)
            layers.append({
                "attn_norm": jnp.ones((d,), f32),
                "attn_out_norm": jnp.ones((d,), f32),
                "ffn_norm": jnp.ones((d,), f32),
                "ffn_out_norm": jnp.ones((d,), f32),
                "q": normal(ks[0], (d, qh * hd)),
                "k": normal(ks[1], (d, kvh * hd)),
                "v": normal(ks[2], (d, kvh * hd)),
                "o": normal(ks[3], (qh * hd, d)),
                "gate": normal(ks[4], (d, self.ff)),
                "up": normal(ks[5], (d, self.ff)),
                "down": normal(ks[6], (self.ff, d))})
        kh, kg = jax.random.split(jax.random.fold_in(key, self.layers))
        return {"layers": layers, "norm": jnp.ones((d,), f32),
                "exit_w": normal(kg, (d,)), "exit_b": jnp.zeros((), f32),
                "head": normal(kh, (d, self.vocab))}

    # ---- pieces ----
    def _mm(self, x, w):
        return matmul(x, w, self.dtype)

    def _norm(self, x, weight):
        return rms_norm(x, weight, self.eps)

    def _layer(self, lay, x):
        with _scope(trace.SCOPE_ATTN):
            y = rotary_attention(self._norm(x, lay["attn_norm"]), lay,
                                 self.heads, self.eps, self.dtype,
                                 self.rotary)
            x = x + self._norm(y, lay["attn_out_norm"])
        with _scope(trace.SCOPE_MLP):
            u = self._norm(x, lay["ffn_norm"])
            hid = jax.nn.silu(self._mm(u, lay["gate"])) \
                * self._mm(u, lay["up"])
            return x + self._norm(self._mm(hid, lay["down"]),
                                  lay["ffn_out_norm"])

    # ---- the loop, the exits and the loss ----
    def exits(self, params, emb: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Token vectors [S, T, hidden] -> (every run's normed output
        ``h_r`` [runs, S, T, hidden], the exit gate's logits [runs, S,
        T])."""
        layer = jax.checkpoint(self._layer, policy=KEEP_ATTN_LOOPS)

        def run(x, _):
            for lay in params["layers"]:
                x = layer(lay, x)
            with _scope(trace.SCOPE_HEAD):
                h = self._norm(x, params["norm"])
            with _scope(trace.SCOPE_EXIT_GATE):
                gate = jnp.sum(h * params["exit_w"], -1) + params["exit_b"]
            return h, (h, gate)

        _, out = jax.lax.scan(run, emb, None, length=self.runs)
        return out

    def logits(self, params, emb: jax.Array) -> jax.Array:
        """The last exit's logits [S, T, vocab]."""
        hs, _ = self.exits(params, emb)
        with _scope(trace.SCOPE_HEAD):
            return self._mm(hs[-1], params["head"])

    def loss(self, params, emb: jax.Array, labels: jax.Array,
             valid: jax.Array):
        """The expected-exit loss of ``labels`` [S, T] over the positions
        ``valid`` marks -> (loss, the step's ``step_scalars``)."""
        hs, gate = self.exits(params, emb)
        nll = head_nll(hs, None, params["head"], labels, self.eps,
                       self.dtype)                    # [runs, slabs, rows]
        with _scope(trace.SCOPE_EXIT_GATE):
            p, log_p = exit_distribution(gate.reshape(nll.shape))
            ok = valid.reshape(nll.shape[1:]).astype(jnp.float32)
            count = jnp.sum(ok)
            entropy = -jnp.sum(p * log_p, axis=0)
            mixed = jnp.sum(p * nll, axis=0) - self.beta * entropy
            run_no = jnp.arange(1, self.runs + 1, dtype=jnp.float32)
            exit_step = jnp.sum(run_no[:, None, None] * p, axis=0)
            mean = jnp.maximum(count, 1.0)
            scalars = {
                "loop_positions": count,
                "loop_exit_step_sum": jnp.sum(exit_step * ok),
                "loop_exit_entropy_sum": jnp.sum(entropy * ok),
                "loop_last_exit_loss": jnp.sum(nll[-1] * ok) / mean}
            return jnp.sum(mixed * ok) / mean, scalars
