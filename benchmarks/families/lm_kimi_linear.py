"""Family ``lm_kimi_linear``: family ``lm`` (``lm.py``: a record is one
token, packed documents, token vectors in the table's rows) for a model
whose layers ``lm.py`` cannot count: ``models/kimi_linear.py``, Kimi Delta
Attention or latent attention followed by a dense or an expert
feed-forward with a shared expert, in every layer. The pool, the seeded
weights, the sample, the first pass, the reference's pass, the compared
numbers, the diagnostics, the control and the faults are ``lm.py``'s own,
by import; ``layer_params`` and ``work`` are this file's. The delta
rule's count is what its recurrence needs (``scan_token``), whatever
chunk the program computes it in.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks import traffic as traffic_mod
from benchmarks.families.lm import (FAULTS, NUM_FIXED,  # noqa: F401
                                    control_precision, diagnostics,
                                    first_pass, make_pool, numbers,
                                    reference_pass, sample, seeded_params)


def layer_params(config: dict) -> Dict[str, float]:
    """Parameters of one sublayer of each kind, and of the head, that a
    token passes through on this chip: a routed expert counts by the
    share of token-choices that fall on the experts held."""
    d = int(config["hidden_size"])
    kh, kd = int(config["linear_attn_num_heads"]), \
        int(config["linear_attn_head_dim"])
    hk, taps = kh * kd, int(config["linear_attn_short_conv_kernel_size"])
    qh, rank = int(config["num_attention_heads"]), \
        int(config["kv_lora_rank"])
    nope, rope, vd = (int(config[k]) for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    mff = int(config["moe_intermediate_size"])
    share = int(config["num_experts_per_token"]) \
        * int(config["num_experts"]) / int(config["router_outputs"])
    return {
        # q, k, v and their convs; decay (two ranks, A_log, dt_bias);
        # beta; the output gate (two ranks and its bias); the head norm; o
        "kda": 3 * d * hk + 3 * taps * hk + d * kd + kd * hk + kh + hk
        + d * kh + d * kd + kd * hk + hk + kd + hk * d,
        "mla": d * qh * (nope + rope) + d * (rank + rope) + rank
        + rank * qh * (nope + vd) + qh * vd * d,
        "mlp": 3 * d * int(config["intermediate_size"]),
        "route": d * int(config["router_outputs"]),
        "shared": 3 * d * mff * int(config["num_shared_experts"]),
        "experts": share * 3 * d * mff,
        "head": d * int(config["vocab_size"]),
    }


def work(config: dict, traffic: dict, chips: int, param_shapes) -> dict:
    """What one step needs on one chip, from shapes alone, counted as
    ``lm.work`` counts: operations of the forward and backward pass (6 a
    parameter a token passes through, plus latent attention's score and
    value products over the causal half and the delta rule's state
    products; nothing recomputed counts), bytes (every dense parameter
    and Adam's two moments read and written once, the rows a step touches
    three times), and the same by ``pbox.*`` scope for the scopes that
    have a roofline of their own."""
    tokens = int(traffic["batch_per_chip"])
    t = int(traffic["seq_len"])
    pattern = str(config["layer_pattern"])
    n_k, n_m = pattern.count("K"), pattern.count("M")
    n_d = int(config["first_k_dense_replace"])
    n_e = len(pattern) - n_d
    lp = layer_params(config)
    d = int(config["hidden_size"])
    kh, kd = int(config["linear_attn_num_heads"]), \
        int(config["linear_attn_head_dim"])
    qh = int(config["num_attention_heads"])
    qk = int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
    vd = int(config["v_head_dim"])
    held, mff = int(config["num_experts"]), \
        int(config["moe_intermediate_size"])

    # a token, forward: the causal half of the score (qk wide) and value
    # (vd wide) products
    attn_token = 2 * (t / 2) * qh * (qk + vd)
    # the delta rule a token and head: S^T k, the rank-1 write, S^T q
    scan_token = 3 * 2 * kd * kd * kh
    per_token = 6.0 * (n_k * lp["kda"] + n_m * lp["mla"] + n_d * lp["mlp"]
                       + n_e * (lp["route"] + lp["shared"] + lp["experts"])
                       + lp["head"]) \
        + 3.0 * (n_m * attn_token + n_k * scan_token)

    n_params = float(sum(np.prod(s) for s in param_shapes)) \
        - int(config["vocab_size"]) * d     # the vectors live in the table
    rows = float(np.sum(-np.expm1(tokens * np.log1p(-traffic_mod.rank_pmf(
        int(config["vocab_size"]) - 1, traffic)))))
    f32 = 4
    scopes = {
        "pbox.kda_scan": {
            "flops": 3.0 * scan_token * tokens * n_k,
            # q, k, v, g and beta read and o written, forward and as
            # cotangents
            "bytes": 3.0 * n_k * tokens * f32 * kh * (4 * kd + 1 + kd)},
        "pbox.attn": {
            "flops": (6.0 * lp["mla"] + 3.0 * attn_token) * tokens * n_m,
            "bytes": n_m * (3.0 * lp["mla"] * f32 + 3.0 * tokens * f32
                            * (2 * d + qh * (2 * qk + vd)))},
        "pbox.mlp": {
            "flops": 6.0 * lp["mlp"] * tokens * n_d,
            # the three matrices read forward and backward and their
            # gradient written; a token's vector in and out
            "bytes": n_d * (3.0 * lp["mlp"] * f32
                            + 3.0 * tokens * f32 * 2 * d)},
        "pbox.moe_experts": {
            "flops": 6.0 * lp["experts"] * tokens * n_e,
            # every held expert's three matrices read forward and backward
            # and their gradient written; a token-choice's row in and out
            "bytes": n_e * (3.0 * held * 3 * d * mff * f32
                            + 3.0 * lp["experts"] / (3 * d * mff)
                            * tokens * 2 * d * f32)},
    }
    return {
        "flops": per_token * tokens,
        "bytes": n_params * f32 * 3 * 2 + rows * (NUM_FIXED + d) * f32 * 3
        + tokens * 8,
        "rows": rows, "tokens": tokens,
        "flops_per_example": per_token, "keys_per_example": 1,
        "scopes": scopes,
    }
