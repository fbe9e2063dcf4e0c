"""Family ``lm_lfm2``: family ``lm`` (``lm.py``: a record is one token,
packed documents, token vectors in the table's rows) for a model whose
layers ``lm.py`` cannot count: ``models/lfm2.py``, a gated short
convolution or rotary attention followed by a dense or an expert
feed-forward, in every layer. The pool, the seeded weights, the sample,
the first pass, the reference's pass, the compared numbers, the
diagnostics, the control and the faults are ``lm.py``'s own, by import;
``layer_params`` and ``work`` are this file's.
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
    qh, kvh = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])
    hd = d // qh
    share = int(config["num_experts_per_tok"]) * int(config["num_experts"]) \
        / int(config["router_outputs"])
    return {
        "conv": d * 3 * d + int(config["conv_L_cache"]) * d + d * d,
        "full_attention": d * hd * (2 * qh + 2 * kvh) + 2 * hd,
        "mlp": 3 * d * int(config["intermediate_size"]),
        "route": d * int(config["router_outputs"]),
        "experts": share * 3 * d * int(config["moe_intermediate_size"]),
        "head": d * int(config["vocab_size"]),
    }


def work(config: dict, traffic: dict, chips: int, param_shapes) -> dict:
    """What one step needs on one chip, from shapes alone, counted as
    ``lm.work`` counts: operations of the forward and backward pass (6 a
    parameter a token passes through, plus causal attention's scores;
    nothing recomputed counts), bytes (every dense parameter and Adam's
    two moments read and written once, the rows a step touches three
    times), and the same by ``pbox.*`` scope for the scopes that have a
    roofline of their own."""
    tokens = int(traffic["batch_per_chip"])
    t = int(traffic["seq_len"])
    kinds = list(config["layer_types"])
    n_c, n_a = kinds.count("conv"), kinds.count("full_attention")
    n_d = int(config["num_dense_layers"])
    n_e = len(kinds) - n_d
    lp = layer_params(config)
    d = int(config["hidden_size"])
    qh, kvh = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])
    hd = d // qh
    held, mff = int(config["num_experts"]), \
        int(config["moe_intermediate_size"])

    # a token, forward: the causal half of the score and value products
    attn_token = 2 * 2 * (t / 2) * hd * qh
    per_token = 6.0 * (n_c * lp["conv"] + n_a * lp["full_attention"]
                       + n_d * lp["mlp"] + n_e * (lp["route"] + lp["experts"])
                       + lp["head"]) + 3.0 * n_a * attn_token

    n_params = float(sum(np.prod(s) for s in param_shapes)) \
        - int(config["vocab_size"]) * d     # the vectors live in the table
    rows = float(np.sum(-np.expm1(tokens * np.log1p(-traffic_mod.rank_pmf(
        int(config["vocab_size"]) - 1, traffic)))))
    f32 = 4
    scopes = {
        "pbox.attn": {
            "flops": (6.0 * lp["full_attention"] + 3.0 * attn_token)
            * tokens * n_a,
            "bytes": n_a * (3.0 * lp["full_attention"] * f32 + 3.0 * tokens
                            * f32 * (2 * d + hd * (qh + 2 * kvh)))},
        "pbox.moe_experts": {
            "flops": 6.0 * lp["experts"] * tokens * n_e,
            # every held expert's three matrices read forward and backward
            # and their gradient written; a token-choice's row in and out
            "bytes": n_e * (3.0 * held * 3 * d * mff * f32
                            + 3.0 * lp["experts"] / (3 * d * mff)
                            * tokens * 2 * d * f32)},
        "pbox.conv_mix": {
            # [B | C | v] read and y written, forward and as cotangents;
            # three taps a channel are no operations worth counting
            "flops": 0.0,
            "bytes": 3.0 * n_c * tokens * 4 * d * f32},
    }
    return {
        "flops": per_token * tokens,
        "bytes": n_params * f32 * 3 * 2 + rows * (NUM_FIXED + d) * f32 * 3
        + tokens * 8,
        "rows": rows, "tokens": tokens,
        "flops_per_example": per_token, "keys_per_example": 1,
        "scopes": scopes,
    }
