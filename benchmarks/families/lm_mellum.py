"""Family ``lm_mellum``: family ``lm`` (``lm.py``: a record is one token,
packed documents, token vectors in the table's rows) for a model whose
layers ``lm.py`` cannot count: ``models/mellum.py``, sliding-window or
full rotary attention followed by routed gated experts, in every layer.
The pool, the seeded weights, the sample, the first pass, the reference's
pass, the compared numbers, the diagnostics, the control and the faults
are ``lm.py``'s own, by import; ``layer_params`` and ``work`` are this
file's: a sliding layer's score and value products count the pairs inside
the windows, not the causal half.
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
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    qh, kvh = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])
    share = int(config["num_experts_per_tok"]) * int(config["num_experts"]) \
        / int(config["router_outputs"])
    return {
        "attention": d * hd * (2 * qh + 2 * kvh) + 2 * hd,
        "route": d * int(config["router_outputs"]),
        "experts": share * 3 * d * int(config["moe_intermediate_size"]),
        "head": d * int(config["vocab_size"]),
    }


def keys_per_query(t: int, window: int) -> float:
    """Keys a query of a sliding layer reads, the mean over a sequence of
    ``t`` positions: position p reads min(p + 1, window)."""
    w = min(int(window), t)
    return (w * (w + 1) / 2 + (t - w) * w) / t


def work(config: dict, traffic: dict, chips: int, param_shapes) -> dict:
    """What one step needs on one chip, from shapes alone, counted as
    ``lm.work`` counts: operations of the forward and backward pass (6 a
    parameter a token passes through, plus attention's score and value
    products: the causal half in a full layer, the windows' pairs in a
    sliding one; nothing recomputed counts), bytes (every dense parameter
    and Adam's two moments read and written once, the rows a step touches
    three times), and the same by ``pbox.*`` scope for the scopes that
    have a roofline of their own."""
    tokens = int(traffic["batch_per_chip"])
    t = int(traffic["seq_len"])
    kinds = list(config["layer_types"])
    n_f, n_s = kinds.count("full_attention"), kinds.count("sliding_attention")
    n_e = len(kinds)
    lp = layer_params(config)
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    qh, kvh = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])
    held, mff = int(config["num_experts"]), \
        int(config["moe_intermediate_size"])

    # a token, forward: the two products over the keys a query reads
    full_token = 2 * 2 * (t / 2) * hd * qh
    window_token = 2 * 2 * keys_per_query(t, config["sliding_window"]) \
        * hd * qh
    per_token = 6.0 * ((n_f + n_s) * lp["attention"]
                       + n_e * (lp["route"] + lp["experts"]) + lp["head"]) \
        + 3.0 * (n_f * full_token + n_s * window_token)

    n_params = float(sum(np.prod(s) for s in param_shapes)) \
        - int(config["vocab_size"]) * d     # the vectors live in the table
    rows = float(np.sum(-np.expm1(tokens * np.log1p(-traffic_mod.rank_pmf(
        int(config["vocab_size"]) - 1, traffic)))))
    f32 = 4

    def attention_scope(n_layers: int, scores_token: float) -> dict:
        return {
            "flops": (6.0 * lp["attention"] + 3.0 * scores_token)
            * tokens * n_layers,
            "bytes": n_layers * (3.0 * lp["attention"] * f32 + 3.0 * tokens
                                 * f32 * (2 * d + hd * (qh + 2 * kvh)))}

    scopes = {
        "pbox.attn": attention_scope(n_f, full_token),
        "pbox.attn_window": attention_scope(n_s, window_token),
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
