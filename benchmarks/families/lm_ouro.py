"""Family ``lm_ouro``: family ``lm`` (``lm.py``: a record is one token,
packed documents, token vectors in the table's rows) for a model whose
step ``lm.py`` cannot count: ``models/ouro.py``, one stack of attention +
dense feed-forward layers run ``total_ut_steps`` times with the same
weights, the head read after every run. The pool, the seeded weights, the
sample, the first pass, the reference's pass, the compared numbers, the
diagnostics and the control are ``lm.py``'s own, by import;
``layer_params``, ``work`` and ``FAULTS`` are this file's: a layer counts
once a run of the loop and the head once an exit, and the second fault
leaves a run of the loop out (``reference/models/ouro.py``), which no
other family's model can.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks import traffic as traffic_mod
from benchmarks.families.lm import (NUM_FIXED,  # noqa: F401
                                    control_precision, diagnostics,
                                    first_pass, make_pool, numbers,
                                    reference_pass, sample, seeded_params)

#: planted in the reference by ``study.py``
FAULTS = ("state_unchanged", "loop_short")


def layer_params(config: dict) -> Dict[str, float]:
    """Parameters of each sublayer of one layer (its two norms with it),
    and of the head, that a token passes through once a run."""
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    qh, kvh = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])
    return {
        "attention": d * hd * (2 * qh + 2 * kvh) + 2 * d,
        "mlp": 3 * d * int(config["intermediate_size"]) + 2 * d,
        "head": d * int(config["vocab_size"]),
    }


def work(config: dict, traffic: dict, chips: int, param_shapes) -> dict:
    """What one step needs on one chip, from shapes alone, counted as
    ``lm.work`` counts: operations of the forward and backward pass (6 a
    parameter a token passes through, a layer counted ``total_ut_steps``
    times and the head once an exit, plus the causal half of attention's
    score and value products a run; nothing recomputed counts), bytes
    (every dense parameter and Adam's two moments read and written once,
    the rows a step touches three times), and the same by ``pbox.*``
    scope for attention and the feed-forward: a layer application reads
    its weights and its gradient is written, whichever run it is."""
    tokens = int(traffic["batch_per_chip"])
    t = int(traffic["seq_len"])
    exits = int(config["total_ut_steps"])
    applications = int(config["num_hidden_layers"]) * exits
    lp = layer_params(config)
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    qh, kvh = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])

    # a token, forward: the causal half of the score and value products
    attn_token = 2 * 2 * (t / 2) * hd * qh
    per_token = 6.0 * (applications * (lp["attention"] + lp["mlp"])
                       + exits * lp["head"]) \
        + 3.0 * applications * attn_token

    n_params = float(sum(np.prod(s) for s in param_shapes)) \
        - int(config["vocab_size"]) * d     # the vectors live in the table
    rows = float(np.sum(-np.expm1(tokens * np.log1p(-traffic_mod.rank_pmf(
        int(config["vocab_size"]) - 1, traffic)))))
    f32 = 4
    scopes = {
        "pbox.attn": {
            "flops": (6.0 * lp["attention"] + 3.0 * attn_token)
            * tokens * applications,
            "bytes": applications * (3.0 * lp["attention"] * f32 + 3.0
                                     * tokens * f32
                                     * (2 * d + hd * (qh + 2 * kvh)))},
        "pbox.mlp": {
            "flops": 6.0 * lp["mlp"] * tokens * applications,
            # the three matrices read forward and backward and their
            # gradient written; a token's vector in and out, forward and
            # as cotangents
            "bytes": applications * (3.0 * lp["mlp"] * f32
                                     + 3.0 * tokens * f32 * 2 * d)},
    }
    return {
        "flops": per_token * tokens,
        "bytes": n_params * f32 * 3 * 2 + rows * (NUM_FIXED + d) * f32 * 3
        + tokens * 8,
        "rows": rows, "tokens": tokens,
        "flops_per_example": per_token, "keys_per_example": 1,
        "scopes": scopes,
    }
