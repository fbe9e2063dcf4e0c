"""Family ``lm``: language models trained on packed documents. What one
record is: ONE TOKEN (a position of a sequence); the rate a cell reports
is tokens a second. A step is ``batch_per_chip`` tokens = whole sequences
of ``seq_len``; every position has an integer label (the next token), the
loss is the step's mean cross-entropy, and the token vectors are rows of
the table (one slot, one key a record, pulled unpooled).

The harness finds this file by the configuration's ``"family": "lm"`` and
knows nothing of what is inside ``what``, ``prog_state`` or ``ref``.

Traffic (``make_pool``): a sequence is documents packed end to end with no
mask between them; a document is id 0 followed by its tokens; lengths are
log-normal (``doc_len_median``, ``doc_len_sigma``), cut at the sequence;
tokens follow ``traffic.py``'s Zipf law over ids 1 .. vocab-1, the
popularity rank scattered over the ids. A sequence is generated one token
longer than ``seq_len``, so that its last position has a label too.

What is compared (``numbers``): the loss of each of the first pass's first
``compare.EARLY_STEPS`` steps, the worst leaf's parameter change over the
pass, and the table rows of the ids that only those first steps touched
(show counts exactly; the vector's change and the Adagrad sum relatively).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import compare, traffic as traffic_mod

#: the precision below the one a configuration states for its products
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}
#: planted in the reference by ``study.py``
FAULTS = ("state_unchanged", "experts_dropped")
NUM_FIXED = 8          # scalar columns of a table row before the vector
G2SUM_COL, SHOW_COL = 6, 0


@dataclasses.dataclass
class SeqPass:
    """One pass: ``tokens`` int32 [sequences, seq_len + 1]."""

    tokens: np.ndarray

    @property
    def inputs(self) -> np.ndarray:
        return self.tokens[:, :-1]

    @property
    def labels(self) -> np.ndarray:
        return self.tokens[:, 1:]

    @property
    def num_records(self) -> int:
        return int(self.inputs.size)


def make_pass(config: dict, traffic: dict, seed: int, index: int) -> SeqPass:
    t = int(traffic["seq_len"])
    n_seq = int(traffic["records_per_pass"]) // t
    vocab = int(config["vocab_size"])
    rng = np.random.default_rng([int(seed), int(index)])
    ranks = traffic_mod._draw_ranks(rng.random((n_seq, t + 1)), vocab - 1,
                                    traffic)
    tokens = (1 + traffic_mod.rank_to_id(ranks, vocab - 1)).astype(np.int32)
    # document starts: cumulative log-normal lengths, the first at 0
    n_docs = 4 + int(4 * (t + 1) / float(traffic["doc_len_median"]))
    lens = np.exp(rng.normal(np.log(float(traffic["doc_len_median"])),
                             float(traffic["doc_len_sigma"]),
                             (n_seq, n_docs)))
    lens = np.clip(np.rint(lens), 2, t).astype(np.int64)
    starts = np.concatenate([np.zeros((n_seq, 1), np.int64),
                             np.cumsum(lens, axis=1)], axis=1)
    if int(starts[:, -1].min()) <= t:
        raise ValueError("too few documents drawn to fill a sequence")
    rows = np.repeat(np.arange(n_seq), starts.shape[1])
    inside = starts.reshape(-1) <= t
    tokens[rows[inside], starts.reshape(-1)[inside]] = 0
    return SeqPass(tokens)


def make_pool(config: dict, traffic: dict, seed: int,
              count: Optional[int] = None) -> List[SeqPass]:
    n = int(traffic["pool_size"]) if count is None else count
    return [make_pass(config, traffic, seed, i) for i in range(n)]


def seeded_params(ref_model, config: dict, seed: int):
    """{"net": the dense weights, "embedding": the token vectors the
    table's rows start from}, made on the device in one jitted call."""
    import jax

    def init(key):
        return {"net": ref_model.init(key, config),
                "embedding": ref_model.init_embedding(key, config)}
    return jax.jit(init)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def _steps_of(pass0: SeqPass, traffic: dict) -> np.ndarray:
    """Step of every input position of a pass, [sequences, seq_len]."""
    seqs = int(traffic["batch_per_chip"]) // int(traffic["seq_len"])
    return np.broadcast_to((np.arange(len(pass0.inputs)) // seqs)[:, None],
                           pass0.inputs.shape)


def sample(pool, traffic: dict, seed: int) -> np.ndarray:
    """What the first pass is compared on: the ids that its first
    ``compare.EARLY_STEPS`` steps read and no later step does (such a row
    still holds what those steps wrote), a seeded ``check_rows`` of them
    at most, sorted."""
    last = np.full(int(pool[0].tokens.max()) + 1, -1, np.int64)
    np.maximum.at(last, pool[0].inputs.reshape(-1),
                  _steps_of(pool[0], traffic).reshape(-1))
    early = np.nonzero((last >= 0) & (last < compare.EARLY_STEPS))[0]
    count = int(traffic["check_rows"])
    if len(early) > count:
        rng = np.random.default_rng([int(seed), 7])
        early = np.sort(rng.choice(early, size=count, replace=False))
    return early.astype(np.int64)


def first_pass(entry, pool, traffic: dict, seed: int):
    """The first pass through the window's own call and feed, and what it
    trained: -> (compared ids, the program's state at them, seconds)."""
    t0 = time.perf_counter()
    entry.train(entry.wait())
    entry.block()
    t1 = time.perf_counter()
    ids = sample(pool, traffic, seed)
    state = entry.read_state(ids)
    return ids, state, {"first_pass_s": t1 - t0,
                        "read_state_s": time.perf_counter() - t1}


def reference_pass(loaded: dict, ref_model, pool, params, chips: int,
                   ids: np.ndarray, precision: Optional[str] = None,
                   fault: Optional[str] = None) -> dict:
    """The plain reference over the run's first pass, its rows cut to
    ``ids``. ``precision``: the matrix products' operands, as the
    configuration states where none is given (the control gives
    ``control_precision``)."""
    import jax
    from benchmarks.reference import lm
    config, traffic = loaded["config"], loaded["traffic"]
    if precision is None:
        precision = config["matmul_dtype"]
    seqs = int(traffic["batch_per_chip"]) * chips // int(traffic["seq_len"])
    ref = lm.run_pass(ref_model, config, pool[0].inputs, pool[0].labels,
                      seqs, params["net"], params["embedding"],
                      precision=precision, fault=fault)
    return {"losses": ref["loss_steps"],
            "rows": np.asarray(jax.device_get(ref["table"][np.asarray(ids)])),
            "params": jax.device_get(ref["params"])}


def numbers(prog_state: dict, ref: dict, init_params, loaded: dict, pool,
            chips: int, ids: np.ndarray) -> Dict[str, float]:
    """The numbers ``compare.judge`` holds against the cell's limits;
    ``prog_state`` is the program's first pass, or a second reference run
    in its place (the control, a planted fault)."""
    k = compare.EARLY_STEPS
    pl = np.asarray(prog_state["losses"], np.float64)[:k]
    rl = np.asarray(ref["losses"], np.float64)[:k]
    pr = np.asarray(prog_state["rows"], np.float64)
    rr = np.asarray(ref["rows"], np.float64)
    missing = np.isnan(pr).any(axis=1)
    pr = np.where(missing[:, None], 0.0, pr)
    start = np.asarray(init_params["embedding"], np.float64)[np.asarray(ids)]
    some = len(ids) > 0
    return {
        "loss": float(np.max(np.abs(pl - rl) / rl)),
        "dparam": compare.worst_leaf_gap(
            compare.tree_sub(prog_state["params"], init_params["net"]),
            compare.tree_sub(ref["params"], init_params["net"])),
        # show counts are sums of small integers: exact
        "rows_count": float(np.max(np.abs(pr[:, SHOW_COL] - rr[:, SHOW_COL]),
                                   initial=0.0) + missing.sum()),
        # the first steps' own writes, as changes from the seeded vector
        "early_embed": (compare._rel(pr[:, NUM_FIXED:] - start,
                                     rr[:, NUM_FIXED:] - start)
                        if some else float("nan")),
        "early_g2sum": (compare._rel(pr[:, G2SUM_COL], rr[:, G2SUM_COL])
                        if some else float("nan")),
    }


def diagnostics(prog_state: dict, ref: dict, init_params) -> dict:
    """Further fields of the run's ``reference`` line, not judged: the
    losses of every step on both sides, and the leaf ``dparam`` reads."""
    import jax
    d = compare._leaf_gaps(
        compare.tree_sub(prog_state["params"], init_params["net"]),
        compare.tree_sub(ref["params"], init_params["net"]))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(ref["params"])[0]]
    return {"losses": [float(x) for x in prog_state["losses"]],
            "losses_reference": [float(x) for x in ref["losses"]],
            "worst_leaves": {"dparam": paths[int(np.argmax(d))]}}


# ---- the step's work, from shapes -----------------------------------------

def layer_params(config: dict) -> Dict[str, float]:
    """Parameters of one layer of each kind, and of the head, that a
    token passes through on this chip: a routed expert counts by the
    share of token-choices that fall on the experts held."""
    d = int(config["hidden_size"])
    h, p = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    g, n = int(config["n_groups"]), int(config["ssm_state_size"])
    di = h * p
    qh, kvh = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])
    hd = int(config["head_dim"])
    ff = int(config["moe_intermediate_size"])
    sff = int(config["moe_shared_expert_intermediate_size"])
    experts = int(config["router_outputs"])
    share = int(config["num_experts_per_tok"]) \
        * int(config["n_routed_experts"]) / experts
    return {
        "M": d * (2 * di + 2 * g * n + h) + di * d,
        "*": d * hd * (2 * qh + 2 * kvh),
        "E_route": d * experts,
        "E_shared": 2 * d * sff,
        "E_experts": share * 2 * d * ff,
        "head": d * int(config["vocab_size"]),
    }


def work(config: dict, traffic: dict, chips: int, param_shapes) -> dict:
    """What one step needs on one chip, from shapes alone: operations of
    the forward and backward pass (6 a parameter a token passes through,
    plus causal attention's scores and the scan's chunk products; nothing
    recomputed counts), bytes (every dense parameter and Adam's two
    moments read and written once, the rows a step touches three times),
    and the same by ``pbox.*`` scope for the scopes that have a roofline
    of their own. Counted once for the algorithm, whatever implements
    it."""
    tokens = int(traffic["batch_per_chip"])
    t = int(traffic["seq_len"])
    pattern = str(config["hybrid_override_pattern"])
    lp = layer_params(config)
    n_m, n_a, n_e = (pattern.count(c) for c in "M*E")
    h, p = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    g, n = int(config["n_groups"]), int(config["ssm_state_size"])
    q = int(config["chunk_size"])
    qh, hd = int(config["num_attention_heads"]), int(config["head_dim"])
    kvh = int(config["num_key_value_heads"])
    d = int(config["hidden_size"])

    # a token, forward: the causal half of the score and value products
    attn_token = 2 * 2 * (t / 2) * hd * qh
    # the chunked scan: the causal half of C B^T and of its product with
    # the inputs inside a chunk, the chunk's state, the state's output
    scan_token = 2 * (q / 2) * g * n + 2 * (q / 2) * h * p + 4 * h * p * n
    per_token = 6.0 * (n_m * lp["M"] + n_a * lp["*"] + n_e * (
        lp["E_route"] + lp["E_shared"] + lp["E_experts"]) + lp["head"]) \
        + 3.0 * (n_a * attn_token + n_m * scan_token)

    n_params = float(sum(np.prod(s) for s in param_shapes)) \
        - int(config["vocab_size"]) * d     # the vectors live in the table
    rows = float(np.sum(-np.expm1(tokens * np.log1p(-traffic_mod.rank_pmf(
        int(config["vocab_size"]) - 1, traffic)))))
    f32 = 4
    scopes = {
        "pbox.ssm_scan": {
            "flops": 3.0 * scan_token * tokens * n_m,
            # x, dt, B, C read and y written, forward and as cotangents
            "bytes": 3.0 * n_m * tokens * f32 * (2 * h * p + h + 2 * g * n)},
        "pbox.attn": {
            "flops": (6.0 * lp["*"] + 3.0 * attn_token) * tokens * n_a,
            "bytes": n_a * (3.0 * lp["*"] * f32
                            + 3.0 * tokens * f32 * (2 * d + hd * (qh + 2 * kvh)))},
        "pbox.moe_experts": {
            "flops": 6.0 * lp["E_experts"] * tokens * n_e,
            # every held expert's weights read forward and backward and
            # their gradient written; a token-choice's row in and out
            "bytes": n_e * (3.0 * int(config["n_routed_experts"]) * 2 * d
                            * int(config["moe_intermediate_size"]) * f32
                            + 3.0 * lp["E_experts"] / (2 * d * int(
                                config["moe_intermediate_size"]))
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


def control_precision(config: dict) -> str:
    """The control's precision: the one below the configuration's."""
    return LOWER[config["matmul_dtype"]]
