"""The comparison that decides ``correct``: what the timed entry trained
in its first pass against the plain reference's pass over the same
records. Every number is a relative gap; ``judge`` holds each against the
cell's limit (``limits/<cell>.json``)."""

from __future__ import annotations

from typing import Dict

import numpy as np

NUM_FIXED = 8


def logloss_from_buckets(pos: np.ndarray, neg: np.ndarray) -> float:
    """Mean cross-entropy of the predictions the program booked into its
    AUC tables (bucket i holds predictions in [i, i+1) / n): exact to the
    bucket's width, 1e-6 of a probability."""
    n = pos.shape[0]
    p = (np.arange(n, dtype=np.float64) + 0.5) / n
    total = pos.sum() + neg.sum()
    if total <= 0:
        return float("nan")   # nothing was booked: the pass did not run
    return float(-(pos * np.log(p) + neg * np.log1p(-p)).sum() / total)


def _leaf_norms(tree) -> np.ndarray:
    import jax
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in jax.tree.leaves(tree)])


def _leaf_gaps(prog_tree, ref_tree) -> np.ndarray:
    a, b = _leaf_norms(prog_tree), _leaf_norms(ref_tree)
    return np.abs(a - b) / np.maximum(b, np.median(b))


def worst_leaf_gap(prog_tree, ref_tree) -> float:
    """Largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    return float(np.max(_leaf_gaps(prog_tree, ref_tree)))


def worst_leaves(prog: Dict, ref: Dict, init_params) -> Dict[str, str]:
    """The leaf that ``dparam`` and ``grad_ema`` each read, by its path:
    goes on the run's ``reference`` line, so that a seed that reads far
    off names its leaf."""
    import jax
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(ref["params"])[0]]
    d = _leaf_gaps(tree_sub(prog["params"], init_params),
                   tree_sub(ref["params"], init_params))
    g = _leaf_gaps(prog["mu"], ref["mu"])
    return {"dparam": paths[int(np.argmax(d))],
            "grad_ema": paths[int(np.argmax(g))]}


def tree_sub(a, b):
    import jax
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


#: a row is early where every touch of it lies in the pass's first steps
EARLY_STEPS = 3


def early_rows(cols, batch: int, keys: np.ndarray) -> np.ndarray:
    """Mask over ``keys``: the keys that the pass ``cols`` (global batches
    of ``batch`` records) touches in its first ``EARLY_STEPS`` steps and
    never after. Such a row still holds what those steps wrote, so it
    reads the first steps without a per-step state: a pass's later steps
    swing with rounding (PERF.md), its first do not."""
    uniq, inv = np.unique(cols.keys, return_inverse=True)
    step = np.repeat(np.arange(cols.num_records) // batch,
                     cols.keys.shape[1])
    last = np.zeros(len(uniq), np.int64)
    np.maximum.at(last, inv.reshape(-1), step)
    return last[np.searchsorted(uniq, keys)] < EARLY_STEPS


def _rel(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def compare(prog: Dict, ref: Dict, init_params, mf_dim: int,
            early: np.ndarray) -> Dict[str, float]:
    """``prog``: an entry's ``read_state`` after its first pass (rows of
    the sampled keys, ``loss`` from its AUC buckets); ``ref``:
    ``reference.ctr.run_pass``'s result with ``rows`` cut to the same
    keys; ``early``: ``early_rows`` of those keys. The control and the
    planted faults put a second reference run in ``prog``'s place."""
    out = {}
    out["loss"] = abs(prog["loss"] - ref["loss"]) / ref["loss"]
    out["dparam"] = worst_leaf_gap(tree_sub(prog["params"], init_params),
                                   tree_sub(ref["params"], init_params))
    out["grad_ema"] = worst_leaf_gap(prog["mu"], ref["mu"])
    pr, rr = prog["rows"].astype(np.float64), ref["rows"].astype(np.float64)
    missing = np.isnan(pr).any(axis=1)
    pr = np.where(missing[:, None], 0.0, pr)
    # show and click counts are sums of small integers: exact
    out["rows_count"] = float(np.max(np.abs(pr[:, 0:2] - rr[:, 0:2]))
                              + missing.sum())
    emb = [4] + list(range(NUM_FIXED, NUM_FIXED + mf_dim))
    out["rows_embed"] = _rel(pr[:, emb], rr[:, emb])
    g2 = [5, 6]
    out["rows_g2sum"] = _rel(pr[:, g2], rr[:, g2])
    # the first steps' own writes: weight and Adagrad sum of the early
    # rows (none of them in the sample: nothing was compared, NaN)
    some = early.any()
    out["early_embed"] = (_rel(pr[early][:, emb], rr[early][:, emb])
                          if some else float("nan"))
    out["early_g2sum"] = (_rel(pr[early][:, g2], rr[early][:, g2])
                          if some else float("nan"))
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """-> (correct, {name: [value, limit]}); a number that is not finite,
    or a limit that is missing, is not correct."""
    table, ok = {}, True
    for name, lim in limits.items():
        v = numbers.get(name, float("nan"))
        table[name] = [v, lim]
        ok = ok and bool(np.isfinite(v)) and v <= lim
    return ok, table
