"""What one training step needs, from shapes alone: the operations of the
dense net and the bytes of the algorithm, never of an implementation.
Table capacity does not enter: a step needs the rows it touches.

bytes of a step on one chip =
    distinct rows this chip serves x logical row bytes x 3
        (read once for the pull; read and written once for the push)
  + dense parameters and Adam's two moments, each read and written once
  + the step's share of the wire, once (a 4 B row index a key, 1 B a
    dense feature, 3 B label/show/click an example)
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from benchmarks.traffic import rank_pmf

NUM_FIXED = 8  # scalar columns of a table row before the factors


def dense_flops_per_example(param_shapes: Sequence[Sequence[int]]) -> float:
    """Forward + backward operations of the dense net for one example:
    2 * in * out a matrix (every leaf of two or more dimensions), times 3
    (forward, and the two products of the backward pass)."""
    return 3.0 * sum(2.0 * float(np.prod(s)) for s in param_shapes
                     if len(s) >= 2)


def expected_distinct_rows(slot_sizes: Sequence[int],
                           slot_vocab: Sequence[int], global_batch: int,
                           traffic: dict) -> float:
    """Expected number of distinct keys in one global batch: a slot that
    draws n = batch * size ids from pmf p holds sum(1 - (1 - p)^n)."""
    total = 0.0
    for size, vocab in zip(slot_sizes, slot_vocab):
        p = rank_pmf(int(vocab), traffic)
        n = global_batch * int(size)
        total += float(np.sum(-np.expm1(n * np.log1p(-p))))
    return total


def step_work(slot_sizes: Sequence[int], slot_vocab: Sequence[int],
              mf_dim: int, dense_dim: int, batch_per_chip: int, chips: int,
              traffic: dict, param_shapes: Sequence[Sequence[int]]) -> dict:
    """FLOPs and bytes one chip needs for one step."""
    rows = expected_distinct_rows(slot_sizes, slot_vocab,
                                  batch_per_chip * chips, traffic) / chips
    row_bytes = (NUM_FIXED + mf_dim) * 4
    table_bytes = rows * row_bytes * 3
    n_params = float(sum(np.prod(s) for s in param_shapes))
    dense_bytes = n_params * 4 * 3 * 2
    wire_bytes = batch_per_chip * (sum(slot_sizes) * 4 + dense_dim + 3)
    return {
        "flops": dense_flops_per_example(param_shapes) * batch_per_chip,
        "bytes": table_bytes + dense_bytes + wire_bytes,
        "rows": rows, "table_bytes": table_bytes,
        "dense_bytes": dense_bytes, "wire_bytes": float(wire_bytes),
    }


def least_step_seconds(work: dict, peaks: dict) -> dict:
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops > t_bytes else "bytes"}
