"""The one traffic generator: a traffic file's parameters + a
configuration's slots + a seed -> the passes of a run, as plain numpy
columns. Nothing here imports the program; an entry wraps the columns in
whatever container its trainer takes.

Every seed gives the same sizes (records a pass, keys an example, batch),
only other ids, dense values and labels, so the work does not change with
the seed.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(kind: str, name: str) -> dict:
    """``benchmarks/<kind>/<name>.json`` (kind: configs | traffic | limits)."""
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


@dataclasses.dataclass
class PassColumns:
    """One pass, record-major. ``keys[r]`` holds record r's keys slot by
    slot (``slot_sizes[s]`` keys for slot s), key = offset[slot] + id."""

    keys: np.ndarray       # uint64 [R, K]  K = sum(slot_sizes)
    key_slot: np.ndarray   # int32 [K]     slot of each key column
    dense: np.ndarray      # float32 [R, D]
    label: np.ndarray      # float32 [R]   click; show is 1 for every record

    @property
    def num_records(self) -> int:
        return int(self.keys.shape[0])

    @property
    def num_keys(self) -> int:
        return int(self.keys.size)


def slot_vocab(config: dict) -> np.ndarray:
    """Ids a slot can take: the configuration's vocabulary of each slot,
    capped at ``vocab_cap`` where the configuration cuts it (ids beyond
    the cap fold onto the ids below it, as DLRM's ``--max-ind-range``
    folds them)."""
    v = np.asarray(config["slot_vocab"], np.int64)
    cap = config.get("vocab_cap")
    return v if cap is None else np.minimum(v, int(cap))


def key_offsets(config: dict) -> np.ndarray:
    """First key of each slot: key = offset[slot] + id, so the keys of a
    configuration are 0 .. sum(vocab) - 1."""
    v = slot_vocab(config)
    return np.concatenate([[0], np.cumsum(v)[:-1]]).astype(np.int64)


def _cdf(x: np.ndarray, vocab: int, s: float) -> np.ndarray:
    """Share of the draws with popularity rank below x - 1 (x in
    1 .. vocab + 1): the continuous power law t^-s over [1, vocab + 1),
    cut into unit cells. s = 1 is Zipf's law proper (log-uniform)."""
    if s == 1.0:
        return np.log(x) / np.log(vocab + 1.0)
    return (x ** (1.0 - s) - 1.0) / ((vocab + 1.0) ** (1.0 - s) - 1.0)


def rank_pmf(vocab: int, traffic: dict) -> np.ndarray:
    """Probability of each popularity rank 0 .. vocab - 1 of a slot."""
    dist = traffic.get("id_distribution", "uniform")
    if dist == "uniform":
        return np.full(vocab, 1.0 / vocab)
    if dist == "zipf":
        x = np.arange(1, vocab + 2, dtype=np.float64)
        return np.diff(_cdf(x, vocab, float(traffic["zipf_s"])))
    raise ValueError(f"unknown id_distribution {dist!r}")


def _draw_ranks(u: np.ndarray, vocab: int, traffic: dict) -> np.ndarray:
    """Ranks from uniform draws ``u`` in [0, 1), by the inverse CDF."""
    dist = traffic.get("id_distribution", "uniform")
    if dist == "uniform":
        x = u * vocab
    elif dist == "zipf":
        s = float(traffic["zipf_s"])
        if s == 1.0:
            x = (vocab + 1.0) ** u - 1.0
        else:
            top = (vocab + 1.0) ** (1.0 - s) - 1.0
            x = (top * u + 1.0) ** (1.0 / (1.0 - s)) - 1.0
    else:
        raise ValueError(f"unknown id_distribution {dist!r}")
    return np.minimum(x.astype(np.int64), vocab - 1)


#: a prime above every vocabulary: rank -> id is a bijection of the slot's
#: ids that scatters the popular ranks over them (real ids are hashes, so
#: a popular feature's row is no neighbour of the next popular one's)
_SCATTER = 2654435761


def rank_to_id(rank: np.ndarray, vocab: int) -> np.ndarray:
    return (rank * _SCATTER) % vocab


def make_pass(config: dict, traffic: dict, seed: int, index: int
              ) -> PassColumns:
    sizes = np.asarray(config["slot_sizes"], np.int64)
    vocab, offset = slot_vocab(config), key_offsets(config)
    r = int(traffic["records_per_pass"])
    rng = np.random.default_rng([int(seed), int(index)])
    key_slot = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    u = rng.random((r, int(sizes.sum())))
    keys = np.empty(u.shape, np.uint64)
    for col, s in enumerate(key_slot):
        v = int(vocab[s])
        keys[:, col] = rank_to_id(_draw_ranks(u[:, col], v, traffic), v) \
            + offset[s]
    dense = rng.standard_normal((r, int(config["dense_dim"])),
                                dtype=np.float32)
    label = (rng.random(r) < float(traffic["label_rate"])
             ).astype(np.float32)
    return PassColumns(keys, key_slot, dense, label)


def make_pool(config: dict, traffic: dict, seed: int,
              count: Optional[int] = None) -> List[PassColumns]:
    """The run's pool of passes (its first ``count``), cycled in order by
    every entry."""
    n = int(traffic["pool_size"]) if count is None else count
    return [make_pass(config, traffic, seed, i) for i in range(n)]
