"""Family ``ctr``: click models of the shape ``(pooled [B,S,D], dense) ->
logit [B]`` over one click label and a table of feature rows. What one
record is: one example (a click or none).

A family is everything between the harness and one kind of model: the
run's pool, the seeded weights, what the first pass is compared on, the
plain reference's pass, the numbers compared, the step's work from
shapes, and the control and faults ``study.py`` plants. The harness finds
it by the configuration's ``"family"`` key (``ctr`` where there is none)
and knows nothing of what is inside ``what``, ``prog_state`` or ``ref``.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import compare, roofline, traffic as traffic_mod

#: the precision below the one a configuration states for its net
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
         "float16": "float8_e4m3fn"}
#: planted in the reference by ``study.py``; a state left unchanged reads
#: 1 and needs no run, but costs none either
FAULTS = ("half_batch", "state_unchanged")


def make_pool(config: dict, traffic: dict, seed: int,
              count: Optional[int] = None) -> List:
    """The run's pool of passes (its first ``count``): ``traffic.py``'s
    columns, cycled in order by every entry."""
    return traffic_mod.make_pool(config, traffic, seed, count)


def seeded_params(ref_model, config: dict, seed: int):
    """The dense weights, made on the device in one jitted call."""
    import jax
    n_slots = len(config["slot_sizes"])
    mf, dd = int(config["mf_dim"]), int(config["dense_dim"])
    args = config["model"]["args"]
    init = jax.jit(lambda key: ref_model.init(key, n_slots, mf, dd, args))
    return init(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def sample(pool, traffic: dict, seed: int) -> np.ndarray:
    """What the first pass is compared on: a seeded sample of its
    distinct keys, ``check_rows`` of them (all, if fewer)."""
    uniq, count = np.unique(pool[0].keys), int(traffic["check_rows"])
    if len(uniq) <= count:
        return uniq
    rng = np.random.default_rng([int(seed), 7])
    return np.sort(rng.choice(uniq, size=count, replace=False))


def first_pass(entry, pool, traffic: dict, seed: int):
    """The first pass through the window's own call and feed, and what it
    trained: -> (compared keys, the program's state at them, seconds).
    The loss is read from the program's click-AUC buckets."""
    t0 = time.perf_counter()
    entry.train(entry.wait())
    entry.block()
    t1 = time.perf_counter()
    keys = sample(pool, traffic, seed)
    state = entry.read_state(keys)
    state["loss"] = compare.logloss_from_buckets(
        state.pop("auc_pos"), state.pop("auc_neg"))
    return keys, state, {"first_pass_s": t1 - t0,
                         "read_state_s": time.perf_counter() - t1}


def reference_pass(loaded: dict, ref_model, pool, params, chips: int,
                   keys: np.ndarray, precision: Optional[str] = None,
                   fault: Optional[str] = None) -> dict:
    """The plain reference over the run's first pass, its rows cut to
    ``keys``. ``precision``: the net's, as the configuration states it
    where none is given (the control gives ``control_precision``)."""
    import jax
    from benchmarks.reference import ctr
    config, traffic = loaded["config"], loaded["traffic"]
    if precision is None:
        precision = config["tower_dtype"]  # as the configuration states
    ref = ctr.run_pass(
        ref_model.forward, config, pool[0],
        int(traffic["batch_per_chip"]) * chips, params,
        tower_dtype=precision, fault=fault)
    at = np.searchsorted(ref["keys"], np.asarray(keys, np.uint64))
    if not np.array_equal(ref.pop("keys")[at], keys):
        raise ValueError("a compared key is not of the reference's pass")
    ref["rows"] = np.asarray(jax.device_get(ref.pop("table")[at]))
    ref["params"] = jax.device_get(ref["params"])
    ref["mu"] = jax.device_get(ref["mu"])
    return ref


def numbers(prog_state: dict, ref: dict, init_params, loaded: dict, pool,
            chips: int, keys: np.ndarray) -> Dict[str, float]:
    """The numbers ``compare.judge`` holds against the cell's limits;
    ``prog_state`` is the program's first pass, or a second reference run
    in its place (the control, a planted fault)."""
    config, traffic = loaded["config"], loaded["traffic"]
    early = compare.early_rows(
        pool[0], int(traffic["batch_per_chip"]) * chips, keys)
    return compare.compare(prog_state, ref, init_params,
                           int(config["mf_dim"]), early)


def diagnostics(prog_state: dict, ref: dict, init_params) -> dict:
    """Further fields of the run's ``reference`` line, not judged: the
    leaf that ``dparam`` and ``grad_ema`` each read."""
    return {"worst_leaves": compare.worst_leaves(prog_state, ref,
                                                 init_params)}


def work(config: dict, traffic: dict, chips: int, param_shapes) -> dict:
    """What one step needs on one chip, from shapes alone: ``flops`` and
    ``bytes`` (``roofline.step_work``), the dense net's ``flops_per_
    example``, ``keys_per_example``, and ``scopes``: operations and bytes
    by ``pbox.*`` scope, empty here (the step has one roofline, the whole
    step's)."""
    out = roofline.step_work(
        config["slot_sizes"], traffic_mod.slot_vocab(config),
        int(config["mf_dim"]), int(config["dense_dim"]),
        int(traffic["batch_per_chip"]), chips, traffic, param_shapes)
    out["flops_per_example"] = roofline.dense_flops_per_example(param_shapes)
    out["keys_per_example"] = int(sum(config["slot_sizes"]))
    out["scopes"] = {}
    return out


def control_precision(config: dict) -> str:
    """The control's precision: the one below the configuration's."""
    return LOWER[config["tower_dtype"]]
