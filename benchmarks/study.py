#!/usr/bin/env python3
"""The readings that the limits in ``limits/<cell>.json`` are set from, on
the chip at the cell's own size, several seeds to a process (set-up is
long, a first pass is short):

    python3 benchmarks/study.py --workload <name> --seeds 11,12,13 \
        [--program-seeds 21,22,23] [--faults half_batch]

``--seeds``: the plain reference over the cell's first pass, then in the
program's place (a) the control, the reference with the net's matmul
operands in the precision below the one the configuration states, and (b)
the reference with a fault planted. ``--program-seeds``: the timed entry's
own first pass against the reference (the lower readings; ``run.py``'s
runs give more). Every reading is judged against the cell's limits as a
run's is, and the process exits 1 where a control or a fault comes out
correct, or a sound run does not. Needs the chip, like ``run.py``.
"""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the precision below the one a configuration states for its net
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
         "float16": "float8_e4m3fn"}
#: planted in the reference; a state left unchanged reads 1 and needs no
#: run, but costs none either
FAULTS = ("half_batch", "state_unchanged")


def _setting(loaded: dict):
    config = loaded["config"]
    ref_model = importlib.import_module(
        "benchmarks.reference.models." + config["reference"])
    return config, loaded["traffic"], int(loaded["cell"]["chips"]), ref_model


def stand_in_readings(loaded: dict, seeds, faults=FAULTS):
    """(seed, run, numbers) of the control and of each planted fault."""
    import jax
    from benchmarks import compare, harness, traffic as traffic_mod
    config, traffic, chips, ref_model = _setting(loaded)
    control = LOWER[config["tower_dtype"]]
    runs = [("control:" + control, dict(tower_dtype=control))]
    runs += [("fault:" + f, dict(fault=f)) for f in faults]
    for seed in seeds:
        pool = traffic_mod.make_pool(config, traffic, seed, count=1)
        params = jax.device_get(harness.seeded_params(ref_model, config,
                                                      seed))
        keys = harness.sample_keys(pool[0], int(traffic["check_rows"]), seed)
        early = compare.early_rows(
            pool[0], int(traffic["batch_per_chip"]) * chips, keys)
        ref = harness.reference_pass(loaded, ref_model, pool, params, chips,
                                     keys)
        for name, kw in runs:
            other = harness.reference_pass(loaded, ref_model, pool, params,
                                           chips, keys, **kw)
            yield seed, name, compare.compare(other, ref, params,
                                              int(config["mf_dim"]), early)


def program_readings(loaded: dict, seeds):
    """(seed, "program", numbers) of the timed entry's own first pass."""
    import jax
    from benchmarks import compare, harness, traffic as traffic_mod
    config, traffic, chips, ref_model = _setting(loaded)
    entry_mod = importlib.import_module(
        "benchmarks.entries." + traffic["entry"])
    for seed in seeds:
        pool = traffic_mod.make_pool(config, traffic, seed, count=2)
        params = harness.seeded_params(ref_model, config, seed)
        init = jax.device_get(params)
        entry = entry_mod.build(config, traffic, pool, params, chips)
        keys, state, _ = harness.first_pass(entry, pool, traffic, seed)
        entry.close()
        del entry, params
        ref = harness.reference_pass(loaded, ref_model, pool, init, chips,
                                     keys)
        early = compare.early_rows(
            pool[0], int(traffic["batch_per_chip"]) * chips, keys)
        numbers = compare.compare(state, ref, init, int(config["mf_dim"]),
                                  early)
        numbers["early_rows"] = int(early.sum())
        numbers["worst_leaves"] = compare.worst_leaves(state, ref, init)
        yield seed, "program", numbers


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--faults", default=",".join(FAULTS))
    args = ap.parse_args()
    from benchmarks import compare, harness
    loaded = harness.load_cell(args.workload)
    harness.require_tpu(int(loaded["cell"]["chips"]))
    from paddlebox_tpu.utils.compile_cache import enable_compilation_cache
    enable_compilation_cache()

    def ints(text):
        return [int(s) for s in text.split(",") if s]

    bad = 0
    for readings, want in ((program_readings(loaded,
                                             ints(args.program_seeds)), True),
                           (stand_in_readings(
                               loaded, ints(args.seeds),
                               [f for f in args.faults.split(",") if f]),
                            False)):
        for seed, run, numbers in readings:
            leaves = numbers.pop("worst_leaves", None)
            numbers.pop("early_rows", None)
            correct, table = compare.judge(numbers, loaded["limits"])
            failed = [k for k, (v, lim) in table.items() if not v <= lim]
            bad += correct != want
            line = {"seed": seed, "run": run, "correct": correct,
                    "failed": failed, "numbers": numbers}
            if leaves:
                line["worst_leaves"] = leaves
            print(json.dumps(line), flush=True)
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
