#!/usr/bin/env python3
"""The readings that the limits in ``limits/<cell>.json`` are set from, on
the chip at the cell's own size, several seeds to a process (set-up is
long, a first pass is short):

    python3 benchmarks/study.py --workload <name> --seeds 11,12,13 \
        [--program-seeds 21,22,23] [--faults half_batch]

``--seeds``: the plain reference over the cell's first pass, then in the
program's place (a) the control, the reference in the precision below the
one the configuration states, and (b) the reference with a fault planted;
the configuration's family file (``families/``) names that precision and
the faults. ``--program-seeds``: the timed entry's
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


def _setting(loaded: dict):
    from benchmarks import harness
    config = loaded["config"]
    ref_model = importlib.import_module(
        "benchmarks.reference.models." + config["reference"])
    return (config, loaded["traffic"], int(loaded["cell"]["chips"]),
            ref_model, harness.family_of(config))


def stand_in_readings(loaded: dict, seeds, faults=None):
    """(seed, run, numbers) of the control and of each planted fault (the
    family's, where none are named)."""
    import jax
    config, traffic, chips, ref_model, family = _setting(loaded)
    control = family.control_precision(config)
    runs = [("control:" + control, dict(precision=control))]
    runs += [("fault:" + f, dict(fault=f))
             for f in (family.FAULTS if faults is None else faults)]
    for seed in seeds:
        pool = family.make_pool(config, traffic, seed, count=1)
        params = jax.device_get(family.seeded_params(ref_model, config,
                                                     seed))
        what = family.sample(pool, traffic, seed)
        ref = family.reference_pass(loaded, ref_model, pool, params, chips,
                                    what)
        for name, kw in runs:
            other = family.reference_pass(loaded, ref_model, pool, params,
                                          chips, what, **kw)
            yield seed, name, family.numbers(other, ref, params, loaded,
                                             pool, chips, what)


def program_readings(loaded: dict, seeds):
    """(seed, "program", numbers) of the timed entry's own first pass."""
    import jax
    config, traffic, chips, ref_model, family = _setting(loaded)
    entry_mod = importlib.import_module(
        "benchmarks.entries." + traffic["entry"])
    for seed in seeds:
        pool = family.make_pool(config, traffic, seed, count=2)
        params = family.seeded_params(ref_model, config, seed)
        init = jax.device_get(params)
        entry = entry_mod.build(config, traffic, pool, params, chips)
        what, state, _ = family.first_pass(entry, pool, traffic, seed)
        entry.close()
        del entry, params
        ref = family.reference_pass(loaded, ref_model, pool, init, chips,
                                    what)
        numbers = family.numbers(state, ref, init, loaded, pool, chips, what)
        yield seed, "program", dict(numbers, **family.diagnostics(
            state, ref, init))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--faults", default=None,
                    help="comma-separated; the family's where not given")
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
                               args.faults and
                               [f for f in args.faults.split(",") if f]),
                            False)):
        for seed, run, numbers in readings:
            correct, table = compare.judge(numbers, loaded["limits"])
            failed = [k for k, (v, lim) in table.items() if not v <= lim]
            bad += correct != want
            print(json.dumps({"seed": seed, "run": run, "correct": correct,
                              "failed": failed, "numbers": numbers}),
                  flush=True)
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
