"""One run of one cell: set-up by parts, the window of whole passes, the
traced passes, the comparison with the plain reference, the result line.

Nothing here knows a cell by name, or a model family by what it compares.
The cell's configuration file, traffic file, entry kind, family, reference
net, limits and per-layer readers are found by the names ``BENCHMARK.json``
and the configuration give.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from typing import Dict, List

from benchmarks import compare, tracered, traffic as traffic_mod
from benchmarks.peaks import device_peaks

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Programs that needed an executable (one backend_compile event a
    jit-cache miss, whether XLA compiled it or the persistent cache
    served it) — chip_smoke.CompileWatch's arithmetic."""

    def __init__(self) -> None:
        import jax.monitoring
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event: str, secs: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.programs += 1


def note(kind: str, **fields) -> None:
    """An earlier line of the run's standard output."""
    print(json.dumps({"line": kind, **fields}), flush=True)


def load_cell(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = traffic_mod.load_json("traffic", cell["traffic"])
    limits = traffic_mod.load_json("limits", workload)
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic, "limits": limits}


def require_tpu(chips: int):
    """The devices of this run, or no run: a TPU with the chips the cell
    asks for, and the program's native library."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"the benchmark measures a TPU; jax found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) != chips:
        raise SystemExit(f"the cell asks for {chips} chip(s); jax found "
                         f"{len(devs)}")
    from paddlebox_tpu.native import require_native
    require_native()
    return devs


def family_of(config: dict):
    """The configuration's family file (``families/<family>.py``); a
    configuration that names none is of family ``ctr``."""
    return importlib.import_module(
        "benchmarks.families." + config.get("family", "ctr"))


def run_window(entry, seconds: float, watch: CompileWatch) -> dict:
    """Whole passes from a pass boundary until the first pass that ends
    at or after ``seconds``; every record of those passes counts."""
    entry.block()
    c0, p0 = entry.counters(), watch.programs
    t_open = time.perf_counter()
    waits, trains, infos = [], [], []
    while True:
        t0 = time.perf_counter()
        rp = entry.wait()
        t1 = time.perf_counter()
        entry.train(rp)
        t2 = time.perf_counter()
        waits.append(t1 - t0)
        trains.append(t2 - t1)
        infos.append(entry.pass_info(rp))
        if t2 - t_open >= seconds:
            break
    entry.block()
    t_close = time.perf_counter()
    c1 = entry.counters()
    return {
        "seconds": t_close - t_open,
        "passes": len(waits),
        "records": sum(i["records"] for i in infos),
        "batches": sum(i["batches"] for i in infos),
        "wire_bytes": sum(i["wire_bytes"] for i in infos),
        "wait_s": waits, "train_s": trains,
        "builds": c1["builds"] - c0["builds"],
        "build_s": c1["build_s"] - c0["build_s"],
        "build_stage_s": {k: v - c0["stage_s"].get(k, 0.0)
                          for k, v in c1["stage_s"].items()},
        "compiles": watch.programs - p0,
    }


def run_traced(entry, n_passes: int) -> dict:
    """``n_passes`` more whole passes under the profiler, the harness's
    own calls inside host spans; returns the reduced trace."""
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    entry.block()
    batches = 0
    jax.profiler.start_trace(TRACE_DIR)
    try:
        with jax.profiler.TraceAnnotation(tracered.SPAN_WINDOW):
            for _ in range(n_passes):
                with jax.profiler.TraceAnnotation("bench.wait"):
                    rp = entry.wait()
                with jax.profiler.TraceAnnotation("bench.train"):
                    entry.train(rp)
                batches += entry.pass_info(rp)["batches"]
            entry.block()
    finally:
        jax.profiler.stop_trace()
    try:
        red = tracered.reduce(tracered.load(TRACE_DIR))
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    red["batches"] = batches
    red["passes"] = n_passes
    note("trace", window_s=red["window_s"], busy_s_each=red["busy_s_each"],
         scopes=red["scopes"], collective_s=red["collective_s"],
         collective_events=red["collective_events"], gaps=red["gaps"])
    return red


def read_layer_metrics(names: List[str], ctx: dict) -> Dict[str, float]:
    out = {}
    for name in names:
        path = os.path.join(HERE, "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmarks.layer_metrics." + name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        val = mod.read(ctx)
        if val is not None:
            out[name] = float(val)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """Drive one run and return the result line's object."""
    loaded = load_cell(workload)
    cell, config, traffic = (loaded["cell"], loaded["config"],
                             loaded["traffic"])
    chips = int(cell["chips"])
    parts = {}
    t = time.perf_counter()

    def lap(name: str) -> None:
        nonlocal t
        now = time.perf_counter()
        parts[name] = now - t
        t = now

    import jax
    devs = require_tpu(chips)
    from paddlebox_tpu.utils.compile_cache import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    watch = CompileWatch()
    entry_mod = importlib.import_module(
        "benchmarks.entries." + traffic["entry"])
    ref_model = importlib.import_module(
        "benchmarks.reference.models." + config["reference"])
    family = family_of(config)
    parts["python_start_s"] = t - t_start
    lap("imports_native_s")
    note("host", cpu_count=os.cpu_count(),
         threads=threading.active_count(), compile_cache=cache_dir,
         device_kind=devs[0].device_kind, chips=len(devs))

    pool = family.make_pool(config, traffic, seed)
    lap("data_pool_s")
    params = family.seeded_params(ref_model, config, seed)
    init_params = jax.device_get(params)
    lap("weights_s")
    entry = entry_mod.build(config, traffic, pool, params, chips)
    lap("table_trainer_s")
    for name, secs in entry.setup_parts.items():   # parts of the above
        parts[name] = secs
        parts["table_trainer_s"] -= secs

    # what the first pass trained is read here and compared once the
    # window has closed
    what, prog_state, secs = family.first_pass(entry, pool, traffic, seed)
    parts.update(secs)
    t = time.perf_counter()
    for _ in range(int(traffic["warm_passes"]) - 1):
        entry.train(entry.wait())
    entry.block()
    lap("warm_passes_s")
    programs_setup = watch.programs

    # Python's collector must not run inside the window
    gc.collect()
    gc.freeze()
    gc.disable()
    setup_s = time.perf_counter() - t_start
    note("setup", setup_s=setup_s, programs=programs_setup, **parts)
    try:
        win = run_window(entry, seconds, watch)
        note("passes", wait_s=win["wait_s"], train_s=win["train_s"],
             builds=win["builds"], build_s=win["build_s"],
             build_stage_s=win["build_stage_s"])
        red = (run_traced(entry, int(traffic["traced_passes"]))
               if trace else None)
    finally:
        gc.enable()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    param_shapes = [tuple(x.shape) for x in jax.tree.leaves(init_params)]
    entry.close()
    del entry
    gc.unfreeze()
    gc.collect()

    t_ref = time.perf_counter()
    ref = family.reference_pass(loaded, ref_model, pool, init_params, chips,
                                what)
    numbers = family.numbers(prog_state, ref, init_params, loaded, pool,
                             chips, what)
    correct, compared = compare.judge(numbers, loaded["limits"])
    note("reference", seconds=time.perf_counter() - t_ref,
         numbers=numbers,
         **family.diagnostics(prog_state, ref, init_params))

    rate = win["records"] / win["seconds"] / chips
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": win["passes"], "failed": 0}
    if not trace:
        result["metrics"] = {
            "train_examples_per_s_per_chip": {"value": rate,
                                              "unit": "examples/s/chip"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        peaks = device_peaks(devs[0].device_kind)
        work = family.work(config, traffic, chips, param_shapes)
        ctx = {"window": win, "trace": red, "chips": chips, "rate": rate,
               "peaks": peaks, "work": work,
               "keys_per_example": work["keys_per_example"],
               "flops_per_example": work["flops_per_example"]}
        units = {m["name"]: m["unit"] for m in loaded["bench"]["per_layer"]
                 if workload in m.get("workloads", [workload])}
        vals = read_layer_metrics(list(units), ctx)
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in vals.items()}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {
            "device_ops": tracered.scoped_ops(red),
            "idle_gaps": sorted(([k, v] for k, v in red["gaps"].items()),
                                key=lambda kv: -kv[1])}
    result["device"] = device
    result["compared"] = compared
    return result


def emit(result: dict) -> None:
    """The compared numbers as the last lines of standard error, the
    result as the last line of standard output."""
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value:.6g} (limit {limit:.6g})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
