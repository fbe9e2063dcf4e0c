"""The benchmark's own tests, on the CPU: the yardstick's arithmetic
against hand counts and a recorded trace, the generator, the contract's
character rules, and whole runs of the harness at toy size: sound ones
that must come out correct, and the control and the planted faults that
must not.

    python -m pytest benchmarks/tests -q
"""

import copy
import glob
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks import compare, harness, roofline, tracered
from benchmarks import traffic as traffic_mod

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = ["deepfm-criteo-kaggle.train-resident"]


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---- trace reduction -------------------------------------------------------

def test_trace_reduction_by_hand():
    """Nested events, two devices, host spans: busy union, self times,
    collective time and gap attribution against a hand count (ns)."""
    dev0 = [["while.1", 100, 800],            # spans its body, 100..900
            ["fusion.a", 100, 300], ["all-to-all.7", 400, 200],
            ["fusion.a", 700, 200], ["fusion.b", 1100, 100]]
    dev1 = [["fusion.a", 0, 500], ["all-reduce.2", 500, 500]]
    # an asynchronous all-to-all: short start and done on the op line,
    # the start..done span (overlapping fusion.a) on the async line
    dev1 += [["all-to-all-start.3", 0, 10], ["all-to-all-done.3", 290, 10]]
    async1 = [["all-to-all-start.3", 0, 300]]
    trace = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": dev0},
                   {"name": "XLA Modules", "events": [["m", 0, 5000]]}]},
        {"name": "/device:TPU:1",
         "lines": [{"name": "XLA Ops", "events": dev1},
                   {"name": "Async XLA Ops", "events": async1}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.traced", 0, 2000], ["bench.wait", 0, 100],
            ["bench.train", 100, 1100], ["bench.wait", 1200, 300],
            ["unrelated", 0, 9999]]}]}]}
    red = tracered.reduce(trace)
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(2000e-9)
    # dev0 busy: [100,900] + [1100,1200] = 900; dev1: [0,1000] = 1000
    assert red["busy_s_each"] == pytest.approx([900e-9, 1000e-9])
    assert red["busy_s"] == pytest.approx(950e-9)
    ops = dict(red["ops"])
    # self times, mean over two devices
    assert ops["while.1"] == pytest.approx((800 - 700) / 2 * 1e-9)
    assert ops["fusion.a"] == pytest.approx((500 + 500 - 20) / 2 * 1e-9)
    assert ops["all-to-all.7"] == pytest.approx(200 / 2 * 1e-9)
    # dev0: [400,600]; dev1: [0,300] u [500,1000], intervals not summed twice
    assert red["collective_s"] == pytest.approx((200 + 300 + 500) / 2 * 1e-9)
    # dev0 idle: [0,100] wait, [900,1100] train, [1200,1500] wait,
    # [1500,2000] other; dev1 idle: [1000,1200] train, [1200,1500] wait,
    # [1500,2000] other
    assert red["gaps"]["wait"] == pytest.approx((400 + 300) / 2 * 1e-9)
    assert red["gaps"]["train"] == pytest.approx((200 + 200) / 2 * 1e-9)
    assert red["gaps"]["other"] == pytest.approx((500 + 500) / 2 * 1e-9)


def test_trace_reduction_recorded():
    """A small trace recorded on the chip (TPU v5 lite, PR 25; cut to the
    events of two steps by ``tracered.load``'s form): the reduction
    gives the numbers worked out from it by hand when it was cut."""
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        rec = json.load(f)
    red = tracered.reduce(rec["trace"])
    exp = rec["expected"]
    assert red["devices"] == exp["devices"]
    assert red["window_s"] == pytest.approx(exp["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    assert red["busy_s"] < red["window_s"]
    got = dict(red["ops"])
    for name, sec in exp["ops"].items():
        assert got[name] == pytest.approx(sec, rel=1e-9)
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["gaps"].values()) == pytest.approx(idle, rel=1e-6)
    for state, sec in exp["gaps"].items():
        assert red["gaps"][state] == pytest.approx(sec, rel=1e-6, abs=1e-12)


# ---- roofline --------------------------------------------------------------

def deepfm_shapes():
    width = 26 * 13 + 13
    return [(13, 1), (1,), (width, 400), (400,), (400, 400), (400,),
            (400, 400), (400,), (400, 1), (1,)]


def test_roofline_hand_count_cell1():
    loaded = harness.load_cell(CELLS[0])
    config, traffic = loaded["config"], loaded["traffic"]
    vocab = traffic_mod.slot_vocab(config)
    assert len(vocab) == 26 and vocab.max() == 8_000_000
    assert vocab.sum() == 31_279_757 <= config["table_rows_per_chip"]
    shapes = deepfm_shapes()
    mats = 13 + 351 * 400 + 400 * 400 + 400 * 400 + 400
    assert roofline.dense_flops_per_example(shapes) == 3 * 2 * mats
    work = roofline.step_work(config["slot_sizes"], vocab, 10, 13, 8192, 1,
                              traffic, shapes)
    # Zipf's law with exponent 1: rank r of a slot of v ids is drawn with
    # p = ln((r + 2) / (r + 1)) / ln(v + 1); a step draws 8,192 a slot
    want = 0.0
    for v in vocab.tolist():
        p = np.log((np.arange(v) + 2.0) / (np.arange(v) + 1.0)) / np.log(v + 1.0)
        want += float(np.sum(1.0 - (1.0 - p) ** 8192))
    assert work["rows"] == pytest.approx(want, rel=1e-6)
    # the slot of 3 ids gives 3 rows, the one of 8M under 8,192 by far
    assert 40_000 < work["rows"] < 70_000
    assert work["table_bytes"] == pytest.approx(work["rows"] * 72 * 3)
    n_params = mats + 1 + 400 + 400 + 400 + 1
    assert work["dense_bytes"] == n_params * 4 * 3 * 2
    assert work["wire_bytes"] == 8192 * (26 * 4 + 13 + 3)
    assert work["flops"] == 3 * 2 * mats * 8192
    least = roofline.least_step_seconds(
        work, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    # 22.7 GFLOP at 197 TFLOP/s (115 us) outlast 23 MB at 819 GB/s (29 us)
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(work["flops"] / 197e12)
    assert work["bytes"] / 819e9 == pytest.approx(29e-6, rel=0.1)
    assert least["seconds"] < 30e-3 / 50   # under a fiftieth of PR 22's step


def test_roofline_ignores_table_capacity():
    loaded = harness.load_cell(CELLS[0])
    config, traffic = loaded["config"], loaded["traffic"]
    vocab = traffic_mod.slot_vocab(config)
    a = roofline.step_work(config["slot_sizes"], vocab, 10, 13, 8192, 1,
                           traffic, deepfm_shapes())
    bigger = dict(config, table_rows_per_chip=config["table_rows_per_chip"] * 4)
    b = roofline.step_work(bigger["slot_sizes"],
                           traffic_mod.slot_vocab(bigger), 10, 13, 8192, 1,
                           traffic, deepfm_shapes())
    assert a == b
    import inspect
    assert "capacity" not in inspect.signature(roofline.step_work).parameters


# ---- traffic ---------------------------------------------------------------

MULTIHOT = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100,
            27, 10, 3, 1, 1]


@pytest.mark.parametrize("sizes", [[1] * 26, MULTIHOT], ids=["one", "multi"])
def test_traffic_same_seed_same_records(sizes):
    loaded = harness.load_cell(CELLS[0])
    config = dict(loaded["config"], slot_sizes=sizes)
    traffic = dict(loaded["traffic"], records_per_pass=512)
    big = 2 ** 31 + 12345
    a = traffic_mod.make_pass(config, traffic, big, 1)
    b = traffic_mod.make_pass(config, traffic, big, 1)
    c = traffic_mod.make_pass(config, traffic, big + 1, 1)
    d = traffic_mod.make_pass(config, traffic, big, 2)
    for x, y in ((a.keys, b.keys), (a.dense, b.dense), (a.label, b.label)):
        assert np.array_equal(x, y)
    assert not np.array_equal(a.keys, c.keys)
    assert not np.array_equal(a.keys, d.keys)
    assert a.keys.shape == c.keys.shape == (512, sum(sizes))
    # every key lies in its own slot's range of the key space
    off = traffic_mod.key_offsets(config)
    vocab = traffic_mod.slot_vocab(config)
    lo, hi = off[a.key_slot], (off + vocab)[a.key_slot]
    assert (a.keys >= lo.astype(np.uint64)).all()
    assert (a.keys < hi.astype(np.uint64)).all()


def test_multihot_has_214_keys_an_example():
    config = dict(harness.load_cell(CELLS[0])["config"], slot_sizes=MULTIHOT)
    traffic = dict(harness.load_cell(CELLS[0])["traffic"],
                   records_per_pass=64)
    assert traffic_mod.make_pass(config, traffic, 1, 0).keys.shape[1] == 214


@pytest.mark.parametrize("s", [1.0, 1.2])
def test_zipf_ids_are_skewed_bounded_and_follow_their_pmf(s):
    config = dict(harness.load_cell(CELLS[0])["config"],
                  slot_sizes=[1, 1], slot_vocab=[1000, 7], vocab_cap=None)
    traffic = dict(harness.load_cell(CELLS[0])["traffic"],
                   records_per_pass=200_000, zipf_s=s)
    keys = traffic_mod.make_pass(config, traffic, 3, 0).keys
    assert keys[:, 0].max() < 1000 and 1000 <= keys[:, 1].min()
    assert keys[:, 1].max() < 1007
    pmf = traffic_mod.rank_pmf(1000, traffic)
    assert pmf.sum() == pytest.approx(1.0) and (np.diff(pmf) < 0).all()
    # the most popular rank sits at its scattered id, with its share
    top = int(traffic_mod.rank_to_id(np.array([0, 1]), 1000)[1])
    share = (keys[:, 0] == top).mean()
    assert share == pytest.approx(pmf[1], rel=0.05)
    # rank -> id is a bijection of the slot's ids
    ids = traffic_mod.rank_to_id(np.arange(1000), 1000)
    assert np.array_equal(np.sort(ids), np.arange(1000))


def test_vocabulary_cap_folds_ids():
    config = harness.load_cell(CELLS[0])["config"]
    raw = np.asarray(config["slot_vocab"])
    assert raw.sum() == 33_762_577 and (raw > 8_000_000).sum() == 2
    assert traffic_mod.slot_vocab(dict(config, vocab_cap=None)).sum() \
        == raw.sum()


# ---- the contract's rules --------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_names_and_units():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = []
    for c in b["configs"]:
        names.append(c["name"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in b["workloads"]:
        names += [w["name"], w["traffic"]]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["name"] == w["config"] + "." + w["traffic"]
    metrics = b["end_to_end"] + b["per_layer"]
    for m in metrics:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0.01 <= m["bound"] <= 0.1 for m in b["end_to_end"])
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    assert 1 <= b["run_seconds"] <= 51
    for path in glob.glob(os.path.join(ROOT, "benchmarks", "**"),
                          recursive=True):
        rel = os.path.relpath(path, ROOT)
        if "__pycache__" in rel or ".pytest_cache" in rel:
            continue
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_every_cell_has_its_files():
    for w in bench()["workloads"]:
        loaded = harness.load_cell(w["name"])
        assert set(loaded["limits"]) >= {"loss", "rows_count"}
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "entries",
            loaded["traffic"]["entry"] + ".py"))


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout
    assert "TPU" in p.stderr


def test_layer_readers_are_silent_without_a_trace():
    ctx = {"window": {"compiles": 0, "wait_s": [0.1], "seconds": 2.0,
                      "builds": 0, "build_s": 0.0, "records": 10,
                      "passes": 1, "wire_bytes": 100},
           "trace": None, "chips": 1, "rate": 5.0,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "work": {"flops": 1.0, "bytes": 1.0}, "keys_per_example": 26,
           "flops_per_example": 1e6}
    names = [m["name"] for m in bench()["per_layer"]]
    vals = harness.read_layer_metrics(names, ctx)
    assert vals["entry.compiles_in_window"] == 0
    assert vals["pipeline.preload_wait_share"] == pytest.approx(5.0)
    assert vals["wire.bytes_per_example"] == 10.0
    for silent in ("step.ms_per_batch", "kernels.step_roofline",
                   "device.idle_share", "hostfront.build_s_per_pass",
                   "hostfront.keys_per_s"):
        assert silent not in vals


# ---- whole runs at toy size ------------------------------------------------

#: limits of the toy cell, from its own readings on seeds 5..13 (PR 25):
#: the program reads early_embed 0.00014..0.00036, early_g2sum
#: 0.00019..0.00046, rows_embed 0.0002..0.0015; the fp8 control 0.030..0.035,
#: 0.029..0.040 and 0.044..0.054. The loss of a toy pass swings with the
#: seed (8 steps of 256), so its toy limit is wide.
TOY_LIMITS = {"loss": 0.05, "dparam": 0.03, "rows_count": 0.0,
              "rows_embed": 0.015, "rows_g2sum": 0.012,
              "early_embed": 0.004, "early_g2sum": 0.004}


@pytest.fixture(autouse=True)
def cpu_float32_matmuls(monkeypatch):
    """A CPU multiplies float32 operands as they are; the references round
    them as a TPU does at its default precision unless told otherwise."""
    from benchmarks.reference.models import deepfm
    monkeypatch.setattr(deepfm, "F32_MATMUL_OPERANDS", None)


def toy_cell(workload):
    toy = copy.deepcopy(harness.load_cell(workload))
    c, t = toy["config"], toy["traffic"]
    c["slot_sizes"] = [1] * 6
    c["slot_vocab"] = [300, 7, 9000, 40, 3, 90]
    c["vocab_cap"] = 5000
    c["model"]["args"]["hidden"] = [32, 16]
    c["table_rows_per_chip"] = 1 << 16
    t.update(records_per_pass=2048, batch_per_chip=256, check_rows=512,
             warm_passes=2, traced_passes=1)
    toy["limits"] = dict(TOY_LIMITS)
    return toy


@pytest.fixture
def on_cpu(monkeypatch):
    """Skip the harness's look for a chip: the CPU's devices stand in."""
    import jax
    monkeypatch.setattr(harness, "require_tpu",
                        lambda chips: jax.devices()[:chips])


def plant(fault, monkeypatch):
    """Break the timed path underneath the harness."""
    import jax
    if fault == "state_unchanged":
        from paddlebox_tpu.train.trainer import Trainer
        monkeypatch.setattr(Trainer, "train_pass_resident",
                            lambda self, rp, log_prefix="": {})
    elif fault == "half_batch":
        from benchmarks.entries import common
        real = common.datasets

        def halved(desc, pool):
            out = real(desc, pool)
            for ds in out:
                odd = np.arange(ds.columnar.num_records) % 2 == 1
                ds.columnar.show[odd] = 0.0
                ds.columnar.clk[odd] = 0.0
            return out
        monkeypatch.setattr(common, "datasets", halved)
    elif fault == "altered_answer":
        from paddlebox_tpu.ps import table as table_mod
        real_update = table_mod.sparse_update

        def off(rows, *a, **k):
            new = real_update(rows, *a, **k)
            return new._replace(embed_w=new.embed_w + 1e-3)
        monkeypatch.setattr(table_mod, "sparse_update", off)
    elif fault is not None:
        raise ValueError(fault)


RUNS = [(CELLS[0], None), (CELLS[0], "state_unchanged"),
        (CELLS[0], "half_batch"), (CELLS[0], "altered_answer")]


@pytest.mark.parametrize("workload,fault", RUNS)
def test_harness_run_is_correct_only_when_sound(workload, fault, on_cpu,
                                                monkeypatch, capsys):
    """The rest of a run with the look for a chip skipped: a sound toy run
    is correct and reports what the contract asks; each planted fault
    comes out not correct."""
    toy = toy_cell(workload)
    monkeypatch.setattr(harness, "load_cell", lambda w: toy)
    plant(fault, monkeypatch)
    res = harness.run_cell(workload, 2 ** 31 + 5, 0.5, False,
                           time.perf_counter())
    harness.emit(res)
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert list(last)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert out.err.strip().splitlines()[-1].startswith("correct:")
    if fault is not None:
        assert res["correct"] is False, res["compared"]
        failed = [k for k, (v, lim) in res["compared"].items()
                  if not (np.isfinite(v) and v <= lim)]
        assert failed
        return
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["count"] == toy["cell"]["chips"]


def test_traced_run_reports_layers_and_breakdown(on_cpu, monkeypatch,
                                                 capsys):
    """A ``--trace 1`` run with the recorded trace standing in for the
    profiler (the CPU has no device plane): the per-layer metrics of the
    cell and no others, ``busy_s``/``window_s``, a breakdown of at most
    ten short names, each under its scope, shares in % and under 100.
    The recording is older than the program's scopes: its ops are given
    some here, by their place on the line."""
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        rec = json.load(f)
    given = ["pbox.decode", "pbox.dedup", "pbox.pull", "pbox.pull.bwd",
             "pbox.pool_cvm", "pbox.dense.bwd", "pbox.push", "other"]
    for plane in rec["trace"]["planes"]:
        for line in plane["lines"]:
            if line["name"] == tracered.OPS_LINE:
                line["events"] = [ev + [given[i % len(given)]]
                                  for i, ev in enumerate(line["events"])]

    def traced(entry, n_passes):
        red = tracered.reduce(rec["trace"])
        red.update(batches=4, passes=n_passes)
        return red
    toy = toy_cell(CELLS[0])
    monkeypatch.setattr(harness, "load_cell", lambda w: toy)
    monkeypatch.setattr(harness, "run_traced", traced)
    monkeypatch.setattr(harness, "device_peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    res = harness.run_cell(CELLS[0], 7, 0.3, True, time.perf_counter())
    want = {m["name"] for m in bench()["per_layer"]
            if CELLS[0] in m.get("workloads", [CELLS[0]])}
    assert set(res["metrics"]) == want
    assert "train_examples_per_s_per_chip" not in res["metrics"]
    assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
    for name in ("device.idle_share", "kernels.step_roofline", "step.mfu",
                 "pipeline.preload_wait_share"):
        assert res["metrics"][name]["unit"] == "%"
        assert 0 <= res["metrics"][name]["value"] < 100
    bd = res["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10
    assert all(len(n) <= 64 for n, _ in bd["device_ops"])
    assert all(n.split("/")[0] in given for n, _ in bd["device_ops"])
    ops = dict(tracered.reduce(rec["trace"])["ops"])
    assert all(ops[n.split("/", 1)[1]] == sec for n, sec in bd["device_ops"])
    # the four scope readers and the ops without a scope are the step
    parts = sum(res["metrics"][n]["value"] for n in (
        "step.decode_dedup_ms", "step.pull_pool_ms", "step.dense_ms",
        "step.push_ms"))
    step = res["metrics"]["step.ms_per_batch"]["value"]
    assert 0.5 * step < parts < step
    assert {n for n, _ in bd["idle_gaps"]} == {"wait", "train", "other"}


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload, monkeypatch):
    """The control, the reference in the program's place with the net's
    matmul operands in fp8 (the precision below the stated bfloat16),
    fails the toy cell's limits on three seeds; so do the faults planted
    in the reference."""
    from benchmarks import study
    toy = toy_cell(workload)
    got = list(study.stand_in_readings(toy, [11, 12, 13]))
    assert len([r for _, r, _ in got if r.startswith("control:")]) == 3
    assert {r for _, r, _ in got} >= {"fault:half_batch",
                                      "fault:state_unchanged"}
    for seed, run, numbers in got:
        ok, table = compare.judge(numbers, toy["limits"])
        assert not ok, (seed, run, numbers)


def test_study_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "study.py"),
         "--workload", CELLS[0], "--seeds", "1"], env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "numbers" not in p.stdout
    assert "TPU" in p.stderr


def test_logloss_from_buckets():
    n = 1000
    pos, neg = np.zeros(n), np.zeros(n)
    pos[700] = 3      # three clicks predicted at about 0.7005
    neg[200] = 1
    want = -(3 * np.log(0.7005) + np.log(1 - 0.2005)) / 4
    assert compare.logloss_from_buckets(pos, neg) == pytest.approx(want)


def test_worst_leaf_gap_is_a_gap_of_norms():
    ref = {"a": np.array([3.0, 4.0]), "b": np.array([0.0, 1e-9]),
           "c": np.array([1.0, 0.0])}
    prog = {"a": np.array([4.0, 3.0]), "b": np.array([0.0, 2e-9]),
            "c": np.array([0.0, 0.5])}
    # a: norms equal; b: tiny against the median leaf (1.0); c: 0.5 / 1.0
    assert compare.worst_leaf_gap(prog, ref) == pytest.approx(0.5)
