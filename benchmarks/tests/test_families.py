"""The seam between the harness and a model family: a second family made
of test files alone goes through ``harness.run_cell`` and ``study.py``
with nothing of CTR's called; ``families/ctr`` gives, number for number,
what the harness gave before the code moved there; and the reduced trace
keeps the program's ``pbox.*`` scopes."""

import json
import os
import sys
import time

import numpy as np
import pytest

from benchmarks import compare, harness, roofline, study, tracered
from benchmarks import traffic as traffic_mod
from benchmarks.families import ctr as ctr_family
from benchmarks.tests import stub_seq
from benchmarks.tests.test_benchmarks import CELLS, bench, toy_cell

HERE = os.path.dirname(os.path.abspath(__file__))
STUB_CELL = "stub-seq.toy"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ---- a family of files alone -----------------------------------------------

def stub_loaded():
    return {
        "bench": bench(),
        "cell": {"name": STUB_CELL, "config": "stub-seq", "traffic": "toy",
                 "chips": 1},
        "config": {"family": "stub_seq", "reference": "stub_seq",
                   "record": "one token", "vocab": 64, "width": 16,
                   "seq_len": 32, "learning_rate": 0.5,
                   "precision": "float32"},
        "traffic": {"entry": "stub_seq", "records_per_pass": 64,
                    "batch_per_chip": 16, "pool_size": 2, "warm_passes": 2,
                    "traced_passes": 1},
        # set as PERF.md sets a cell's: the program reads dparam
        # 1.5e-7..3.7e-7 on six seeds, the float16 control 3.9e-6..7.7e-6,
        # a state left unchanged 1; the loss of three steps separates
        # nothing at this size (sound 3.4e-7, the fault 1.4e-6)
        "limits": {"loss": 1e-5, "dparam": 1.5e-6}}


@pytest.fixture
def stub_cell(monkeypatch):
    """The stub under the three names the harness imports, the look for a
    chip skipped, and every CTR function a trap."""
    import jax
    from benchmarks.reference import ctr as ref_ctr
    for name in ("benchmarks.families.stub_seq",
                 "benchmarks.entries.stub_seq",
                 "benchmarks.reference.models.stub_seq"):
        monkeypatch.setitem(sys.modules, name, stub_seq)
    loaded = stub_loaded()
    monkeypatch.setattr(harness, "load_cell", lambda w: loaded)
    monkeypatch.setattr(harness, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "device_peaks", lambda kind: PEAKS)

    def trap(mod, fn):
        def sprung(*a, **k):
            raise AssertionError(f"{mod.__name__}.{fn} was called for a "
                                 "family that is not ctr")
        monkeypatch.setattr(mod, fn, sprung)
    for mod, fns in ((ref_ctr, ("run_pass",)),
                     (compare, ("compare", "early_rows",
                                "logloss_from_buckets", "worst_leaves")),
                     (roofline, ("step_work", "dense_flops_per_example")),
                     (traffic_mod, ("make_pool", "make_pass")),
                     (ctr_family, ("first_pass", "reference_pass",
                                   "numbers", "work"))):
        for fn in fns:
            trap(mod, fn)
    return loaded


def scoped_trace():
    """One device, 1000 ns of window: a ``while`` over four ops of three
    scopes and one with none."""
    dev = [["while.1", 100, 800],
           ["fusion.1 f32[8]", 100, 300, "stub.head"],
           ["fusion.2 f32[8]", 400, 200, "stub.head.bwd"],
           ["fusion.3 f32[8]", 600, 100, "stub.embed"],
           ["copy.4 f32[8]", 700, 100]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": dev}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.traced", 0, 1000], ["bench.train", 0, 1000]]}]}]}


def test_a_second_family_runs_through_the_harness(stub_cell, monkeypatch,
                                                  capsys):
    harness.emit(harness.run_cell(STUB_CELL, 2 ** 31 + 9, 0.3, False,
                                  time.perf_counter()))
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert last["correct"] is True, last["compared"]
    assert set(last["compared"]) == {"loss", "dparam"}
    assert set(last["metrics"]) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    assert last["attempted"] >= 1 and last["failed"] == 0
    # records are tokens: 64 sequences of 32 positions a pass
    passes = [json.loads(ln) for ln in out.out.splitlines()
              if ln.startswith('{"line": "passes"')][0]
    rate = last["metrics"]["train_examples_per_s_per_chip"]["value"]
    window = sum(passes["wait_s"]) + sum(passes["train_s"])
    assert rate == pytest.approx(64 * 32 * last["attempted"] / window,
                                 rel=0.2)
    assert "compared loss:" in out.err and "compared dparam:" in out.err


def test_a_second_family_reports_layers_from_its_own_work(stub_cell,
                                                          monkeypatch):
    """``--trace 1`` with the profiler patched out: every reader gets the
    family's ``work`` and the trace's scopes in ``ctx``."""
    def traced(entry, n_passes):
        red = tracered.reduce(scoped_trace())
        red.update(batches=4, passes=n_passes)
        return red
    seen = {}
    real = harness.read_layer_metrics

    def reading(names, ctx):
        seen.update(ctx)
        return real(names, ctx)
    monkeypatch.setattr(harness, "run_traced", traced)
    monkeypatch.setattr(harness, "read_layer_metrics", reading)
    res = harness.run_cell(STUB_CELL, 5, 0.3, True, time.perf_counter())
    assert res["correct"] is True, res["compared"]
    work = stub_seq.work(stub_cell["config"], stub_cell["traffic"], 1, [])
    assert seen["work"] == work and "stub.head" in seen["work"]["scopes"]
    assert seen["trace"]["scopes"]["stub.head"] == pytest.approx(300e-9)
    assert seen["keys_per_example"] == 1
    assert seen["flops_per_example"] == work["flops_per_example"]
    # the metrics of every cell, none of the DeepFM cell's own
    want = {m["name"] for m in bench()["per_layer"] if "workloads" not in m}
    silent = {"hostfront.build_s_per_pass", "hostfront.keys_per_s"}
    assert set(res["metrics"]) == want - silent
    mfu = res["metrics"]["step.mfu"]["value"]
    rate = 100.0 * work["flops_per_example"] / PEAKS["bf16_flops_per_s"]
    assert 0 < mfu == pytest.approx(rate * seen["rate"])
    assert 0 < res["metrics"]["kernels.step_roofline"]["value"] < 100
    names = [n for n, _ in res["breakdown"]["device_ops"]]
    assert names[0] == "stub.head/fusion.1 f32[8]"
    assert "other/copy.4 f32[8]" in names and "other/while.1" in names


def test_a_second_family_with_its_step_broken_is_not_correct(stub_cell,
                                                             monkeypatch):
    """The timed path broken underneath the harness: a step that returns
    its state unchanged."""
    real = stub_seq.Entry.__init__

    def broken(self, *a, **k):
        real(self, *a, **k)
        step = self.step
        self.step = lambda p, seq: (p, step(p, seq)[1])
    monkeypatch.setattr(stub_seq.Entry, "__init__", broken)
    res = harness.run_cell(STUB_CELL, 6, 0.2, False, time.perf_counter())
    assert res["correct"] is False
    assert res["compared"]["dparam"][0] == pytest.approx(1.0)


def test_study_reads_a_second_familys_control_and_faults(stub_cell):
    got = list(study.stand_in_readings(stub_cell, [3, 2 ** 31 + 4]))
    assert [run for _, run, _ in got] == [
        "control:float16", "fault:state_unchanged"] * 2
    for seed, run, numbers in got:
        ok, _ = compare.judge(numbers, stub_cell["limits"])
        assert not ok, (seed, run, numbers)
    for seed, run, numbers in study.program_readings(stub_cell, [8]):
        ok, _ = compare.judge(numbers, stub_cell["limits"])
        assert ok and run == "program", numbers


def test_harness_and_study_name_nothing_of_ctr():
    """The acceptance criterion, as text: neither file calls a CTR
    function by name."""
    for path in ("harness.py", "study.py"):
        with open(os.path.join(os.path.dirname(HERE), path)) as f:
            text = f.read()
        for name in ("reference.ctr", "reference import ctr", "run_pass",
                     "compare.compare", "early_rows", "logloss_from_buckets",
                     "step_work", "dense_flops_per_example", "make_pool(",
                     "import roofline", "roofline."):
            if name == "make_pool(":
                assert "traffic_mod.make_pool" not in text, path
                continue
            assert name not in text, (path, name)


# ---- families/ctr against the parent ---------------------------------------

#: recorded at the parent (a8e5e2c, before the code moved) on the CPU with
#: ``toy_cell``: ``study.stand_in_readings(toy, [11, 2**31 + 12],
#: faults=("half_batch",))`` and the ``work`` of the toy cell as
#: ``harness.run_cell`` put it together (the same values with XLA's CPU
#: thread pool on and off)
PINNED = {
    11: {
        "control:float8_e4m3fn": {
            "loss": 0.004391693373716784, "dparam": 0.014271187047291451,
            "grad_ema": 0.025420779955363713, "rows_count": 0.0,
            "rows_embed": 0.05376054647855351,
            "rows_g2sum": 0.04134349529844288,
            "early_embed": 0.034481970700953275,
            "early_g2sum": 0.040179273024728755},
        "fault:half_batch": {
            "loss": 0.028527234247118255, "dparam": 0.09868453623740832,
            "grad_ema": 0.2073410906405104, "rows_count": 368.0,
            "rows_embed": 0.946550231827404,
            "rows_g2sum": 2.847597977246453,
            "early_embed": 0.9787957070417529,
            "early_g2sum": 2.2371219735731906}},
    2 ** 31 + 12: {
        "control:float8_e4m3fn": {
            "loss": 0.000573612758604022, "dparam": 0.07178392741715812,
            "grad_ema": 0.01586960162701221, "rows_count": 0.0,
            "rows_embed": 0.04234371321176085,
            "rows_g2sum": 0.0296098253451758,
            "early_embed": 0.025300400898961448,
            "early_g2sum": 0.044686769737743534},
        "fault:half_batch": {
            "loss": 0.005507652544228649, "dparam": 0.10349196433602927,
            "grad_ema": 0.2897757467586046, "rows_count": 329.0,
            "rows_embed": 1.2633747073209982,
            "rows_g2sum": 2.817031864303061,
            "early_embed": 1.0247360381447794,
            "early_g2sum": 2.4068421595423675}}}
PINNED_WORK = {
    "flops": 5303808.0, "bytes": 177652.16731624477,
    "rows": 385.83410794557767, "table_bytes": 83340.16731624477,
    "dense_bytes": 84072.0, "wire_bytes": 10240.0,
    "flops_per_example": 20718.0, "keys_per_example": 6, "scopes": {}}
TOY_SHAPES = [(1,), (13, 1), (32,), (91, 32), (16,), (32, 16), (1,),
              (16, 1)]


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_ctr_family_reads_what_the_parent_read(seed, monkeypatch):
    from benchmarks.reference.models import deepfm
    monkeypatch.setattr(deepfm, "F32_MATMUL_OPERANDS", None)
    toy = toy_cell(CELLS[0])
    assert harness.family_of(toy["config"]) is ctr_family
    assert harness.family_of({}) is ctr_family      # no key: ctr
    got = {run: numbers for _, run, numbers in study.stand_in_readings(
        toy, [seed], faults=("half_batch",))}
    assert got == PINNED[seed]


def test_ctr_family_work_is_what_the_parent_put_together():
    import jax
    toy = toy_cell(CELLS[0])
    config, traffic = toy["config"], toy["traffic"]
    from benchmarks.reference.models import deepfm
    params = ctr_family.seeded_params(deepfm, config, 11)
    shapes = [tuple(x.shape) for x in jax.tree.leaves(params)]
    assert shapes == TOY_SHAPES
    assert ctr_family.work(config, traffic, 1, shapes) == PINNED_WORK


# ---- the reduced trace keeps the program's names ---------------------------

def test_scope_of_a_name_stack():
    assert tracered.scope_of(
        "jit(run)/while/body/pbox.pull/gather") == "pbox.pull"
    assert tracered.scope_of(
        "jit(run)/while/body/transpose(jvp(pbox.pull))/gather:"
    ) == "pbox.pull.bwd"
    assert tracered.scope_of("jit(run)/jvp(pbox.dense)/dot") == "pbox.dense"
    # the innermost catalog name wins
    assert tracered.scope_of(
        "jit(run)/pbox.push/while/body/pbox.dedup/sort") == "pbox.dedup"
    assert tracered.scope_of("jit(run)/while/body/copy") == "other"
    assert tracered.scope_of("") == "other"


def test_scopes_sum_to_ops_and_unscoped_ops_are_other():
    trace = scoped_trace()
    # a second device with the same ops twice as long: means over devices
    dev1 = [[e[0], e[1] * 2, e[2] * 2] + e[3:]
            for e in trace["planes"][0]["lines"][0]["events"]]
    trace["planes"].insert(1, {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": dev1}]})
    trace["planes"][2]["lines"][0]["events"] = [["bench.traced", 0, 2000]]
    red = tracered.reduce(trace)
    assert red["devices"] == 2
    sc = red["scopes"]
    assert sc["stub.head"] == pytest.approx(1.5 * 300e-9)
    assert sc["stub.head.bwd"] == pytest.approx(1.5 * 200e-9)
    assert sc["stub.embed"] == pytest.approx(1.5 * 100e-9)
    # the op with no scope and the while's self time
    assert sc["other"] == pytest.approx(1.5 * (100 + 100) * 1e-9)
    assert sum(sc.values()) == pytest.approx(sum(s for _, s in red["ops"]))
    assert sum(sc.values()) == pytest.approx(red["busy_s"])
    assert red["op_scope"]["fusion.2 f32[8]"] == "stub.head.bwd"
    assert red["op_scope"]["while.1"] == "other"
    named = tracered.scoped_ops(red, top=2)
    assert named == [["stub.head/fusion.1 f32[8]", red["ops"][0][1]],
                     ["stub.head.bwd/fusion.2 f32[8]", red["ops"][1][1]]]
    red["batches"] = 2
    # forward and backward fold into one reading; a scope that is not in
    # the trace reads as nothing
    assert tracered.scope_ms_per_batch(red, ("stub.head",)) == pytest.approx(
        1e3 * 1.5 * 500e-9 / 2)
    assert tracered.scope_ms_per_batch(red, ("pbox.push",)) is None
    assert tracered.scope_ms_per_batch(None, ("stub.head",)) is None


def _pb(no, payload):
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(payload, int):
        return varint(no << 3) + varint(payload)
    payload = payload.encode() if isinstance(payload, str) else payload
    return varint(no << 3 | 2) + varint(len(payload)) + payload


def test_op_scopes_reads_the_tf_op_stat_of_the_event_metadata(tmp_path):
    """An xplane written by hand in the wire format: a device plane whose
    event metadata carry the name stack as the stat ``tf_op`` (one as a
    string, one as a reference to a stat's name), one event without it,
    and a host plane that is not read."""
    stat_meta = (_pb(5, _pb(1, 7) + _pb(2, _pb(1, 7) + _pb(2, "tf_op")))
                 + _pb(5, _pb(1, 9) + _pb(2, _pb(1, 9) + _pb(
                     2, "jit(run)/transpose(jvp(pbox.pull))/mul"))))

    def event_meta(key, name, stat=None):
        body = _pb(1, key) + _pb(2, name)
        if stat is not None:
            body += _pb(5, _pb(1, 7) + stat)
        return _pb(4, _pb(1, key) + _pb(2, body))
    device = (_pb(2, "/device:TPU:0") + stat_meta
              + event_meta(1, "%fusion.1 = f32[8]{0} fusion(...)",
                           _pb(5, "jit(run)/while/body/pbox.push/scatter"))
              + event_meta(2, "%fusion.2 = f32[8]{0} fusion(...)",
                           _pb(7, 9))
              + event_meta(3, "%copy.3 = f32[8]{0} copy(...)"))
    host = _pb(2, "/host:CPU") + stat_meta + event_meta(
        1, "x", _pb(5, "pbox.push"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(1, device) + _pb(1, host))
    assert tracered.op_scopes(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]{0} fusion(...)": "pbox.push",
        "%fusion.2 = f32[8]{0} fusion(...)": "pbox.pull.bwd"}}


def test_the_four_scope_readers_read_their_scopes():
    red = {"batches": 10, "scopes": {
        "pbox.decode": 0.03, "pbox.dedup": 0.02, "pbox.pull": 0.02,
        "pbox.pull.bwd": 0.02, "pbox.pool_cvm": 0.005,
        "pbox.pool_cvm.bwd": 0.005, "pbox.dense": 0.002,
        "pbox.dense.bwd": 0.004, "pbox.loss": 0.001, "pbox.dense_opt": 0.002,
        "pbox.auc": 0.001, "pbox.push": 0.05, "other": 0.005}}
    names = ["step.decode_dedup_ms", "step.pull_pool_ms", "step.dense_ms",
             "step.push_ms"]
    vals = harness.read_layer_metrics(names, {"trace": red})
    assert vals == pytest.approx({
        "step.decode_dedup_ms": 5.0, "step.pull_pool_ms": 5.0,
        "step.dense_ms": 1.0, "step.push_ms": 5.0})
    # with ``other`` they are the whole step
    assert sum(vals.values()) + 0.5 == pytest.approx(
        1e3 * sum(red["scopes"].values()) / 10)
    assert harness.read_layer_metrics(names, {"trace": None}) == {}
    assert harness.read_layer_metrics(
        names, {"trace": {"batches": 10, "scopes": {"other": 1.0}}}) == {}
    by_name = {m["name"]: m for m in bench()["per_layer"]}
    for name in names:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "ms", "lower", "device_trace", "device step")
        assert m["moves"] == "train_examples_per_s_per_chip"
        assert m["workloads"] == CELLS
