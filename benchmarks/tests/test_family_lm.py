"""Family ``lm`` (``families/lm.py``, ``entries/resident_seq.py``,
``reference/lm.py`` + ``reference/models/nemotron_h.py``) through the
harness and ``study.py`` on the CPU at a toy size, as ``test_families.py``
does for the stub family: sound runs come out correct, the control and
the planted faults do not; the generator and ``work`` against hand
counts."""

import copy
import json
import os
import time

import numpy as np
import pytest

from benchmarks import compare, harness, study, tracered
from benchmarks.families import lm
from benchmarks.tests.test_benchmarks import bench
from benchmarks.tests.test_families import PEAKS

CELL = "nemotron3-nano-30b-a3b.train-packed-8k"

#: limits of the toy cell, from its own readings on the CPU: the program
#: (bfloat16 operands) against the reference with the same roundings
#: written out, seeds 5..10, reads early_embed 0.010..0.020, early_g2sum
#: 0.006..0.019, dparam 0.007..0.015, loss 3e-5..1.3e-4; the float8
#: control (operands float8, cotangents kept), seeds 5..7: 0.199..0.231,
#: 0.153..0.554, 0.047..0.066 and 8.7e-4..2.1e-3; a state left unchanged
#: reads 1 on the three first. Each limit lies between its two readings.
TOY_LIMITS = {"loss": 4e-4, "dparam": 0.3, "rows_count": 0.0,
              "early_embed": 0.06, "early_g2sum": 0.06}


def toy_cell():
    toy = copy.deepcopy(harness.load_cell(CELL))
    toy["config"].update(
        hidden_size=64, vocab_size=96, mamba_num_heads=8, mamba_head_dim=8,
        n_groups=2, ssm_state_size=16, chunk_size=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=96, router_outputs=16,
        n_routed_experts=4, num_experts_per_tok=3, table_rows_per_chip=96)
    toy["config"]["dense_optimizer"]["learning_rate"] = 1e-3
    toy["traffic"].update(
        seq_len=24, batch_per_chip=48, records_per_pass=192,
        doc_len_median=6, doc_len_sigma=1.0, pool_size=2, warm_passes=2,
        check_rows=64, traced_passes=1)
    toy["limits"] = dict(TOY_LIMITS)
    return toy


@pytest.fixture
def toy(monkeypatch):
    import jax
    cell = toy_cell()
    monkeypatch.setattr(harness, "load_cell", lambda w: cell)
    monkeypatch.setattr(harness, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "device_peaks", lambda kind: PEAKS)
    return cell


# ---- the generator ---------------------------------------------------------

def test_packed_documents_same_seed_same_tokens():
    cell = harness.load_cell(CELL)
    config, traffic = cell["config"], dict(cell["traffic"],
                                           records_per_pass=4 * 8192)
    a = lm.make_pass(config, traffic, 2 ** 31 + 7, 1)
    b = lm.make_pass(config, traffic, 2 ** 31 + 7, 1)
    c = lm.make_pass(config, traffic, 2 ** 31 + 8, 1)
    assert a.tokens.shape == (4, 8193) and a.tokens.dtype == np.int32
    assert np.array_equal(a.tokens, b.tokens)
    assert not np.array_equal(a.tokens, c.tokens)
    assert a.num_records == 4 * 8192
    assert np.array_equal(a.inputs[:, 1:], a.labels[:, :-1])
    # every sequence opens with a document; id 0 opens documents only,
    # lengths have the stated median to a factor (log-normal, cut)
    assert (a.tokens[:, 0] == 0).all()
    assert 0 < a.tokens.max() < int(config["vocab_size"])
    starts = np.nonzero(a.tokens.reshape(-1) == 0)[0]
    gaps = np.diff(starts)
    assert 300 < np.median(gaps) < 1200
    # Zipf: the most frequent id is seen far more often than the median
    counts = np.bincount(a.tokens[a.tokens > 0])
    assert counts.max() > 30 * np.median(counts[counts > 0])


def test_sample_is_the_ids_only_the_first_steps_read():
    cell = toy_cell()
    pool = lm.make_pool(cell["config"], cell["traffic"], 11, count=1)
    ids = lm.sample(pool, cell["traffic"], 11)
    seqs = 48 // 24
    early = set(pool[0].inputs[:compare.EARLY_STEPS * seqs].reshape(-1))
    late = set(pool[0].inputs[compare.EARLY_STEPS * seqs:].reshape(-1))
    assert set(ids) == early - late and len(ids) > 0


# ---- the step's work from shapes -------------------------------------------

def test_work_counts_the_parameters_a_token_passes_through():
    cell = harness.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    lp = lm.layer_params(config)
    # the issue's own arithmetic: 38.7M a Mamba layer, 23.4M attention,
    # 20.3M of an expert layer outside its routed experts, 9.98M an expert
    assert lp["M"] == 2688 * 10304 + 4096 * 2688 == 38_707_200
    assert lp["*"] == 2 * 2688 * 4096 + 2 * 2688 * 256 == 23_396_352
    assert lp["E_route"] + lp["E_shared"] == 2688 * 128 + 2 * 2688 * 3712
    assert lp["E_experts"] == pytest.approx(0.375 * 2 * 2688 * 1856)
    through = (4 * lp["M"] + lp["*"] + 4 * (lp["E_route"] + lp["E_shared"]
                                            + lp["E_experts"]) + lp["head"])
    assert through == pytest.approx(318.4e6, rel=1e-3)
    shapes = [(2688, 16384), (16384, 2688), (2688, 10304)]
    w = lm.work(config, traffic, 1, shapes)
    attn = 3 * 2 * 2 * 4096 * 128 * 32
    scan = 3 * 4 * (2 * 64 * 8 * 128 + 2 * 64 * 64 * 64
                    + 4 * 64 * 64 * 128)
    assert w["flops_per_example"] == pytest.approx(6 * through + attn + scan)
    assert w["flops"] == w["flops_per_example"] * 16384
    assert w["keys_per_example"] == 1 and w["tokens"] == 16384
    assert set(w["scopes"]) == {"pbox.ssm_scan", "pbox.attn",
                                "pbox.moe_experts"}
    assert w["scopes"]["pbox.attn"]["flops"] == pytest.approx(
        (6 * lp["*"] + attn) * 16384)
    assert w["scopes"]["pbox.moe_experts"]["flops"] == pytest.approx(
        4 * 6 * lp["E_experts"] * 16384)
    # distinct rows of a step under Zipf(1): a few thousand of 16,383
    assert 3000 < w["rows"] < 9000
    # a second size: half the tokens, an attention-only stack
    small = dict(config, hybrid_override_pattern="**")
    w2 = lm.work(small, dict(traffic, batch_per_chip=8192), 1, shapes)
    assert w2["flops_per_example"] == pytest.approx(
        6 * (2 * lp["*"] + lp["head"]) + 2 * attn)
    assert w2["flops"] == w2["flops_per_example"] * 8192
    assert w2["scopes"]["pbox.ssm_scan"]["flops"] == 0


# ---- whole runs at toy size ------------------------------------------------

def test_harness_run_is_correct(toy, capsys):
    harness.emit(harness.run_cell(CELL, 2 ** 31 + 5, 0.3, False,
                                  time.perf_counter()))
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert last["correct"] is True, last["compared"]
    assert set(last["compared"]) == set(TOY_LIMITS)
    assert set(last["metrics"]) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    ref = [json.loads(ln) for ln in out.out.splitlines()
           if ln.startswith('{"line": "reference"')][0]
    assert len(ref["losses"]) == len(ref["losses_reference"]) == 4
    # a record is a token: 192 a pass
    passes = [json.loads(ln) for ln in out.out.splitlines()
              if ln.startswith('{"line": "passes"')][0]
    window = sum(passes["wait_s"]) + sum(passes["train_s"])
    rate = last["metrics"]["train_examples_per_s_per_chip"]["value"]
    assert rate == pytest.approx(192 * last["attempted"] / window, rel=0.2)


def test_traced_run_reports_the_cells_layers(toy, monkeypatch):
    """``--trace 1`` with the profiler patched out by a hand-made plane
    under the step's own scopes: every metric of the cell reads, the
    scope readers sum to the step, no share passes 100."""
    scopes = ["pbox.decode", "pbox.dedup", "pbox.pull", "pbox.ssm_proj",
              "pbox.ssm_conv", "pbox.ssm_scan", "pbox.ssm_scan.bwd",
              "pbox.attn", "pbox.attn.bwd", "pbox.moe_route",
              "pbox.moe_experts", "pbox.moe_experts.bwd", "pbox.moe_shared",
              "pbox.head", "pbox.loss", "pbox.push", "pbox.dense_opt"]
    dev = [["while.1", 0, 100 * len(scopes) * 10 ** 6]] + [
        [f"fusion.{i} f32[8]", i * 10 ** 8, 10 ** 8, s]
        for i, s in enumerate(scopes)]
    end = 100 * len(scopes) * 10 ** 6
    plane = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": dev}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.traced", 0, end], ["bench.train", 0, end]]}]}]}

    def traced(entry, n_passes):
        red = tracered.reduce(plane)
        red.update(batches=4, passes=n_passes)
        return red
    monkeypatch.setattr(harness, "run_traced", traced)
    res = harness.run_cell(CELL, 9, 0.3, True, time.perf_counter())
    assert res["correct"] is True, res["compared"]
    want = {m["name"] for m in bench()["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(res["metrics"]) == want
    val = {k: v["value"] for k, v in res["metrics"].items()}
    assert val["step.ssm_ms"] == pytest.approx(4 * 25.0)
    assert val["step.attn_ms"] == pytest.approx(2 * 25.0)
    assert val["step.moe_ms"] == pytest.approx(4 * 25.0)
    assert val["step.head_loss_ms"] == pytest.approx(2 * 25.0)
    assert val["step.ms_per_batch"] == pytest.approx(len(scopes) * 25.0)
    # the table's wide-row paths have readers of their own on this cell
    assert val["step.wide_pull_ms"] == pytest.approx(25.0)
    assert val["step.wide_push_ms"] == pytest.approx(25.0)
    for name in ("kernels.ssm_scan_roofline", "kernels.moe_experts_roofline",
                 "kernels.attn_roofline", "kernels.step_roofline",
                 "step.mfu"):
        assert 0 < val[name] < 100, name
    assert val["moe.load_imbalance"] >= 1.0
    assert val["entry.compiles_in_window"] == 0
    assert val["wire.bytes_per_example"] > 0
    assert val["hostfront.keys_per_s"] > 0


def test_a_step_that_trains_nothing_is_not_correct(toy, monkeypatch):
    from paddlebox_tpu.train.step import SeqTrainStep
    real = SeqTrainStep._step

    def frozen(self, state, batch, rng):
        new, stats = real(self, state, batch, rng)
        return state._replace(step=new.step), stats
    monkeypatch.setattr(SeqTrainStep, "_step", frozen)
    res = harness.run_cell(CELL, 6, 0.2, False, time.perf_counter())
    assert res["correct"] is False
    assert res["compared"]["dparam"][0] == pytest.approx(1.0)
    assert res["compared"]["rows_count"][0] > 0


def test_study_reads_the_control_and_both_faults(toy):
    got = list(study.stand_in_readings(toy, [3, 2 ** 31 + 4]))
    assert [run for _, run, _ in got] == [
        "control:float8_e4m3fn", "fault:state_unchanged",
        "fault:experts_dropped"] * 2
    for seed, run, numbers in got:
        ok, _ = compare.judge(numbers, toy["limits"])
        if run != "fault:experts_dropped":
            # at 192 tokens a pass few choices pass an expert's capacity:
            # the drop moves the toy's numbers less than rounding does
            assert not ok, (seed, run, numbers)
        assert numbers["rows_count"] == 0 or run == "fault:state_unchanged"
    for seed, run, numbers in study.program_readings(toy, [8]):
        ok, _ = compare.judge(numbers, toy["limits"])
        assert ok and run == "program", numbers


def test_dropping_over_capacity_changes_what_the_reference_computes():
    """The planted fault at a size where it bites: every token picks the
    same experts, capacity 1.0 keeps an even share of them."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference.models import nemotron_h as ref
    config = toy_cell()["config"]
    z = ref.dims(config)
    idx = jnp.tile(jnp.array([[0, 1, 2]]), (32, 1)).reshape(2, 16, 3)
    w = jnp.ones((2, 16, 3))
    kept = ref.drop_over_capacity(idx, w, z, 1.0)
    # 96 choices over 16 experts: 6 an expert kept, in token order
    assert float(kept.sum()) == 3 * 6
    assert bool((kept.reshape(32, 3)[:6] == 1).all())
    assert bool((kept.reshape(32, 3)[6:] == 0).all())
