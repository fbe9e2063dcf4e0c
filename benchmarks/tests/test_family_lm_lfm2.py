"""Family ``lm_lfm2`` (``families/lm_lfm2.py`` over ``families/lm.py``,
``entries/resident_seq.py``, ``reference/lm.py`` +
``reference/models/lfm2.py``) through the harness and ``study.py`` on the
CPU at a toy size, as ``test_family_lm.py`` does for family ``lm``: sound
runs come out correct, the control and the faults that bite do not;
``work`` against hand counts."""

import copy
import json
import time

import pytest

from benchmarks import compare, harness, study, tracered
from benchmarks.families import lm, lm_lfm2
from benchmarks.tests.test_benchmarks import bench
from benchmarks.tests.test_families import PEAKS

CELL = "lfm2-24b-a2b.train-packed-8k"

#: limits of the toy cell, from its own readings on the CPU: the program
#: (bfloat16 operands) against the reference with the same roundings
#: written out, seeds 5..10 and 2^31+5, reads early_embed 0.0034..0.0052,
#: early_g2sum 0.0021..0.0032, dparam 0.001..0.032, loss 0.8e-5..5e-5; the
#: float8 control (operands float8, cotangents kept), seeds 3, 5 and
#: 2^31+4: 0.049..0.053, 0.0156..0.0181, 0.011..0.029 and 2.8e-4..7.9e-4;
#: a state left unchanged reads 1 on the three first. Each limit lies
#: between its two readings (dparam's control reads inside the program's
#: range at this size: the limit only catches a state left unchanged).
TOY_LIMITS = {"loss": 1.5e-4, "dparam": 0.3, "rows_count": 0.0,
              "early_embed": 0.016, "early_g2sum": 0.008}


def toy_cell():
    toy = copy.deepcopy(harness.load_cell(CELL))
    toy["config"].update(
        hidden_size=64, vocab_size=96, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=80,
        moe_intermediate_size=48, router_outputs=16, num_experts=4,
        num_experts_per_tok=3, num_hidden_layers=4, num_dense_layers=1,
        layer_types=["conv", "full_attention", "conv", "conv"],
        table_rows_per_chip=96)
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


# ---- the files ---------------------------------------------------------------

def test_the_family_is_lm_but_for_the_counting():
    for name in ("make_pool", "seeded_params", "sample", "first_pass",
                 "reference_pass", "numbers", "diagnostics",
                 "control_precision", "FAULTS"):
        assert getattr(lm_lfm2, name) is getattr(lm, name), name
    assert lm_lfm2.work is not lm.work
    assert lm_lfm2.layer_params is not lm.layer_params


def test_configuration_keeps_the_published_widths():
    """Every number of the catalog's row under its own key, but the five
    that ``reduced`` names; the cut is the issue's."""
    config = harness.load_cell(CELL)["config"]
    entry = [c for c in bench()["configs"] if c["name"] == config["name"]][0]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["rope_theta"] == config["rope_parameters"]["rope_theta"]
    assert config["router_outputs"] == config["published"]["num_experts"] \
        == 64
    assert (config["num_experts"], config["first_expert_held"]) == (8, 0)
    kinds = config["published"]["layer_types"]
    assert len(kinds) == 40 and kinds[:4] * 10 == kinds
    assert config["layer_types"] == [kinds[0]] + kinds[2:9]
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 8
    assert config["vocab_size"] * config["vocabulary_parallel"] == \
        config["published"]["vocab_size"]
    assert config["table_rows_per_chip"] == config["vocab_size"]


# ---- the step's work from shapes -------------------------------------------

def test_work_counts_the_parameters_a_token_passes_through():
    cell = harness.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    lp = lm_lfm2.layer_params(config)
    # the issue's own arithmetic: 16.78M a conv operator, 10.49M
    # attention, 72.35M the dense MLP, 9.44M an expert of which a token
    # passes through 4 x 8 / 64 = half of one
    assert lp["conv"] == 2048 * 6144 + 3 * 2048 + 2048 * 2048 == 16_783_360
    assert lp["full_attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512 + 128 \
        == 10_485_888
    assert lp["mlp"] == 3 * 2048 * 11776 == 72_351_744
    assert lp["route"] == 2048 * 64
    assert lp["experts"] == pytest.approx(0.5 * 3 * 2048 * 1536)
    assert lp["head"] == 2048 * 8192
    through = (6 * lp["conv"] + 2 * lp["full_attention"] + lp["mlp"]
               + 7 * (lp["route"] + lp["experts"]) + lp["head"])
    assert through == pytest.approx(244.8e6, rel=1e-3)
    shapes = [(2048, 8192), (8192, 2048), (2048, 6144)]
    w = lm_lfm2.work(config, traffic, 1, shapes)
    attn = 3 * 2 * 2 * 4096 * 64 * 32
    assert w["flops_per_example"] == pytest.approx(6 * through + 2 * attn)
    assert w["flops_per_example"] == pytest.approx(1.67e9, rel=5e-3)
    assert w["flops"] == w["flops_per_example"] * 16384
    assert w["keys_per_example"] == 1 and w["tokens"] == 16384
    # the dense parameters: everything but the token vectors
    assert w["bytes"] == pytest.approx(
        (2048 * 8192 + 2048 * 6144) * 4 * 6 + w["rows"] * 2056 * 12
        + 16384 * 8)
    assert set(w["scopes"]) == {"pbox.attn", "pbox.moe_experts",
                                "pbox.conv_mix"}
    assert w["scopes"]["pbox.attn"]["flops"] == pytest.approx(
        2 * (6 * lp["full_attention"] + attn) * 16384)
    assert w["scopes"]["pbox.attn"]["bytes"] == pytest.approx(
        2 * (12 * lp["full_attention"]
             + 12 * 16384 * (2 * 2048 + 64 * (32 + 16))))
    assert w["scopes"]["pbox.moe_experts"]["flops"] == pytest.approx(
        7 * 6 * lp["experts"] * 16384)
    # three matrices an expert, eight experts, seven layers, three times;
    # half a token-choice a token, its row in and out, three times
    assert w["scopes"]["pbox.moe_experts"]["bytes"] == pytest.approx(
        7 * (3 * 8 * 3 * 2048 * 1536 * 4 + 3 * 0.5 * 16384 * 2 * 2048 * 4))
    assert w["scopes"]["pbox.conv_mix"] == {
        "flops": 0.0, "bytes": 3.0 * 6 * 16384 * 4 * 2048 * 4}
    # distinct rows of a step under Zipf(1): a few thousand of 8,191
    assert 2000 < w["rows"] < 6000
    # a second size: half the tokens, an attention-only stack of two
    # expert layers
    small = dict(config, layer_types=["full_attention"] * 2,
                 num_dense_layers=0)
    w2 = lm_lfm2.work(small, dict(traffic, batch_per_chip=8192), 1, shapes)
    assert w2["flops_per_example"] == pytest.approx(
        6 * (2 * (lp["full_attention"] + lp["route"] + lp["experts"])
             + lp["head"]) + 2 * attn)
    assert w2["flops"] == w2["flops_per_example"] * 8192
    assert w2["scopes"]["pbox.conv_mix"]["bytes"] == 0


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


def test_traced_run_reports_exactly_the_cells_metrics(toy, monkeypatch):
    """``--trace 1`` with the profiler patched out by a hand-made plane
    under the step's own scopes: exactly the metrics that list the cell
    read, the scope readers sum to the step, every share of a roofline
    or of the peak lies in (0, 100)."""
    scopes = ["pbox.decode", "pbox.dedup", "pbox.pull", "pbox.conv_proj",
              "pbox.conv_mix", "pbox.conv_mix.bwd", "pbox.attn",
              "pbox.attn.bwd", "pbox.mlp", "pbox.moe_route",
              "pbox.moe_experts", "pbox.moe_experts.bwd", "pbox.head",
              "pbox.loss", "pbox.push", "pbox.dense_opt"]
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
    listed = {m["name"] for m in bench()["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(res["metrics"]) == listed
    assert {"step.conv_ms", "step.dense_mlp_ms", "kernels.conv_mix_roofline",
            "step.attn_ms", "step.moe_ms", "step.head_loss_ms",
            "moe.load_imbalance", "kernels.attn_roofline",
            "kernels.moe_experts_roofline", "step.wide_pull_ms",
            "step.wide_push_ms"} <= listed
    assert not {"step.ssm_ms", "kernels.ssm_scan_roofline"} & listed
    val = {k: v["value"] for k, v in res["metrics"].items()}
    assert val["step.conv_ms"] == pytest.approx(3 * 25.0)
    assert val["step.dense_mlp_ms"] == pytest.approx(25.0)
    assert val["step.attn_ms"] == pytest.approx(2 * 25.0)
    assert val["step.moe_ms"] == pytest.approx(3 * 25.0)
    assert val["step.head_loss_ms"] == pytest.approx(2 * 25.0)
    assert val["step.wide_pull_ms"] == pytest.approx(25.0)
    assert val["step.wide_push_ms"] == pytest.approx(25.0)
    assert val["step.ms_per_batch"] == pytest.approx(len(scopes) * 25.0)
    for name in listed:
        if "roofline" in name or "mfu" in name:
            assert 0 < val[name] < 100, name
    assert val["moe.load_imbalance"] >= 1.0
    assert val["entry.compiles_in_window"] == 0


def test_the_new_readers_are_silent_where_the_program_has_no_such_scope():
    """On the parent's program (no ``pbox.conv_*``, no ``pbox.mlp``) the
    three readers return nothing and do not raise."""
    red = {"batches": 4, "scopes": {"pbox.ssm_scan": 1.0, "other": 0.5}}
    ctx = {"trace": red, "work": {"scopes": {}},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    names = ["step.conv_ms", "step.dense_mlp_ms", "kernels.conv_mix_roofline"]
    assert harness.read_layer_metrics(names, ctx) == {}
    assert harness.read_layer_metrics(names, dict(ctx, trace=None)) == {}


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
