"""Family ``lm_mellum`` (``families/lm_mellum.py`` over ``families/lm.py``,
``entries/resident_seq.py``, ``reference/lm.py`` +
``reference/models/mellum.py``) through the harness and ``study.py`` on
the CPU at a toy size, as ``test_family_lm_lfm2.py`` does for family
``lm_lfm2``: sound runs come out correct, the control and the faults that
bite do not; ``work`` against hand counts."""

import copy
import json
import math
import time

import pytest

from benchmarks import compare, harness, study, tracered
from benchmarks.families import lm, lm_mellum
from benchmarks.tests.test_benchmarks import bench
from benchmarks.tests.test_families import PEAKS

CELL = "mellum2-12b-a2.5b.train-packed-8k"

#: limits of the toy cell, from its own readings on the CPU: the program
#: (bfloat16 operands) against the reference with the same roundings
#: written out, seeds 5..10 and 2^31+5, reads early_embed 0.0024..0.0035,
#: early_g2sum 0.0023..0.0030, dparam 0.0005..0.0015, loss 0.3e-5..2.2e-5;
#: the float8 control (operands float8, cotangents kept), seeds 3, 5 and
#: 2^31+4: 0.0299..0.0329, 0.0064..0.0116, 0.0181..0.0226 and
#: 1.3e-4..2.4e-4; a state left unchanged reads 1 on the three first.
#: Each limit is the geometric middle of its two readings.
TOY_LIMITS = {"loss": 5.3e-5, "dparam": 0.0052, "rows_count": 0.0,
              "early_embed": 0.0102, "early_g2sum": 0.0044}

#: a toy YaRN group whose ramp has an inside: low 1, high 5 of 8 pairs
TOY_YARN = {"rope_type": "yarn", "rope_theta": 100, "factor": 4,
            "original_max_position_embeddings": 64, "beta_fast": 4,
            "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1}


def toy_config(pattern: str = "SSSF", **over) -> dict:
    """The keys a toy stack of ``pattern`` (``S`` sliding, ``F`` full)
    changes in the cell's configuration."""
    kinds = {"S": "sliding_attention", "F": "full_attention"}
    cfg = dict(
        hidden_size=64, vocab_size=96, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_intermediate_size=48,
        router_outputs=16, num_experts=4, first_expert_held=0,
        num_experts_per_tok=3, num_hidden_layers=len(pattern),
        layer_types=[kinds[c] for c in pattern], layer_pattern=pattern,
        mlp_layer_types=["sparse"] * len(pattern), sliding_window=7,
        rope_parameters={
            "full_attention": dict(TOY_YARN),
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 100}},
        rope_theta=100, yarn_factor=4,
        yarn_original_max_position_embeddings=64, yarn_beta_fast=4,
        yarn_beta_slow=1, yarn_attention_factor=TOY_YARN["attention_factor"],
        table_rows_per_chip=96)
    cfg.update(over)
    return cfg


def toy_cell():
    toy = copy.deepcopy(harness.load_cell(CELL))
    toy["config"].update(toy_config())
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
        assert getattr(lm_mellum, name) is getattr(lm, name), name
    assert lm_mellum.work is not lm.work
    assert lm_mellum.layer_params is not lm.layer_params


def test_configuration_keeps_the_published_widths():
    """Every number of the catalog's row under its own key, but the five
    that ``reduced`` names; the cut is the issue's."""
    config = harness.load_cell(CELL)["config"]
    entry = [c for c in bench()["configs"] if c["name"] == config["name"]][0]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_experts", "vocab_size"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert entry["source"] == config["source"]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "sliding_window": 1024,
        "tie_word_embeddings": False, "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}}}
    for key, value in published.items():
        assert config[key] == value, key
    # the top-level copies the plain reference reads are the nested numbers
    full = config["rope_parameters"]["full_attention"]
    assert config["rope_theta"] == full["rope_theta"] == \
        config["rope_parameters"]["sliding_attention"]["rope_theta"]
    for key in ("factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor"):
        assert config["yarn_" + key] == full[key], key
        assert "yarn_" + key in config["assumed"]
    assert config["yarn_attention_factor"] == pytest.approx(
        0.1 * math.log(16) + 1, rel=1e-12)
    assert config["router_outputs"] == config["published"]["num_experts"] \
        == 64
    assert (config["num_experts"], config["first_expert_held"]) == (8, 0)
    kinds = config["published"]["layer_types"]
    assert len(kinds) == 28 and kinds[:4] * 7 == kinds
    assert kinds[:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert config["layer_types"] == kinds[:8]
    assert config["layer_pattern"] == "".join(
        k[0].upper() for k in config["layer_types"]) == "SSSFSSSF"
    assert config["mlp_layer_types"] == \
        config["published"]["mlp_layer_types"][:8] == ["sparse"] * 8
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 8
    assert config["vocab_size"] * config["vocabulary_parallel"] == \
        config["published"]["vocab_size"] == 98304
    assert config["table_rows_per_chip"] == config["vocab_size"] == 12288
    for key in ("qk_norm", "router", "multi_token_prediction",
                "max_window_layers", "sliding_window", "auxiliary_loss",
                "router_bias", "rotary_positions", "dense_optimizer",
                "embedding_rule", "weights", "row_bytes", "layer_pattern",
                "rope_theta"):
        assert config["assumed"][key], key


# ---- the step's work from shapes -------------------------------------------

def test_work_counts_the_windows_pairs_in_the_sliding_layers():
    cell = harness.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    lp = lm_mellum.layer_params(config)
    # the issue's own arithmetic: 21.23M attention, 6.19M an expert of
    # which a token passes through 8 x 8 / 64 = one
    assert lp["attention"] == 2304 * 128 * 72 + 256 == 21_233_920
    assert lp["route"] == 2304 * 64 == 147_456
    assert lp["experts"] == pytest.approx(3 * 2304 * 896) == 6_193_152
    assert lp["head"] == 2304 * 12288
    through = 8 * (lp["attention"] + lp["route"] + lp["experts"]) + lp["head"]
    assert through == pytest.approx(248.9e6, rel=1e-3)
    # a query of a sliding layer reads min(t + 1, 1024) keys
    keys = sum(min(p + 1, 1024) for p in range(8192)) / 8192
    assert lm_mellum.keys_per_query(8192, 1024) == keys == 960.0625
    assert lm_mellum.keys_per_query(24, 7) == (28 + 17 * 7) / 24
    assert lm_mellum.keys_per_query(16, 1024) == 8.5      # the causal half
    shapes = [(2304, 12288), (12288, 2304), (2304, 4096)]
    w = lm_mellum.work(config, traffic, 1, shapes)
    full = 3 * 2 * 2 * 4096 * 128 * 32          # the causal half, a layer
    window = 3 * 2 * 2 * keys * 128 * 32        # the windows' pairs
    assert w["flops_per_example"] == pytest.approx(
        6 * through + 2 * full + 6 * window)
    assert 2 * full == pytest.approx(403e6, rel=2e-3)
    assert 6 * window == pytest.approx(283e6, rel=2e-3)
    assert w["flops_per_example"] == pytest.approx(2.179e9, rel=1e-3)
    assert w["flops"] == w["flops_per_example"] * 16384
    assert w["flops"] == pytest.approx(35.7e12, rel=2e-3)
    assert w["keys_per_example"] == 1 and w["tokens"] == 16384
    # the dense parameters: everything but the token vectors
    assert w["bytes"] == pytest.approx(
        (2304 * 12288 + 2304 * 4096) * 4 * 6 + w["rows"] * 2312 * 12
        + 16384 * 8)
    assert set(w["scopes"]) == {"pbox.attn", "pbox.attn_window",
                                "pbox.moe_experts"}
    # pbox.attn is the 2 full layers alone, pbox.attn_window the 6 sliding
    assert w["scopes"]["pbox.attn"]["flops"] == pytest.approx(
        2 * (6 * lp["attention"] + full) * 16384)
    assert w["scopes"]["pbox.attn_window"]["flops"] == pytest.approx(
        6 * (6 * lp["attention"] + window) * 16384)
    per_layer = 12 * lp["attention"] + 12 * 16384 * (2 * 2304 + 128 * 40)
    assert w["scopes"]["pbox.attn"]["bytes"] == pytest.approx(2 * per_layer)
    assert w["scopes"]["pbox.attn_window"]["bytes"] == pytest.approx(
        6 * per_layer)
    assert w["scopes"]["pbox.moe_experts"]["flops"] == pytest.approx(
        8 * 6 * lp["experts"] * 16384)
    # three matrices an expert, eight experts, eight layers, three times;
    # one token-choice a token, its row in and out, three times
    assert w["scopes"]["pbox.moe_experts"]["bytes"] == pytest.approx(
        8 * (3 * 8 * 3 * 2304 * 896 * 4 + 3 * 1.0 * 16384 * 2 * 2304 * 4))
    # the sliding layers are about half the count, the full ones a third
    total = w["flops"]
    assert w["scopes"]["pbox.attn_window"]["flops"] / total == \
        pytest.approx(0.48, abs=0.01)
    assert w["scopes"]["pbox.attn"]["flops"] / total == \
        pytest.approx(0.30, abs=0.01)
    # distinct rows of a step under Zipf(1): a few thousand of 12,287
    assert 2000 < w["rows"] < 7000
    # a second size: half the tokens, full layers only
    small = dict(config, layer_types=["full_attention"] * 2)
    w2 = lm_mellum.work(small, dict(traffic, batch_per_chip=8192), 1, shapes)
    assert w2["flops_per_example"] == pytest.approx(
        6 * (2 * (lp["attention"] + lp["route"] + lp["experts"])
             + lp["head"]) + 2 * full)
    assert w2["flops"] == w2["flops_per_example"] * 8192
    assert w2["scopes"]["pbox.attn_window"] == {"flops": 0.0, "bytes": 0.0}


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


NEW_READERS = ["step.attn_window_ms", "kernels.attn_window_roofline",
               "moe.held_share", "moe.rows_per_choice"]


def test_traced_run_reports_exactly_the_cells_metrics(toy, monkeypatch):
    """``--trace 1`` with the profiler patched out by a hand-made plane
    under the step's own scopes: exactly the metrics that list the cell
    read, the scope readers sum to the step, every share of a roofline
    or of the peak lies in (0, 100)."""
    scopes = ["pbox.decode", "pbox.dedup", "pbox.pull", "pbox.attn",
              "pbox.attn.bwd", "pbox.attn_window", "pbox.attn_window.bwd",
              "pbox.moe_route", "pbox.moe_experts", "pbox.moe_experts.bwd",
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
    listed = {m["name"] for m in bench()["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(res["metrics"]) == listed
    assert set(NEW_READERS) | {
        "step.attn_ms", "step.moe_ms", "step.head_loss_ms",
        "moe.load_imbalance", "kernels.attn_roofline",
        "kernels.moe_experts_roofline", "step.wide_pull_ms",
        "step.wide_push_ms"} <= listed
    assert not {"step.ssm_ms", "kernels.ssm_scan_roofline", "step.conv_ms",
                "step.dense_mlp_ms", "kernels.conv_mix_roofline"} & listed
    val = {k: v["value"] for k, v in res["metrics"].items()}
    # a sliding layer's time is read apart from a full layer's
    assert val["step.attn_ms"] == pytest.approx(2 * 25.0)
    assert val["step.attn_window_ms"] == pytest.approx(2 * 25.0)
    assert val["step.moe_ms"] == pytest.approx(3 * 25.0)
    assert val["step.head_loss_ms"] == pytest.approx(2 * 25.0)
    assert val["step.wide_pull_ms"] == pytest.approx(25.0)
    assert val["step.wide_push_ms"] == pytest.approx(25.0)
    assert val["step.ms_per_batch"] == pytest.approx(len(scopes) * 25.0)
    for name in listed:
        if "roofline" in name or "mfu" in name:
            assert 0 < val[name] < 100, name
    assert val["moe.load_imbalance"] >= 1.0
    # the held experts' part of every choice the routers made
    assert 0 < val["moe.held_share"] <= 100
    assert val["moe.rows_per_choice"] >= 1.0
    assert val["entry.compiles_in_window"] == 0


def test_the_new_readers_are_silent_where_the_program_has_no_such_scope():
    """On the parent's program (no ``pbox.attn_window``, no
    ``moe_choices`` counter on its spans) the four readers return
    nothing and do not raise."""
    red = {"batches": 4, "scopes": {"pbox.attn": 1.0, "other": 0.5}}
    ctx = {"trace": red, "work": {"scopes": {}}, "window": {},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert harness.read_layer_metrics(NEW_READERS, ctx) == {}
    assert harness.read_layer_metrics(NEW_READERS,
                                      dict(ctx, trace=None)) == {}


def test_the_counter_readers_are_silent_on_spans_without_the_counters(
        monkeypatch):
    """A window whose ``pass.finish`` spans carry the expert counters but
    not every choice's (cells 2 and 3): ``moe.rows_per_choice`` reads,
    ``moe.held_share`` returns nothing; with it, the share in percent."""
    from benchmarks import span_counters, span_window as sw
    from paddlebox_tpu.obs.trace import SpanRecord

    def rec(name, t0, dur, **attrs):
        return SpanRecord(name=name, lane=sw.LANE, pass_seq=None, span_id=0,
                          parent_id=0, link_from=0, t0_ns=t0, dur_ns=dur,
                          attrs=attrs)
    spans = []
    for i in range(3):
        t0 = i * 10 ** 9
        spans += [rec("pass.train", t0, 8 * 10 ** 8),
                  rec("pass.finish", t0 + 7 * 10 ** 8, 10 ** 7,
                      moe_rows_computed=1024.0 * (i + 1),
                      moe_choices_held=800.0)]
    monkeypatch.setattr(sw, "ring", lambda: spans)
    ctx = {"window": {"train_s": [0.8, 0.8]}}
    assert span_counters.ratio(ctx, "moe_rows_computed",
                               "moe_choices_held") == pytest.approx(
        (2048 + 3072) / 1600)
    assert span_counters.ratio(ctx, "moe_choices_held",
                               "moe_choices") is None
    both = ["moe.held_share", "moe.rows_per_choice"]
    assert harness.read_layer_metrics(both, ctx) == {
        "moe.rows_per_choice": pytest.approx(3.2)}
    for r in spans:
        if r.name == "pass.finish":
            r.attrs["moe_choices"] = 6400.0
    assert harness.read_layer_metrics(both, ctx) == {
        "moe.held_share": pytest.approx(12.5),
        "moe.rows_per_choice": pytest.approx(3.2)}


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
