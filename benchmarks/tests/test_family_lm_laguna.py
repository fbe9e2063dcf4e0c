"""Family ``lm_laguna`` (``families/lm_laguna.py`` over ``families/lm.py``,
``entries/resident_seq.py``, ``reference/lm.py`` +
``reference/models/laguna.py``) through the harness and ``study.py`` on
the CPU at a toy size, as ``test_family_lm_kimi_linear.py`` does for
family ``lm_kimi_linear``: sound runs come out correct, the control and
the faults that bite do not; ``work`` against hand counts; the
configuration against the catalog's row."""

import copy
import json
import math
import time

import numpy as np
import pytest

from benchmarks import compare, harness, study, tracered
from benchmarks.families import lm, lm_laguna
from benchmarks.tests.test_benchmarks import bench
from benchmarks.tests.test_families import PEAKS

CELL = "laguna-s-2.1.train-packed-8k-1seq"

#: the published config.json's numbers and strings (the configuration's
#: ``source``), every one of which the cut keeps under its own key but
#: the three scalars ``reduced`` names; the per-layer lists follow
#: ``PATTERN`` over 48 layers
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512, "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}}
#: a published layer kind by ``F`` (full_attention, 48 query heads) and
#: ``S`` (sliding_attention, 72): layer i is ``PATTERN[i % 4]``
PATTERN = "FSSS"

#: limits of the toy cell (a full layer with the dense feed-forward, a
#: sliding one with the experts), from its own readings on the CPU: the
#: program (bfloat16 operands) against the reference with the same
#: roundings written out, seeds 5..10 and 2^31+5, reads loss
#: 1.5e-6..1.6e-5, dparam 0.00055..0.0067, early_embed 0.0021..0.0033,
#: early_g2sum 0.0020..0.0028; the float8 control (operands float8,
#: cotangents kept), seeds 3, 5 and 2^31+4: 1.9e-4..3.6e-4,
#: 0.0080..0.0115, 0.0303..0.0341 and 0.0086..0.0092; a state left
#: unchanged reads 1 on the three last; dropping the choices over an
#: expert's even share reads dparam 0.0105..0.0327. Each limit is the
#: geometric middle of the sound side's largest and the control's
#: smallest.
TOY_LIMITS = {"loss": 5.5e-5, "dparam": 0.0073, "rows_count": 0.0,
              "early_embed": 0.01, "early_g2sum": 0.0049}

#: a toy YaRN group for heads of 16 turned on their first 8: low 0, high
#: 3 of 4 pairs
TOY_YARN = {"rope_type": "yarn", "rope_theta": 100, "factor": 4,
            "original_max_position_embeddings": 64, "beta_fast": 4,
            "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1,
            "partial_rotary_factor": 0.5}


def toy_config(pattern: str = "FS", heads=(4, 6), mlp: str = "DE",
               **over) -> dict:
    """The keys a toy stack changes in the cell's configuration: layer
    kinds ``pattern`` (``F`` full, ``S`` sliding), query heads a layer
    ``heads`` over 2 key/value heads, feed-forwards ``mlp`` (``D`` dense,
    ``E`` experts)."""
    kinds = {"S": "sliding_attention", "F": "full_attention"}
    mlps = {"D": "dense", "E": "sparse"}
    cfg = dict(
        hidden_size=64, vocab_size=96, num_key_value_heads=2, head_dim=16,
        num_attention_heads=heads[0],
        num_attention_heads_per_layer=list(heads),
        layer_heads=",".join(str(h) for h in heads),
        intermediate_size=80, moe_intermediate_size=24,
        shared_expert_intermediate_size=24, router_outputs=16,
        num_experts=4, first_expert_held=0, num_experts_per_tok=3,
        num_hidden_layers=len(pattern),
        layer_types=[kinds[c] for c in pattern], layer_pattern=pattern,
        mlp_layer_types=[mlps[c] for c in mlp], mlp_layer_pattern=mlp,
        sliding_window=7,
        rope_parameters={
            "full_attention": dict(TOY_YARN),
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 50,
                                  "partial_rotary_factor": 1}},
        sliding_rope_theta=50, yarn_rope_theta=100, yarn_factor=4,
        yarn_original_max_position_embeddings=64, yarn_beta_fast=4,
        yarn_beta_slow=1, yarn_attention_factor=TOY_YARN["attention_factor"],
        yarn_partial_rotary_factor=0.5, table_rows_per_chip=96)
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
        assert getattr(lm_laguna, name) is getattr(lm, name), name
    assert lm_laguna.work is not lm.work
    assert lm_laguna.layer_params is not lm.layer_params


def test_configuration_keeps_the_published_widths():
    """Every number and string of the published config under its own
    key, the nested ``rope_parameters`` whole, but the six that
    ``reduced`` names, which ``published`` keeps; the cut (layers 0-4,
    experts 0..7 of 256, ids 0..12,543), the deployment and the
    assumptions."""
    config = harness.load_cell(CELL)["config"]
    entry = [c for c in bench()["configs"] if c["name"] == config["name"]][0]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    published = config["published"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert published[key] == value, key
        else:
            assert config[key] == value, key
    kinds = [{"F": "full_attention", "S": "sliding_attention"}[
        PATTERN[i % 4]] for i in range(48)]
    assert published["layer_types"] == kinds
    assert published["num_attention_heads_per_layer"] == [
        48 if k == "full_attention" else 72 for k in kinds]
    assert published["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert config["gating_types"] == ["per_head"] * 48
    assert PUBLISHED["rope_parameters"]["full_attention"][
        "attention_factor"] == pytest.approx(0.1 * math.log(128) + 1,
                                             rel=1e-12)
    # the top-level copies the plain reference reads are the nested numbers
    full = config["rope_parameters"]["full_attention"]
    sliding = config["rope_parameters"]["sliding_attention"]
    assert config["sliding_rope_theta"] == sliding["rope_theta"]
    assert config["yarn_rope_theta"] == full["rope_theta"]
    for key in ("factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor", "partial_rotary_factor"):
        assert config["yarn_" + key] == full[key], key
        assert "yarn_" + key in config["assumed"]
    # the cut: the published lists' first 5, the dense lead and one period
    assert config["num_hidden_layers"] == 5
    assert config["layer_types"] == kinds[:5]
    assert config["layer_pattern"] == "".join(
        k[0].upper() for k in config["layer_types"]) == "FSSSF"
    assert config["mlp_layer_types"] == published["mlp_layer_types"][:5] \
        == ["dense"] + ["sparse"] * 4
    assert config["mlp_layer_pattern"] == "DEEEE"
    assert config["num_attention_heads_per_layer"] == \
        published["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert config["layer_heads"] == "48,72,72,72,48"
    assert (config["num_experts"], config["first_expert_held"],
            config["router_outputs"], config["chips_per_layer"]) == \
        (8, 0, 256, 32)
    assert config["router_outputs"] == config["chips_per_layer"] \
        * config["num_experts"] == published["num_experts"]
    assert config["vocab_size"] * config["vocabulary_parallel"] == \
        published["vocab_size"] == 100352
    assert config["table_rows_per_chip"] == config["vocab_size"] == 12544
    assert "32 chips a layer" in config["deployment"]
    for key in ("router", "gate", "shared_expert", "qk_norm",
                "partial_rotary", "sliding_window", "rotary_positions",
                "dense_optimizer", "token_vectors", "weights", "row_bytes",
                "layer_pattern", "mlp_layer_pattern", "layer_heads",
                "gating_types"):
        assert config["assumed"][key], key
    assert config["dense_optimizer"]["learning_rate"] == 1e-6
    assert "bfloat16 operands" in config["precision"]


# ---- the step's work from shapes -------------------------------------------

def test_work_counts_772m_parameters_and_the_windows_pairs():
    """The cut's arithmetic: 772.4M dense parameters; 3.662 GFLOP a
    token, 30.0 TFLOP a step, sliding attention 35.5%, full 31.0%, the
    dense layer 18.6%, experts and router 8.6%, the head 6.3%."""
    cell = harness.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    lp = lm_laguna.layer_params(config)
    assert lp["full"] + lp["gate_full"] == pytest.approx(44.19e6, rel=1e-3)
    assert lp["sliding"] + lp["gate_sliding"] == pytest.approx(63.14e6,
                                                               rel=1e-3)
    assert lp["gate_full"] == 3072 * 48 and lp["gate_sliding"] == 3072 * 72
    assert lp["mlp"] == 3 * 3072 * 12288
    assert lp["route"] == 3072 * 256
    assert lp["shared"] == 3 * 3072 * 1024
    # a token passes through 10 x 8 / 256 of an expert's 9.44M
    assert lp["experts"] == pytest.approx(10 * 8 / 256 * 3 * 3072 * 1024)
    assert lp["head"] == 3072 * 12544
    # the parameters this chip holds, from the model's own shapes
    import jax
    from paddlebox_tpu.models import LagunaMoe
    shapes = [tuple(s.shape) for s in jax.tree.leaves(jax.eval_shape(
        LagunaMoe(config).init, jax.random.PRNGKey(0)))]
    n = sum(int(np.prod(s)) for s in shapes)
    assert n == pytest.approx(772.4e6, rel=1e-3)
    layer = {"F": lp["full"] + lp["gate_full"],
             "S": lp["sliding"] + lp["gate_sliding"]}
    experts = 8 * 3 * 3072 * 1024
    assert n == sum(layer[k] for k in "FSSSF") + lp["mlp"] \
        + 4 * (lp["route"] + lp["shared"] + experts) + lp["head"] \
        + 3072 * (2 * 5 + 1)
    w = lm_laguna.work(config, traffic, 1, shapes + [(12544, 3072)])
    keys = sum(min(p + 1, 512) for p in range(8192)) / 8192
    assert lm_laguna.keys_per_query(8192, 512) == keys
    full = 3 * 2 * 2 * 4096 * 128 * 48          # the causal half, a layer
    window = 3 * 2 * 2 * keys * 128 * 72        # the windows' pairs
    moe = 6 * (lp["route"] + lp["shared"] + lp["experts"])
    parts = {"full": 2 * (6 * layer["F"] + full),
             "sliding": 3 * (6 * layer["S"] + window),
             "mlp": 6 * lp["mlp"], "moe": 4 * moe, "head": 6 * lp["head"]}
    assert w["flops_per_example"] == pytest.approx(sum(parts.values()))
    assert w["flops_per_example"] == pytest.approx(3.662e9, rel=1e-3)
    assert w["flops"] == w["flops_per_example"] * 8192
    assert w["flops"] == pytest.approx(30.0e12, rel=2e-3)
    for part, share in (("sliding", 0.355), ("full", 0.310), ("mlp", 0.186),
                        ("moe", 0.086), ("head", 0.063)):
        assert parts[part] / w["flops_per_example"] == pytest.approx(
            share, abs=0.001), part
    assert w["keys_per_example"] == 1 and w["tokens"] == 8192
    # the dense parameters: everything but the token vectors
    assert w["bytes"] == pytest.approx(
        n * 4 * 6 + w["rows"] * 3080 * 12 + 8192 * 8)
    assert set(w["scopes"]) == {"pbox.attn", "pbox.attn_window", "pbox.mlp",
                                "pbox.moe_experts"}
    # each attention scope counts its layers without their gates (the
    # gates' time is under pbox.attn_gate)
    assert w["scopes"]["pbox.attn"]["flops"] == pytest.approx(
        2 * (6 * lp["full"] + full) * 8192)
    assert w["scopes"]["pbox.attn_window"]["flops"] == pytest.approx(
        3 * (6 * lp["sliding"] + window) * 8192)
    assert w["scopes"]["pbox.attn_window"]["bytes"] == pytest.approx(
        3 * (12 * lp["sliding"] + 12 * 8192 * (2 * 3072 + 128 * 88)))
    assert w["scopes"]["pbox.mlp"]["flops"] == pytest.approx(
        6 * lp["mlp"] * 8192)
    assert w["scopes"]["pbox.moe_experts"]["flops"] == pytest.approx(
        4 * 6 * lp["experts"] * 8192)


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


NEW_READERS = ["step.attn_gate_ms"]


def test_traced_run_reports_exactly_the_cells_metrics(toy, monkeypatch):
    """``--trace 1`` with the profiler patched out by a hand-made plane
    under the step's own scopes: exactly the metrics that list the cell
    read, the scope readers sum to the step, every share of a roofline
    or of the peak lies in (0, 100)."""
    scopes = ["pbox.decode", "pbox.dedup", "pbox.pull", "pbox.attn",
              "pbox.attn.bwd", "pbox.attn_window", "pbox.attn_window.bwd",
              "pbox.attn_gate", "pbox.attn_gate.bwd", "pbox.mlp",
              "pbox.moe_route", "pbox.moe_experts", "pbox.moe_experts.bwd",
              "pbox.moe_shared", "pbox.head", "pbox.loss", "pbox.push",
              "pbox.dense_opt"]
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
        "step.attn_ms", "step.attn_window_ms", "kernels.attn_roofline",
        "kernels.attn_window_roofline", "step.moe_ms",
        "kernels.moe_experts_roofline", "moe.load_imbalance",
        "moe.held_share", "moe.rows_per_choice", "step.dense_mlp_ms",
        "kernels.mlp_roofline", "step.head_loss_ms", "step.wide_pull_ms",
        "step.wide_push_ms"} <= listed
    assert not {"step.ssm_ms", "kernels.ssm_scan_roofline", "step.conv_ms",
                "kernels.conv_mix_roofline", "step.exit_gate_ms",
                "loop.expected_exit_step", "step.kda_ms",
                "kernels.kda_scan_roofline"} & listed
    val = {k: v["value"] for k, v in res["metrics"].items()}
    assert val["step.attn_gate_ms"] == pytest.approx(2 * 25.0)
    assert val["step.attn_ms"] == pytest.approx(2 * 25.0)
    assert val["step.attn_window_ms"] == pytest.approx(2 * 25.0)
    assert val["step.dense_mlp_ms"] == pytest.approx(25.0)
    assert val["step.moe_ms"] == pytest.approx(4 * 25.0)
    assert val["step.head_loss_ms"] == pytest.approx(2 * 25.0)
    assert val["step.ms_per_batch"] == pytest.approx(len(scopes) * 25.0)
    for name in listed:
        if "roofline" in name or "mfu" in name:
            assert 0 < val[name] < 100, name
    assert val["moe.load_imbalance"] >= 1.0
    assert 0 < val["moe.held_share"] <= 100
    assert val["moe.rows_per_choice"] >= 1.0
    assert val["entry.compiles_in_window"] == 0


def test_the_new_reader_is_silent_where_the_program_has_no_such_scope():
    """On the parent's programs (no ``pbox.attn_gate`` scope) the reader
    returns nothing and does not raise."""
    red = {"batches": 4, "scopes": {"pbox.attn": 1.0, "other": 0.5}}
    ctx = {"trace": red, "work": {"scopes": {}}, "window": {},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert harness.read_layer_metrics(NEW_READERS, ctx) == {}
    assert harness.read_layer_metrics(NEW_READERS,
                                      dict(ctx, trace=None)) == {}
    red["scopes"]["pbox.attn_gate"] = 0.2
    assert harness.read_layer_metrics(NEW_READERS, ctx) == {
        "step.attn_gate_ms": pytest.approx(50.0)}


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
    got = list(study.stand_in_readings(toy, [2 ** 31 + 4]))
    assert [run for _, run, _ in got] == [
        "control:float8_e4m3fn", "fault:state_unchanged",
        "fault:experts_dropped"]
    for seed, run, numbers in got:
        ok, _ = compare.judge(numbers, toy["limits"])
        assert not ok, (seed, run, numbers)
        assert numbers["rows_count"] == 0 or run == "fault:state_unchanged"
    for seed, run, numbers in study.program_readings(toy, [8]):
        ok, _ = compare.judge(numbers, toy["limits"])
        assert ok and run == "program", numbers
