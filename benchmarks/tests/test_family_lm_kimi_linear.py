"""Family ``lm_kimi_linear`` (``families/lm_kimi_linear.py`` over
``families/lm.py``, ``entries/resident_seq.py``, ``reference/lm.py`` +
``reference/models/kimi_linear.py``) through the harness and ``study.py``
on the CPU at a toy size, as ``test_family_lm_mellum.py`` does for family
``lm_mellum``: sound runs come out correct, the control and the faults
that bite do not; ``work`` against hand counts; the configuration against
the catalog's row."""

import copy
import json
import os
import time

import numpy as np
import pytest

from benchmarks import compare, harness, study, tracered
from benchmarks.families import lm, lm_kimi_linear
from benchmarks.tests.test_benchmarks import bench
from benchmarks.tests.test_families import PEAKS

CELL = "kimi-linear-48b-a3b.train-packed-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

#: limits of the toy cell, from its own readings on the CPU: the program
#: (bfloat16 operands) against the reference with the same roundings
#: written out but for the delta rule's (the reference runs it token by
#: token in float32), seeds 5..10 and 2^31+5, reads loss 2.8e-6..2.6e-5,
#: dparam 0.0019..0.0065, early_embed 0.0023..0.0034, early_g2sum
#: 0.0021..0.0032; the float8 control (operands float8, cotangents
#: kept), seeds 3, 5 and 2^31+4: 4.8e-5..3.7e-4, 0.019..0.036,
#: 0.032..0.034 and 0.0083..0.0117; a state left unchanged reads 1 on the
#: three last; dropping the choices over an expert's even share reads
#: dparam 0.047..0.082. Each limit is the geometric middle of the sound
#: side's largest and the control's smallest.
TOY_LIMITS = {"loss": 3.5e-5, "dparam": 0.011, "rows_count": 0.0,
              "early_embed": 0.0105, "early_g2sum": 0.0052}


def toy_config(pattern: str = "KKKMK", **over) -> dict:
    """The keys a toy stack of ``pattern`` (``K`` KDA, ``M`` latent
    attention; layer 0's feed-forward dense) changes in the cell's
    configuration."""
    base = harness.load_cell(CELL)["config"]["linear_attn_config"]
    kda = [i + 1 for i, c in enumerate(pattern) if c == "K"]
    full = [i + 1 for i, c in enumerate(pattern) if c == "M"]
    cfg = dict(
        hidden_size=64, vocab_size=96, num_attention_heads=4,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
        kv_lora_rank=24, intermediate_size=80, moe_intermediate_size=24,
        router_outputs=16, num_experts=4, first_expert_held=0,
        num_experts_per_token=3, num_hidden_layers=len(pattern),
        linear_attn_config=dict(base, num_heads=2, head_dim=16,
                                kda_layers=kda, full_attn_layers=full),
        layer_pattern=pattern, linear_attn_num_heads=2,
        linear_attn_head_dim=16, table_rows_per_chip=96)
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
        assert getattr(lm_kimi_linear, name) is getattr(lm, name), name
    assert lm_kimi_linear.work is not lm.work
    assert lm_kimi_linear.layer_params is not lm.layer_params


def test_configuration_keeps_the_published_widths():
    """Every number of the catalog's row under its own key, the nested
    ``linear_attn_config`` whole, but the three that ``reduced`` names;
    the cut, the deployment and the assumptions are the issue's."""
    config = harness.load_cell(CELL)["config"]
    entry = [c for c in bench()["configs"] if c["name"] == config["name"]][0]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert entry["source"] == config["source"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [json.loads(ln) for ln in f
                   if '"Kimi-Linear-48B-A3B-Instruct"' in ln][0]
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config["published"][key] == value, key
            else:
                assert config[key] == value, key
    assert config["published"] == {"num_hidden_layers": 27,
                                   "num_experts": 256,
                                   "vocab_size": 163840}
    # the widths the issue names
    lin = config["linear_attn_config"]
    assert (config["hidden_size"], lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (2304, 32, 128, 4)
    assert (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["kv_lora_rank"], config["q_lora_rank"],
            config["mla_use_nope"]) == (32, 128, 64, 128, 512, None, True)
    assert (config["intermediate_size"], config["moe_intermediate_size"],
            config["num_shared_experts"], config["num_experts_per_token"],
            config["routed_scaling_factor"]) == (9216, 1024, 1, 8, 2.446)
    # the cut: published layers 1..5, experts 0..7 of 256, ids 0..20479
    assert config["num_hidden_layers"] == 5
    assert config["layer_pattern"] == "KKKMK" == "".join(
        "K" if i in lin["kda_layers"] else "M" for i in range(1, 6))
    assert (config["num_experts"], config["first_expert_held"],
            config["router_outputs"], config["chips_per_layer"]) == \
        (8, 0, 256, 32)
    assert config["router_outputs"] == config["chips_per_layer"] \
        * config["num_experts"]
    assert config["vocab_size"] * config["vocabulary_parallel"] == 163840
    assert config["table_rows_per_chip"] == config["vocab_size"] == 20480
    for key in ("num_heads", "head_dim", "short_conv_kernel_size"):
        assert config["linear_attn_" + key] == lin[key], key
        assert config["assumed"]["linear_attn_" + key]
    assert "32 chips a layer" in config["deployment"]
    for key in ("document_states", "dense_optimizer", "token_vectors",
                "router_bias", "A_log", "dt_bias", "gate_bias", "head_dim",
                "layer_pattern"):
        assert config["assumed"][key], key
    assert config["dense_optimizer"]["learning_rate"] == 1e-6
    assert "bfloat16 operands" in config["precision"]


# ---- the step's work from shapes -------------------------------------------

def test_work_counts_555m_parameters_and_a_chunk_free_scan():
    cell = harness.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    lp = lm_kimi_linear.layer_params(config)
    assert lp["kda"] == pytest.approx(39.52e6, rel=1e-3)
    assert lp["mla"] == pytest.approx(29.12e6, rel=1e-3)
    assert lp["mlp"] == 3 * 2304 * 9216
    assert lp["route"] == 2304 * 256
    assert lp["shared"] == 3 * 2304 * 1024
    # a token passes through 8 x 8 / 256 of an expert's 7.08M
    assert lp["experts"] == pytest.approx(0.25 * 3 * 2304 * 1024)
    assert lp["head"] == 2304 * 20480
    # the parameters this chip holds, from the model's own shapes
    import jax
    from paddlebox_tpu.models import KimiLinear
    shapes = [tuple(s.shape) for s in jax.tree.leaves(jax.eval_shape(
        KimiLinear(config).init, jax.random.PRNGKey(0)))]
    n = sum(int(np.prod(s)) for s in shapes)
    assert n == pytest.approx(555.2e6, rel=1e-3)
    experts = 8 * 3 * 2304 * 1024
    assert n == pytest.approx(
        lp["kda"] * 4 + lp["mla"] + lp["mlp"]
        + 4 * (lp["route"] + 256 + lp["shared"] + experts) + lp["head"]
        + 2304 * (2 * 5 + 1), rel=1e-9)
    w = lm_kimi_linear.work(config, traffic, 1, shapes + [(20480, 2304)])
    through = 4 * lp["kda"] + lp["mla"] + lp["mlp"] \
        + 4 * (lp["route"] + lp["shared"] + lp["experts"]) + lp["head"]
    attn = 3 * 2 * 4096 * 32 * (192 + 128)
    scan = 3 * 4 * 6 * 128 * 128 * 32
    assert w["flops_per_example"] == pytest.approx(6 * through + attn + scan)
    assert w["flops"] == w["flops_per_example"] * 16384
    # the issue's 37 TFLOP a step, 188 ms at 197 TFLOP/s
    assert w["flops"] == pytest.approx(37.8e12, rel=5e-3)
    assert w["keys_per_example"] == 1 and w["tokens"] == 16384
    assert w["bytes"] == pytest.approx(
        n * 4 * 6 + w["rows"] * 2312 * 12 + 16384 * 8)
    assert set(w["scopes"]) == {"pbox.kda_scan", "pbox.attn", "pbox.mlp",
                                "pbox.moe_experts"}
    sc = w["scopes"]["pbox.kda_scan"]
    assert sc["flops"] == pytest.approx(scan * 16384)
    # q, k, g (128 a head), v and o (128), beta (1): 32 heads, 4 layers,
    # forward and twice as cotangents, float32
    assert sc["bytes"] == pytest.approx(3 * 4 * 16384 * 4 * 32 * (5 * 128 + 1))
    # memory-bound: its bytes take ten times its operations' least time
    assert sc["bytes"] / 819e9 > 5 * sc["flops"] / 197e12
    assert w["scopes"]["pbox.attn"]["flops"] == pytest.approx(
        (6 * lp["mla"] * 16384 + attn * 16384))
    assert w["scopes"]["pbox.mlp"]["flops"] == pytest.approx(
        6 * lp["mlp"] * 16384)
    assert w["scopes"]["pbox.moe_experts"]["flops"] == pytest.approx(
        4 * 6 * lp["experts"] * 16384)
    # the KDA layers' projections and rule: 43% of the count, the latent
    # layer's causal half 11%
    kda_share = (6 * 4 * lp["kda"] + scan) / w["flops_per_example"]
    assert kda_share == pytest.approx(0.428, abs=0.002)
    assert attn / w["flops_per_example"] == pytest.approx(0.109, abs=0.002)
    # the count of the rule has no chunk in it: a configuration or model
    # constant of another chunk size moves nothing
    from paddlebox_tpu.models import kimi_linear
    w2 = lm_kimi_linear.work(dict(config, chunk_size=128), traffic, 1,
                             shapes)
    assert w2["scopes"]["pbox.kda_scan"] == sc
    assert kimi_linear.KDA_CHUNK == 64


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


NEW_READERS = ["step.kda_ms", "kernels.kda_scan_roofline"]


def test_traced_run_reports_exactly_the_cells_metrics(toy, monkeypatch):
    """``--trace 1`` with the profiler patched out by a hand-made plane
    under the step's own scopes: exactly the metrics that list the cell
    read, the scope readers sum to the step, every share of a roofline
    or of the peak lies in (0, 100)."""
    scopes = ["pbox.decode", "pbox.dedup", "pbox.pull", "pbox.kda_proj",
              "pbox.kda_conv", "pbox.kda_scan", "pbox.kda_scan.bwd",
              "pbox.attn", "pbox.attn.bwd", "pbox.mlp", "pbox.moe_route",
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
    listed = {m["name"] for m in bench()["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(res["metrics"]) == listed
    assert set(NEW_READERS) | {
        "step.attn_ms", "kernels.attn_roofline", "step.moe_ms",
        "kernels.moe_experts_roofline", "moe.load_imbalance",
        "moe.held_share", "moe.rows_per_choice", "step.dense_mlp_ms",
        "kernels.mlp_roofline", "step.head_loss_ms", "step.wide_pull_ms",
        "step.wide_push_ms"} <= listed
    assert not {"step.ssm_ms", "kernels.ssm_scan_roofline", "step.conv_ms",
                "kernels.conv_mix_roofline", "step.attn_window_ms",
                "kernels.attn_window_roofline", "step.exit_gate_ms",
                "loop.expected_exit_step"} & listed
    val = {k: v["value"] for k, v in res["metrics"].items()}
    assert val["step.kda_ms"] == pytest.approx(4 * 25.0)
    assert val["step.attn_ms"] == pytest.approx(2 * 25.0)
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


def test_the_new_readers_are_silent_where_the_program_has_no_such_scope():
    """On the parent's programs (no ``pbox.kda_*`` scope) the two readers
    return nothing and do not raise."""
    red = {"batches": 4, "scopes": {"pbox.attn": 1.0, "other": 0.5}}
    ctx = {"trace": red, "work": {"scopes": {}}, "window": {},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert harness.read_layer_metrics(NEW_READERS, ctx) == {}
    assert harness.read_layer_metrics(NEW_READERS,
                                      dict(ctx, trace=None)) == {}
    # with the scope but a family that counts nothing for it: the time
    red["scopes"]["pbox.kda_scan"] = 0.2
    assert harness.read_layer_metrics(NEW_READERS, ctx) == {
        "step.kda_ms": pytest.approx(50.0)}


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
        assert not ok, (seed, run, numbers)
        assert numbers["rows_count"] == 0 or run == "fault:state_unchanged"
    for seed, run, numbers in study.program_readings(toy, [8]):
        ok, _ = compare.judge(numbers, toy["limits"])
        assert ok and run == "program", numbers
