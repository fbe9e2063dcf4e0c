"""Family ``lm_ouro`` (``families/lm_ouro.py`` over ``families/lm.py``,
``entries/resident_seq.py``, ``reference/lm.py`` +
``reference/models/ouro.py``) through the harness and ``study.py`` on the
CPU at a toy size, as ``test_family_lm_mellum.py`` does for family
``lm_mellum``: sound runs come out correct, the control and both faults
do not; ``work`` against hand counts; the configuration against the
catalog's row."""

import copy
import json
import os
import time

import numpy as np
import pytest

from benchmarks import compare, harness, study, tracered
from benchmarks.families import lm, lm_ouro
from benchmarks.tests.test_benchmarks import bench
from benchmarks.tests.test_families import PEAKS

CELL = "ouro-2.6b.train-packed-8k-1seq"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

#: limits of the toy cell, from its own readings on the CPU: the program
#: (bfloat16 operands) against the reference with the same roundings
#: written out, seeds 5..10 and 2^31+5, reads early_embed 0.0095..0.0201,
#: early_g2sum 0.0017..0.0105, dparam 0.0006..0.0018, loss 3.0e-5..1.7e-4;
#: the float8 control (operands float8, cotangents kept), seeds 3, 5 and
#: 2^31+4: 0.155..0.309, 0.089..0.206, 0.0085..0.0111 and
#: 0.0013..0.0039; a loop one run short: 0.144..0.194, 0.070..0.102,
#: 0.0132..0.0160 and 0.0045..0.0080 (Adam moves a weight by about its
#: rate whatever the gradient's size, so ``dparam`` sees a missing run
#: least); a state left unchanged reads 1 on the three first. Each limit
#: is the geometric middle of the sound side's largest and the smallest
#: of the control's and the short loop's.
TOY_LIMITS = {"loss": 4.8e-4, "dparam": 0.0039, "rows_count": 0.0,
              "early_embed": 0.054, "early_g2sum": 0.027}


def toy_config(layers: int = 2, **over) -> dict:
    """The keys a toy stack of ``layers`` layers changes in the cell's
    configuration."""
    cfg = dict(
        hidden_size=64, vocab_size=96, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, intermediate_size=80,
        num_hidden_layers=layers, layer_types=["full_attention"] * layers,
        rope_theta=100, total_ut_steps=4, table_rows_per_chip=96)
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

def test_the_family_is_lm_but_for_the_counting_and_the_faults():
    for name in ("make_pool", "seeded_params", "sample", "first_pass",
                 "reference_pass", "numbers", "diagnostics",
                 "control_precision"):
        assert getattr(lm_ouro, name) is getattr(lm, name), name
    assert lm_ouro.work is not lm.work
    assert lm_ouro.layer_params is not lm.layer_params
    assert lm_ouro.FAULTS == ("state_unchanged", "loop_short")


def test_configuration_keeps_every_published_number_but_the_depth():
    """Every key of the catalog's row under its own name and with its own
    value, but the two that ``reduced`` names; what the file adds is
    stated under ``assumed``."""
    config = harness.load_cell(CELL)["config"]
    entry = [c for c in bench()["configs"] if c["name"] == config["name"]][0]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert entry["source"] == config["source"]
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    for key, value in published.items():
        assert config[key] == value, key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "Ouro-2.6B"][0]
        assert row["source_url"] == config["source"]
        assert set(row["config"]) == set(published) | set(config["reduced"])
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
        assert config["published"] == {
            k: row["config"][k] for k in config["reduced"]}
    kinds = config["published"]["layer_types"]
    assert kinds == ["full_attention"] * 48
    assert config["published"]["num_hidden_layers"] == len(kinds) == 48
    # ISSUE 40's fallback, with its reason and readings in the file: 6
    # layers, one stage of eight
    assert config["layer_types"] == kinds[:6]
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 6
    assert "9.39 GiB where 9.28 GiB were free" in config["fallback"]
    # the cut is depth alone: the whole vocabulary, one chip a layer
    assert config["table_rows_per_chip"] == config["vocab_size"] == 49152
    assert config["chips_per_layer"] == 1
    assert config["exit_entropy_beta"] == 0.1
    assert config["dense_optimizer"]["learning_rate"] == 1e-5
    for key in ("sandwich_norms", "final_norm_in_loop", "exit_gate", "loss",
                "exit_entropy_beta", "early_exit_threshold",
                "attention_bias", "qk_norm", "rotary", "max_window_layers",
                "multi_token_prediction", "rotary_positions",
                "dense_optimizer", "embedding_rule", "weights",
                "token_vectors", "row_bytes"):
        assert config["assumed"][key], key
    assert "eight stages of 6 layers" in config["deployment"]


# ---- the step's work from shapes -------------------------------------------

def test_work_counts_a_layer_a_run_and_the_head_an_exit():
    cell = harness.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    lp = lm_ouro.layer_params(config)
    # the issue's own arithmetic: a layer is 51.39M with its four norms
    assert lp["attention"] == 4 * 2048 * 2048 + 2 * 2048 == 16_781_312
    assert lp["mlp"] == 3 * 2048 * 5632 + 2 * 2048 == 34_607_104
    layer = lp["attention"] + lp["mlp"]
    assert layer == pytest.approx(51.39e6, rel=1e-4)
    assert lp["head"] == 49152 * 2048 == 100_663_296
    dense = 6 * layer + lp["head"] + 2048 + 2049
    assert dense == pytest.approx(409.0e6, rel=1e-4)
    assert 8 * layer + lp["head"] + 4097 == pytest.approx(511.8e6, rel=1e-4)
    # the weights' shapes as the reference makes them: 409.0M without the
    # token vectors
    import jax
    from benchmarks.reference.models import ouro as ref
    shapes = jax.eval_shape(lambda k: {
        "net": ref.init(k, config), "embedding": ref.init_embedding(
            k, config)}, jax.random.PRNGKey(0))
    param_shapes = [tuple(x.shape) for x in jax.tree.leaves(shapes)]
    assert sum(int(np.prod(s)) for s in param_shapes) \
        == dense + 49152 * 2048
    w = lm_ouro.work(config, traffic, 1, param_shapes)
    causal_half = 3 * 2 * 2 * 4096 * 128 * 16      # a layer application
    assert causal_half == 3 * 33_554_432
    assert w["flops_per_example"] == pytest.approx(
        6 * (24 * layer + 4 * lp["head"]) + 24 * causal_half)
    assert w["flops_per_example"] == pytest.approx(12.23e9, rel=1e-3)
    assert w["flops"] == w["flops_per_example"] * 8192
    assert w["flops"] == pytest.approx(100.2e12, rel=1e-3)
    # the head's four reads: 19.8% of the count at 6 layers, 15.6% at
    # ISSUE 40's 8 (15.5 GFLOP a token), 3% at the published 48
    def head_share(layers):
        deep = lm_ouro.work(dict(config, num_hidden_layers=layers), traffic,
                            1, param_shapes)
        return 6 * 4 * lp["head"] / deep["flops_per_example"], \
            deep["flops_per_example"]
    assert head_share(6)[0] == pytest.approx(0.198, abs=0.001)
    assert head_share(8)[0] == pytest.approx(0.156, abs=0.001)
    assert head_share(8)[1] == pytest.approx(15.5e9, rel=2e-3)
    assert head_share(48)[0] == pytest.approx(0.03, abs=0.002)
    assert w["keys_per_example"] == 1 and w["tokens"] == 8192
    assert w["bytes"] == pytest.approx(
        dense * 4 * 6 + w["rows"] * 2056 * 12 + 8192 * 8)
    assert set(w["scopes"]) == {"pbox.attn", "pbox.mlp"}
    assert w["scopes"]["pbox.attn"]["flops"] == pytest.approx(
        24 * (6 * lp["attention"] + causal_half) * 8192)
    assert w["scopes"]["pbox.mlp"]["flops"] == pytest.approx(
        24 * 6 * lp["mlp"] * 8192)
    assert w["scopes"]["pbox.attn"]["bytes"] == pytest.approx(
        24 * (12 * lp["attention"] + 12 * 8192 * (2 * 2048 + 128 * 48)))
    assert w["scopes"]["pbox.mlp"]["bytes"] == pytest.approx(
        24 * (12 * lp["mlp"] + 12 * 8192 * 2 * 2048))
    # four fifths of the count are the layers' dense products and
    # attention over 8,192 rows: the feed-forward, then attention, then
    # the head
    total = w["flops"]
    assert w["scopes"]["pbox.mlp"]["flops"] / total == \
        pytest.approx(0.41, abs=0.01)
    assert w["scopes"]["pbox.attn"]["flops"] / total == \
        pytest.approx(0.40, abs=0.01)
    # distinct rows of a step under Zipf(1): a few thousand of 49,151
    assert 2000 < w["rows"] < 8192
    # a second size: two runs, sequences of 4,096
    small = dict(config, total_ut_steps=2)
    w2 = lm_ouro.work(small, dict(traffic, seq_len=4096), 1, param_shapes)
    assert w2["flops_per_example"] == pytest.approx(
        6 * (12 * layer + 2 * lp["head"]) + 12 * causal_half / 2)


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


NEW_READERS = ["step.exit_gate_ms", "loop.expected_exit_step",
               "kernels.mlp_roofline"]


def test_traced_run_reports_exactly_the_cells_metrics(toy, monkeypatch):
    """``--trace 1`` with the profiler patched out by a hand-made plane
    under the step's own scopes: exactly the metrics that list the cell
    read, the scope readers sum to the step, every share of a roofline
    or of the peak lies in (0, 100)."""
    scopes = ["pbox.decode", "pbox.dedup", "pbox.pull", "pbox.attn",
              "pbox.attn.bwd", "pbox.mlp", "pbox.mlp.bwd", "pbox.exit_gate",
              "pbox.exit_gate.bwd", "pbox.head", "pbox.loss", "pbox.push",
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
        "step.attn_ms", "step.dense_mlp_ms", "step.head_loss_ms",
        "kernels.attn_roofline", "step.wide_pull_ms",
        "step.wide_push_ms"} <= listed
    assert not {"step.ssm_ms", "step.conv_ms", "step.moe_ms",
                "step.attn_window_ms", "moe.load_imbalance",
                "moe.held_share", "kernels.moe_experts_roofline"} & listed
    val = {k: v["value"] for k, v in res["metrics"].items()}
    assert val["step.attn_ms"] == pytest.approx(2 * 25.0)
    assert val["step.dense_mlp_ms"] == pytest.approx(2 * 25.0)
    assert val["step.exit_gate_ms"] == pytest.approx(2 * 25.0)
    assert val["step.head_loss_ms"] == pytest.approx(2 * 25.0)
    assert val["step.wide_pull_ms"] == pytest.approx(25.0)
    assert val["step.wide_push_ms"] == pytest.approx(25.0)
    assert val["step.ms_per_batch"] == pytest.approx(len(scopes) * 25.0)
    for name in listed:
        if "roofline" in name or "mfu" in name:
            assert 0 < val[name] < 100, name
    # a gate at logit 0 over four runs leaves at 1.875 on average; the
    # toy's has trained for thirty steps at a rate of 1e-3 by the window
    assert 1.875 < val["loop.expected_exit_step"] < 4
    assert val["entry.compiles_in_window"] == 0


def test_the_new_readers_are_silent_where_the_program_has_no_such_scope():
    """On the parent's program (no ``pbox.exit_gate``, no ``loop_*``
    counter on its spans) and on a family that counts no ``pbox.mlp`` the
    three readers return nothing and do not raise; on a stub trace with
    the scope and the counts they return a number."""
    red = {"batches": 4, "scopes": {"pbox.attn": 1.0, "pbox.mlp": 0.5,
                                    "other": 0.5}}
    ctx = {"trace": red, "work": {"scopes": {}}, "window": {},
           "peaks": PEAKS}
    assert harness.read_layer_metrics(NEW_READERS, ctx) == {}
    assert harness.read_layer_metrics(NEW_READERS,
                                      dict(ctx, trace=None)) == {}
    red = {"batches": 4, "scopes": {"pbox.mlp": 0.8, "pbox.exit_gate": 0.1,
                                    "pbox.exit_gate.bwd": 0.1}}
    need = {"pbox.mlp": {"flops": 197e12 * 0.05, "bytes": 1.0}}
    got = harness.read_layer_metrics(
        NEW_READERS, dict(ctx, trace=red, work={"scopes": need}))
    assert got == {"step.exit_gate_ms": pytest.approx(50.0),
                   "kernels.mlp_roofline": pytest.approx(25.0)}


def test_the_counter_reader_is_silent_on_spans_without_the_counters(
        monkeypatch):
    from benchmarks import span_window as sw
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
                      moe_choices_held=800.0)]
    monkeypatch.setattr(sw, "ring", lambda: spans)
    ctx = {"window": {"train_s": [0.8, 0.8]}}
    name = ["loop.expected_exit_step"]
    assert harness.read_layer_metrics(name, ctx) == {}
    for i, r in enumerate(spans):
        if r.name == "pass.finish":
            r.attrs.update(loop_positions=32768.0,
                           loop_exit_step_sum=32768.0 * (1.5 + i // 2))
    # the window's two passes are the last two: 2.5 and 3.5
    assert harness.read_layer_metrics(name, ctx) == {
        "loop.expected_exit_step": pytest.approx(3.0)}


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


def test_a_program_that_leaves_a_run_out_is_not_correct(toy, monkeypatch):
    """The fault in the program itself and not in the reference: a loop
    of three runs where the configuration states four."""
    import paddlebox_tpu.models as models

    class Short(models.OuroLoop):
        def __init__(self, config, **kw):
            super().__init__(dict(config, total_ut_steps=3), **kw)
    monkeypatch.setattr(models, "OuroLoop", Short)
    res = harness.run_cell(CELL, 6, 0.2, False, time.perf_counter())
    assert res["correct"] is False
    failed = {k for k, (v, lim) in res["compared"].items() if not v <= lim}
    assert {"loss", "dparam", "early_embed", "early_g2sum"} <= failed
    assert res["compared"]["rows_count"][0] == 0


def test_study_reads_the_control_and_both_faults(toy):
    got = list(study.stand_in_readings(toy, [3, 2 ** 31 + 4]))
    assert [run for _, run, _ in got] == [
        "control:float8_e4m3fn", "fault:state_unchanged",
        "fault:loop_short"] * 2
    for seed, run, numbers in got:
        ok, table = compare.judge(numbers, toy["limits"])
        assert not ok, (seed, run, numbers)
        failed = [k for k, (v, lim) in table.items() if not v <= lim]
        assert len(failed) >= 3, (seed, run, failed)
        assert numbers["rows_count"] == 0 or run == "fault:state_unchanged"
    for seed, run, numbers in study.program_readings(toy, [8]):
        ok, _ = compare.judge(numbers, toy["limits"])
        assert ok and run == "program", numbers
