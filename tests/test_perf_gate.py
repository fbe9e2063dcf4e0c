"""Bench perf-regression gate (scripts/perf_gate.py): artifact folding,
trajectory append, the latest-vs-best check (synthetic degradation is
flagged, the repo's real trajectory passes), graceful no-file skip, and
the critical-path math smoke (ISSUE 10 tier-1 wiring)."""

import importlib.util
import json
import os

import pytest

REPO = os.path.join(os.path.dirname(__file__), os.pardir)


def _load(name, rel):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def perf_gate():
    return _load("perf_gate", os.path.join("scripts", "perf_gate.py"))


def _row(metric, value, source, **kw):
    return dict(metric=metric, value=value, source=source,
                unit="examples/sec/chip", **kw)


# ---- folding -----------------------------------------------------------
def test_parse_driver_wrapper_artifact(perf_gate, tmp_path):
    tail = "\n".join([
        "some log line",
        json.dumps({"metric": "m_a", "value": 100.0, "unit": "u",
                    "mode": "resident", "shape": "uniform",
                    "device_busy_frac": 0.5}),
        json.dumps({"not_a_bench_row": 1}),
        "{broken json",
        json.dumps({"metric": "m_b", "value": 7.5, "unit": "u"}),
    ])
    p = tmp_path / "BENCH_r01.json"
    p.write_text(json.dumps({"n": 1, "cmd": "x", "rc": 0, "tail": tail}))
    rows = perf_gate.parse_bench_artifact(str(p))
    assert [r["metric"] for r in rows] == ["m_a", "m_b"]
    assert rows[0]["source"] == "BENCH_r01"
    assert rows[0]["device_busy_frac"] == 0.5
    assert rows[0]["mode"] == "resident"


def test_fold_builds_trajectory(perf_gate, tmp_path):
    for rnd, val in (("r01", 50.0), ("r02", 80.0)):
        (tmp_path / f"BENCH_{rnd}.json").write_text(json.dumps({
            "tail": json.dumps({"metric": "m", "value": val,
                                "unit": "u"})}))
    # the elastic-churn gate's artifact family folds in too (ISSUE 18)
    (tmp_path / "ELASTIC_r01.json").write_text(json.dumps({
        "tail": json.dumps({"metric": "elastic.reshard_stall_ms",
                            "value": 120.0, "unit": "ms"})}))
    out = str(tmp_path / "BENCH_trajectory.json")
    data = perf_gate.fold(repo_root=str(tmp_path), out_path=out)
    assert [r["value"] for r in data["rows"]] == [50.0, 80.0, 120.0]
    assert data["rows"][2]["source"] == "ELASTIC_r01"
    on_disk = json.load(open(out))
    assert on_disk["rows"] == data["rows"]


def test_fold_real_repo_artifacts_and_check_passes(perf_gate, tmp_path):
    """The REAL recorded rounds fold cleanly and pass the gate — the
    trajectory the repo commits must never itself trip the check."""
    out = str(tmp_path / "traj.json")
    data = perf_gate.fold(repo_root=REPO, out_path=out)
    metrics = {r["metric"] for r in data["rows"]}
    assert "deepfm_ctr_examples_per_sec_per_chip" in metrics
    failures, summary = perf_gate.check_rows(data["rows"])
    assert failures == [], failures
    assert summary
    assert perf_gate.check(out) == 0


def test_committed_trajectory_is_current_and_passes(perf_gate):
    """tier-1 wiring of `perf_gate.py --check`: the committed
    BENCH_trajectory.json exists and the gate passes on its RECORDED
    rounds (--ignore-live: rows bench.py appended live are gated by
    the bench banner, not CI)."""
    path = perf_gate.default_trajectory_path()
    assert os.path.exists(path), \
        "BENCH_trajectory.json missing — run scripts/perf_gate.py --fold"
    assert perf_gate.main(["--check", "--trajectory", path,
                           "--ignore-live"]) == 0


# ---- the gate ----------------------------------------------------------
def test_check_flags_synthetic_degradation(perf_gate, tmp_path):
    rows = [_row("m", 100.0, "r01"), _row("m", 90.0, "r02"),
            _row("m", 40.0, "live")]   # 60% below best 100
    failures, _ = perf_gate.check_rows(rows, max_drop_frac=0.5)
    assert len(failures) == 1
    assert "PERF REGRESSION" in failures[0]
    assert "m" in failures[0] and "floor" in failures[0]
    # CLI exit code 1
    p = str(tmp_path / "t.json")
    perf_gate._write(p, {"version": 1, "rows": rows})
    assert perf_gate.main(["--check", "--trajectory", p]) == 1


def test_check_tolerates_drop_within_threshold(perf_gate):
    rows = [_row("m", 100.0, "r01"), _row("m", 60.0, "live")]
    failures, summary = perf_gate.check_rows(rows, max_drop_frac=0.5)
    assert failures == []
    assert len(summary) == 1
    # a tighter threshold flips it
    failures, _ = perf_gate.check_rows(rows, max_drop_frac=0.25)
    assert len(failures) == 1


def test_check_single_row_and_improvements_pass(perf_gate):
    rows = [_row("solo", 5.0, "r01"),
            _row("up", 10.0, "r01"), _row("up", 30.0, "live")]
    failures, summary = perf_gate.check_rows(rows)
    assert failures == []
    assert any("no history" in s for s in summary)


def test_check_reports_every_regressed_key_worst_first(perf_gate,
                                                       tmp_path):
    """One --check run over a round that regressed SEVERAL keys — the
    multichip scaling rows included — must name them all, ordered by
    drop severity, in one pass (ISSUE 11)."""
    rows = [
        _row("sharded.n4.uniform.ex_per_sec_per_chip", 1000.0, "r06",
             n_chips=4),
        _row("sharded.n4.uniform.ex_per_sec_per_chip", 100.0, "r07",
             n_chips=4),                                  # -90%
        _row("sharded.n8.uniform.scaling_efficiency", 0.8, "r06"),
        _row("sharded.n8.uniform.scaling_efficiency", 0.3, "r07"),  # -62%
        _row("m_fine", 50.0, "r06"), _row("m_fine", 49.0, "r07"),
    ]
    failures, summary = perf_gate.check_rows(rows, max_drop_frac=0.5)
    assert len(failures) == 2, failures
    # worst drop first
    assert "sharded.n4.uniform.ex_per_sec_per_chip" in failures[0]
    assert "sharded.n8.uniform.scaling_efficiency" in failures[1]
    assert any("m_fine" in s for s in summary)
    # CLI still exits 1 and prints both
    p = str(tmp_path / "t.json")
    perf_gate._write(p, {"version": 1, "rows": rows})
    assert perf_gate.main(["--check", "--trajectory", p]) == 1


# ---- lower-is-better latency keys (BENCH_MODE=serve — ISSUE 15) --------
def test_ms_keys_gate_lower_is_better(perf_gate):
    """``*_ms`` metrics (serving latency) regress when the latest
    value RISES past best*(1+frac): best is the LOWEST recorded row,
    improvements (lower latency) always pass."""
    assert perf_gate.lower_is_better("serving.uniform.p99_ms")
    assert not perf_gate.lower_is_better("serving.uniform.qps")
    rows = [_row("serving.uniform.p99_ms", 2.0, "SERVE_r01"),
            _row("serving.uniform.p99_ms", 2.4, "SERVE_r02"),
            _row("serving.uniform.p99_ms", 8.0, "live")]  # 4x the best
    failures, _ = perf_gate.check_rows(rows, max_drop_frac=0.5)
    assert len(failures) == 1
    assert "PERF REGRESSION" in failures[0]
    assert "ceiling" in failures[0]
    # within the ceiling: passes; an IMPROVEMENT (lower) always passes
    ok = [_row("serving.uniform.p99_ms", 2.0, "SERVE_r01"),
          _row("serving.uniform.p99_ms", 2.9, "live")]
    failures, summary = perf_gate.check_rows(ok, max_drop_frac=0.5)
    assert failures == [] and len(summary) == 1
    better = [_row("serving.uniform.p99_ms", 2.0, "SERVE_r01"),
              _row("serving.uniform.p99_ms", 0.5, "live")]
    failures, _ = perf_gate.check_rows(better, max_drop_frac=0.5)
    assert failures == []


def test_ms_regression_ranks_with_throughput_drops(perf_gate, tmp_path):
    """A mixed round (throughput drop + latency rise) reports BOTH,
    worst severity first, and the CLI exits 1."""
    rows = [
        _row("serving.uniform.p99_ms", 1.0, "SERVE_r01"),
        _row("serving.uniform.p99_ms", 4.0, "live"),      # +300%
        _row("serving.uniform.qps", 1000.0, "SERVE_r01"),
        _row("serving.uniform.qps", 400.0, "live"),       # -60%
    ]
    failures, _ = perf_gate.check_rows(rows, max_drop_frac=0.5)
    assert len(failures) == 2, failures
    assert "p99_ms" in failures[0]     # +300% outranks -60%
    assert "qps" in failures[1]
    p = str(tmp_path / "t.json")
    perf_gate._write(p, {"version": 1, "rows": rows})
    assert perf_gate.main(["--check", "--trajectory", p]) == 1


def test_multichip_extra_fields_ride_the_row(perf_gate, tmp_path):
    """n_chips / a2a_chunks / exchange_overlap_frac are first-class
    trajectory passthrough fields (EXTRA_FIELDS) on both the fold and
    the live-append paths."""
    tail = json.dumps({"metric": "sharded.n2.uniform.ex_per_sec_per_chip",
                       "value": 5000.0, "unit": "examples/sec/chip",
                       "mode": "multichip", "n_chips": 2,
                       "a2a_chunks": 2})
    p = tmp_path / "BENCH_r09.json"
    p.write_text(json.dumps({"n": 9, "cmd": "x", "rc": 0, "tail": tail}))
    rows = perf_gate.parse_bench_artifact(str(p))
    assert rows[0]["n_chips"] == 2 and rows[0]["a2a_chunks"] == 2
    traj = str(tmp_path / "traj.json")
    perf_gate.record_result(
        {"metric": "m_sharded", "value": 1.0, "unit": "u",
         "exchange_overlap_frac": 0.4, "n_chips": 4}, path=traj)
    live = json.load(open(traj))["rows"][-1]
    assert live["exchange_overlap_frac"] == 0.4 and live["n_chips"] == 4


def test_check_keys_are_per_metric(perf_gate):
    """The tiered metric regressing must flag even while resident is
    fine (per-mode/shape gating — the metric name carries both)."""
    rows = [_row("m_tiered", 28000.0, "r06"),
            _row("m_tiered", 8000.0, "live"),
            _row("m", 100000.0, "r06"), _row("m", 110000.0, "live")]
    failures, _ = perf_gate.check_rows(rows, max_drop_frac=0.5)
    assert len(failures) == 1
    assert "m_tiered" in failures[0]


def test_check_skips_gracefully_without_file(perf_gate, tmp_path):
    missing = str(tmp_path / "nope.json")
    assert perf_gate.main(["--check", "--trajectory", missing]) == 0


# ---- bench append hook -------------------------------------------------
def test_record_result_appends_and_gates(perf_gate, tmp_path, capsys):
    p = str(tmp_path / "traj.json")
    perf_gate._write(p, {"version": 1, "rows": [
        _row("m", 100.0, "r01")]})
    fails = perf_gate.record_result(
        {"metric": "m", "value": 95.0, "unit": "u", "mode": "resident",
         "shape": "uniform", "device_busy_frac": 0.9}, path=p,
        max_drop_frac=0.5)
    assert fails == []
    data = json.load(open(p))
    assert len(data["rows"]) == 2
    live = data["rows"][-1]
    assert live["source"] == "live" and "recorded_at" in live
    assert live["device_busy_frac"] == 0.9
    # a degraded live row is flagged loudly
    fails = perf_gate.record_result(
        {"metric": "m", "value": 10.0, "unit": "u"}, path=p,
        max_drop_frac=0.5)
    assert len(fails) == 1 and "PERF REGRESSION" in fails[0]
    assert "PERF REGRESSION" in capsys.readouterr().err


def test_record_result_raises_on_broken_trajectory(perf_gate, tmp_path):
    """A bench run whose record was lost must not exit 0."""
    bad = str(tmp_path / "bad.json")
    open(bad, "w").write("{not json")
    with pytest.raises(ValueError):
        perf_gate.record_result({"metric": "m", "value": 1.0}, path=bad)


# ---- critical-path math smoke (deterministic synthetic events) --------
def test_critical_path_smoke_end_to_end():
    """The gate's sibling tier-1 requirement: deterministic synthetic
    pass parts → block math → report verdicts, no trainers involved."""
    from paddlebox_tpu.obs import trace
    tr = _load("telemetry_report",
               os.path.join("scripts", "telemetry_report.py"))
    # 4 device-bound passes, one fence-bound straggler
    events = []
    specs = [(1.0, {"build_wait": 0.05}), (1.0, {}),
             (0.8, {"fence_wait": 1.2}), (1.0, {"stage_wait": 0.02}),
             (1.0, {"evict_emergency": 0.4})]
    for i, (train, parts) in enumerate(specs):
        blk = trace.critical_path_block(train, parts)
        assert blk["wall_sec"] == pytest.approx(
            train + sum(parts.values()))
        events.append({"event": "pass", "ts": i, "seq": i, "proc": 0,
                       "kind": "train_pass_resident",
                       "pass_seq": i + 1, "batches": 1, "examples": 10,
                       "elapsed_sec": train,
                       "examples_per_sec": 10 / train,
                       "critical_path": blk})
    line = tr.critical_path_summary(events)
    assert "4/5 passes device-bound" in line
    assert "pass 3 fence_wait-bound: +1.200s" in line
    report = tr.render_report(events)
    assert "bottleneck" in report
    assert "fence_wait +1.200s" in report
