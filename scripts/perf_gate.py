#!/usr/bin/env python
"""Bench perf-regression gate over the recorded trajectory (ISSUE 10).

The per-round ``BENCH_r0*.json`` artifacts record every bench round's
headline rows, but nothing ever compared them — a throughput regression
(tiered at 0.14x before PR 8) surfaced only when a human re-read the
numbers. This script makes the trajectory machine-readable and gates on
it:

- ``--fold``: parse every ``BENCH_r0*.json`` artifact (the driver's
  ``{n, cmd, rc, tail}`` wrapper — bench rows are the JSON lines inside
  ``tail``; raw bench stdout / JSONL also parses) into
  ``BENCH_trajectory.json``: one row per (metric, mode, shape) per
  round, carrying value/unit plus ``device_busy_frac`` and
  ``begin_delta_steady_sec`` when the round reported them.
- ``bench.py`` APPENDS its live headline rows to the trajectory after
  each run (``record_result``; ``BENCH_TRAJECTORY=0`` disables,
  ``BENCH_TRAJECTORY=/path`` overrides) and prints a loud REGRESSION
  banner when a fresh row lands below the gate.
- ``--check``: for every (metric, mode, shape) key, compare the LATEST
  row against the best earlier row; fail (exit 1) when the latest value
  drops more than ``--max-drop-frac`` below the best. Skips gracefully
  (exit 0, a note) when no trajectory file exists yet.

Threshold: the default ``--max-drop-frac 0.5`` tolerates the 2-3x
round-to-round swings the recorded rounds show on raw ex/s while still
catching architecture-level regressions like the pre-PR 8 tiered
collapse (8.5k vs a 28k best = 0.70 drop — flagged). Override per run
with ``BENCH_GATE_MAX_DROP``.

Stdlib only — runs anywhere the artifacts land. Wired into tier-1 by
``tests/test_perf_gate.py`` (synthetic degradation flagged, real
trajectory passes).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

DEFAULT_MAX_DROP = 0.5
#: per-row fields copied into the trajectory when the bench reported
#: them (the "where did the time go" companions of the headline value).
#: n_chips/a2a_chunks/exchange_overlap_frac ride the sharded rows.
#: pv_batch_size/instances_per_pass ride the PV rank-attention lane
#: rows (``adsrank_pv_*``, BENCH_MODE=pv — ISSUE 13).
EXTRA_FIELDS = ("device_busy_frac", "begin_delta_steady_sec",
                "end_pass_overlap_frac", "vs_baseline", "n_chips",
                "a2a_chunks", "exchange_overlap_frac",
                "pv_batch_size", "instances_per_pass",
                "qps", "queries", "batch")

#: metric-name suffixes gated LOWER-is-better: latency rows
#: (``serving.{shape}.p99_ms``, BENCH_MODE=serve — ISSUE 15) regress
#: when the latest value RISES past best*(1+max_drop_frac), the mirror
#: of the throughput rule. Everything else stays higher-is-better.
LOWER_IS_BETTER_SUFFIXES = ("_ms",)


def lower_is_better(metric: str) -> bool:
    return str(metric).endswith(LOWER_IS_BETTER_SUFFIXES)


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def default_trajectory_path() -> str:
    return os.path.join(_repo_root(), "BENCH_trajectory.json")


def row_key(row: Dict) -> Tuple[str]:
    """Gate key. The metric name already encodes mode and shape
    (``…_tiered``, ``…_zipf_tiered``, ``…_sharded``, ``…_streaming``,
    the wire-normalized ``…_per_wire_mb_per_sec``), and early rounds'
    rows predate the explicit mode/shape fields — keying on anything
    more would split one metric's history into phantom keys across
    rounds."""
    return (str(row.get("metric", "")),)


def _rows_from_lines(lines, source: str) -> List[Dict]:
    rows = []
    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            d = json.loads(line)
        except json.JSONDecodeError:
            continue
        if not isinstance(d, dict) or "metric" not in d:
            continue
        v = d.get("value")
        if not isinstance(v, (int, float)):
            continue
        row = {"source": source, "metric": d["metric"],
               "value": float(v), "unit": d.get("unit", "")}
        for k in ("mode", "shape"):
            if d.get(k):
                row[k] = d[k]
        for k in EXTRA_FIELDS:
            if isinstance(d.get(k), (int, float)):
                row[k] = d[k]
        rows.append(row)
    return rows


def parse_bench_artifact(path: str) -> List[Dict]:
    """Bench rows out of one artifact: the driver wrapper ({..., tail})
    or raw bench output / JSONL."""
    source = os.path.splitext(os.path.basename(path))[0]
    with open(path) as fh:
        text = fh.read()
    try:
        outer = json.loads(text)
    except json.JSONDecodeError:
        outer = None
    if isinstance(outer, dict) and "tail" in outer:
        return _rows_from_lines(str(outer["tail"]).splitlines(), source)
    return _rows_from_lines(text.splitlines(), source)


def load_trajectory(path: str) -> Optional[Dict]:
    if not os.path.exists(path):
        return None
    with open(path) as fh:
        data = json.load(fh)
    if not isinstance(data, dict) or "rows" not in data:
        raise ValueError(f"{path}: not a trajectory file")
    return data


def _write(path: str, data: Dict) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(data, fh, indent=1)
        fh.write("\n")
    os.replace(tmp, path)


def fold(repo_root: Optional[str] = None,
         out_path: Optional[str] = None) -> Dict:
    """Recorded artifacts → BENCH_trajectory.json (sorted by family,
    then round). Besides the driver's ``BENCH_r0*`` rounds this folds
    the multichip scaling rounds (``MULTICHIP_r0*``, ISSUE 11), the
    kernel-microbench rounds (``KERNELS_r0*``,
    ``scripts/profile_keypath.py --set kernels`` — ISSUE 12) and the
    serving-lane rounds (``SERVE_r0*``, BENCH_MODE=serve — ISSUE 15)
    and the elastic-churn rounds (``ELASTIC_r0*``,
    ``scripts/elastic_check.py --artifact`` — ISSUE 18), so a rebuild
    keeps their gate history instead of silently dropping it."""
    root = repo_root or _repo_root()
    out = out_path or os.path.join(root, "BENCH_trajectory.json")
    rows: List[Dict] = []
    for pattern in ("BENCH_r[0-9]*.json", "MULTICHIP_r[0-9]*.json",
                    "KERNELS_r[0-9]*.json", "SERVE_r[0-9]*.json",
                    "ONLINE_r[0-9]*.json", "ELASTIC_r[0-9]*.json"):
        for path in sorted(glob.glob(os.path.join(root, pattern))):
            rows.extend(parse_bench_artifact(path))
    data = {"version": 1, "rows": rows}
    _write(out, data)
    return data


def append_row(row: Dict, path: str) -> None:
    """Append one live bench row (bench.py's per-run record)."""
    data = load_trajectory(path) or {"version": 1, "rows": []}
    data["rows"].append(row)
    _write(path, data)


def check_rows(rows: List[Dict],
               max_drop_frac: float = DEFAULT_MAX_DROP
               ) -> Tuple[List[str], List[str]]:
    """(failures, summary) over the trajectory: per key, the LATEST
    row vs the best EARLIER row. A single-row key has no history and
    passes by definition."""
    by_key: Dict[Tuple, List[Dict]] = {}
    for r in rows:
        by_key.setdefault(row_key(r), []).append(r)
    flagged: List[Tuple[float, str]] = []
    summary: List[str] = []
    for key in sorted(by_key):
        hist = by_key[key]
        latest = hist[-1]
        prior = hist[:-1]
        label = "/".join(k for k in key if k)
        if not prior:
            summary.append(f"  {label}: {latest['value']:g} "
                           f"(1 row, no history)")
            continue
        if lower_is_better(key[0]):
            # latency keys: best = the LOWEST recorded value; the gate
            # fails when the latest RISES past best*(1+max_drop_frac)
            best = min(prior, key=lambda r: r["value"])
            ceil = best["value"] * (1.0 + max_drop_frac)
            drop = (latest["value"] / best["value"] - 1.0
                    if best["value"] > 0 else 0.0)
            line = (f"  {label}: latest {latest['value']:g} "
                    f"({latest.get('source', '?')}) vs best "
                    f"{best['value']:g} ({best.get('source', '?')}) — "
                    f"rise {drop:+.1%}, ceiling {ceil:g}")
            bad = latest["value"] > ceil
        else:
            best = max(prior, key=lambda r: r["value"])
            floor = best["value"] * (1.0 - max_drop_frac)
            drop = 1.0 - latest["value"] / best["value"] \
                if best["value"] > 0 else 0.0
            line = (f"  {label}: latest {latest['value']:g} "
                    f"({latest.get('source', '?')}) vs best "
                    f"{best['value']:g} ({best.get('source', '?')}) — "
                    f"drop {drop:+.1%}, floor {floor:g}")
            bad = latest["value"] < floor
        if bad:
            flagged.append((drop, "PERF REGRESSION:" + line))
        else:
            summary.append(line)
    # EVERY regressed key reports in one run, worst drop first — a
    # multichip round regressing several sharded.n{N}.{shape} keys at
    # once must name them all, not just the first (ISSUE 11)
    failures = [line for _, line in
                sorted(flagged, key=lambda t: -t[0])]
    return failures, summary


def check(path: str,
          max_drop_frac: float = DEFAULT_MAX_DROP,
          ignore_live: bool = False) -> int:
    """CLI --check body: 0 = pass/skip, 1 = regression.
    ``ignore_live`` gates only the RECORDED rounds (BENCH_r0*
    artifacts), skipping rows bench.py appended live — what tier-1
    runs, so a slow shared dev box can't fail CI through a live row
    while the committed trajectory stays gated."""
    data = load_trajectory(path)
    if data is None:
        print(f"perf_gate: no trajectory at {path} — nothing to gate "
              "yet (run --fold or a bench round first); skipping",
              file=sys.stderr)
        return 0
    rows = data["rows"]
    if ignore_live:
        rows = [r for r in rows if r.get("source") != "live"]
    failures, summary = check_rows(rows, max_drop_frac)
    for line in summary:
        print(line)
    for line in failures:
        print(line, file=sys.stderr)
    if failures:
        print(f"perf_gate: {len(failures)} metric(s) below "
              f"{max_drop_frac:.0%} of their recorded best",
              file=sys.stderr)
        return 1
    print(f"perf_gate: OK ({len(summary)} metric key(s), "
          f"max allowed drop {max_drop_frac:.0%})")
    return 0


def record_result(result: Dict, path: Optional[str] = None,
                  max_drop_frac: Optional[float] = None) -> List[str]:
    """bench.py's hook: append a just-measured row to the trajectory,
    then gate THAT key against its recorded best — returns the failure
    lines (empty = fine), already printed loudly to stderr. A broken
    trajectory file raises: a bench run whose record was lost must not
    exit 0."""
    p = path or os.environ.get("BENCH_TRAJECTORY") \
        or default_trajectory_path()
    drop = (float(os.environ.get("BENCH_GATE_MAX_DROP",
                                 DEFAULT_MAX_DROP))
            if max_drop_frac is None else max_drop_frac)
    row = {"source": "live", "recorded_at": round(time.time(), 3),
           "metric": result.get("metric"),
           "value": float(result["value"]),
           "unit": result.get("unit", "")}
    for k in ("mode", "shape"):
        if result.get(k):
            row[k] = result[k]
    for k in EXTRA_FIELDS:
        if isinstance(result.get(k), (int, float)):
            row[k] = result[k]
    append_row(row, p)
    data = load_trajectory(p)
    keyed = [r for r in data["rows"] if row_key(r) == row_key(row)]
    failures, _ = check_rows(keyed, drop)
    for line in failures:
        print(line, file=sys.stderr)
    return failures


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fold", action="store_true",
                    help="rebuild the trajectory from BENCH_r0*.json")
    ap.add_argument("--check", action="store_true",
                    help="gate the latest row per metric key against "
                    "its recorded best")
    ap.add_argument("--trajectory", default=None,
                    help="trajectory path (default: repo-root "
                    "BENCH_trajectory.json)")
    ap.add_argument("--max-drop-frac", type=float,
                    default=float(os.environ.get("BENCH_GATE_MAX_DROP",
                                                 DEFAULT_MAX_DROP)),
                    help="fail when latest < best*(1-this) "
                    f"(default {DEFAULT_MAX_DROP})")
    ap.add_argument("--ignore-live", action="store_true",
                    help="gate only the recorded rounds, skipping "
                    "live bench-appended rows (what tier-1 uses)")
    args = ap.parse_args(argv)
    path = args.trajectory or default_trajectory_path()
    if not args.fold and not args.check:
        ap.print_help()
        return 2
    if args.fold:
        data = fold(out_path=path)
        keys = {row_key(r) for r in data["rows"]}
        print(f"perf_gate: folded {len(data['rows'])} rows "
              f"({len(keys)} metric keys) -> {path}")
    if args.check:
        return check(path, args.max_drop_frac,
                     ignore_live=args.ignore_live)
    return 0


if __name__ == "__main__":
    sys.exit(main())
