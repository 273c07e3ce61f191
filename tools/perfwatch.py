#!/usr/bin/env python
"""Perf-regression watchdog over bench payloads + benchmark results.

The perf trajectory is product surface the same way correctness is.
This tool makes a perf-shaped regression fail CI the way a lint rule
does:

* **bench history** (``BENCH_r*.json``, driver format ``{"parsed":
  {...}}`` or a raw bench.py payload / stdout tail): per metric
  *series*, the newest run carrying the series is compared against the
  best prior run, within a tolerance (default 25%). An empty history
  is no regression: the recorded gates below still decide. Series are
  keyed by the payload's ``metric`` name, so a methodology change
  starts a fresh series instead of flagging a fake collapse. Variant rows (serve req/s, int8 speedup, lm tokens/s,
  ckpt stall ratio, ...) are series of their own.
* **results gates** (``benchmarks/results/*.json``): files that carry
  their own acceptance gates — boolean ``gate_*``/``*_pass`` flags and
  ``gate_pct`` thresholds over ``*_overhead_pct`` measurements — are
  re-checked, so a stale-but-failing recorded result cannot sit green.
* **fleet reports** (``--fleet``, repeatable): ``tools/fleetstat.py
  --json`` documents appended as runs of their own — the fleet-health
  series (``step.wall.p99_over_p50``, the cross-rank straggler
  spread) is tracked like any bench series, so a widening p99/p50 gap
  across sessions regresses CI the same way a throughput drop does.

Exit codes: 0 = no regressions, 1 = regressions/gate failures (each
listed on stdout), 2 = unusable input. ``--check`` runs the repo
defaults — the in-process tier-1 gate next to ``mxlint --check``.

Usage::

    python tools/perfwatch.py --check
    python tools/perfwatch.py --check --payload new_bench_stdout.json
    python tools/perfwatch.py --check --fleet fleet_r01.json --fleet fleet_r02.json
    python tools/perfwatch.py --history /path/to/BENCH_dir --tolerance 0.1
    python tools/perfwatch.py --json --check

Pure stdlib — runs anywhere the repo checks out.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_TOLERANCE = 0.25

# payload sub-metrics tracked as their own series: (path, direction)
# direction "up" = bigger is better, "down" = smaller is better
VARIANT_PATHS = [
    (("serve", "req_per_sec"), "up"),
    (("serve", "latency_ms", "p99"), "down"),
    (("quant", "int8_speedup"), "up"),
    (("lm", "train_tokens_per_sec"), "up"),
    (("lm", "decode_tokens_per_sec"), "up"),
    (("lm", "max_context"), "up"),
    (("decode_batch", "slots1_tokens_per_sec"), "up"),
    (("decode_batch", "slots8_tokens_per_sec"), "up"),
    (("decode_batch", "speedup_8v1"), "up"),
    (("decode_batch", "ttft_2048_ms"), "down"),
    (("decode_batch", "spec_speedup"), "up"),
    (("decode_batch", "prefix_hit_rate"), "up"),
    (("spmd", "spmd_vs_kvstore"), "up"),
    (("ckpt", "exposed_ratio"), "down"),
    (("lm_mfu", "train_mfu_pct"), "up"),
    (("lm_mfu", "decode_fp8_tokens_per_sec"), "up"),
    (("lm_mfu", "decode_attn_speedup"), "up"),
]

# per-series tolerance overrides (substring match on the series name);
# CPU-fallback variant rows ride shared CI machines and are noisier
TOLERANCES = {
    "_cpu_fallback": 0.5,
}

_ROUND_RE = re.compile(r"r(\d+)")

# fleetstat --json series and whether bigger is better; anything the
# report grows later defaults to "down" (fleet-health series are
# spread/imbalance shaped: smaller is healthier)
FLEET_SERIES_DIRECTIONS = {
    "step.wall.p99_over_p50": "down",
    # worst-rank training-health state (0 ok / 1 degraded / 2 diverged)
    # from the health plane via fleetstat --json
    "train.health.state.max": "down",
}


# --------------------------------------------------------------- loading
def load_payload(path):
    """A bench payload dict from any of the shapes the driver leaves:
    the ``{"parsed": {...}}`` BENCH_r record, a raw payload object, or
    text whose last JSON line is the payload. None when unusable."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return None
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
        for line in reversed(text.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    doc = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
    if not isinstance(doc, dict):
        return None
    if isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    return doc if "metric" in doc else None


def _round_of(path):
    m = _ROUND_RE.findall(os.path.basename(path))
    return int(m[-1]) if m else None


def extract_series(payload):
    """{series_name: (value, direction)} for one payload's tracked
    metrics. Null / missing / error'd rows are skipped — an absent
    measurement is a coverage gap, not a regression."""
    out = {}
    metric = str(payload.get("metric", "?"))
    v = payload.get("value")
    if isinstance(v, (int, float)):
        out[metric] = (float(v), "up")
    for path, direction in VARIANT_PATHS:
        node = payload
        for key in path:
            node = node.get(key) if isinstance(node, dict) else None
            if node is None:
                break
        if isinstance(node, bool) or not isinstance(node, (int, float)):
            continue
        out[f"{metric}.{'.'.join(path)}"] = (float(node), direction)
    return out


def load_history(history_dir=None, extra_payloads=()):
    """Ordered [(tag, {series: (value, dir)})] — BENCH_r*.json rounds
    ascending, then any explicitly passed payloads (newest last)."""
    runs = []
    d = history_dir or REPO
    paths = sorted(glob.glob(os.path.join(d, "BENCH_r*.json")),
                   key=lambda p: (_round_of(p) or 0, p))
    for p in paths:
        payload = load_payload(p)
        if payload is not None:
            runs.append((os.path.basename(p), extract_series(payload)))
    for p in extra_payloads:
        payload = load_payload(p)
        if payload is None:
            raise ValueError(f"--payload {p}: not a bench payload")
        runs.append((os.path.basename(p), extract_series(payload)))
    return runs


def load_fleet_reports(paths):
    """[(tag, {series: (value, dir)})] from fleetstat --json reports —
    one run per report, series prefixed ``fleet.`` so they never
    collide with bench metric names."""
    runs = []
    for p in paths:
        try:
            with open(p) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            raise ValueError(f"--fleet {p}: not a fleetstat --json "
                             "report")
        series = doc.get("series") if isinstance(doc, dict) else None
        if not isinstance(series, dict):
            raise ValueError(f"--fleet {p}: no series block (produce "
                             "it with tools/fleetstat.py --json)")
        out = {}
        for name in sorted(series):
            val = series[name]
            if isinstance(val, bool) or not isinstance(val, (int, float)):
                continue
            direction = FLEET_SERIES_DIRECTIONS.get(name, "down")
            out[f"fleet.{name}"] = (float(val), direction)
        runs.append((os.path.basename(p), out))
    return runs


# ------------------------------------------------------------ comparison
def _tolerance_for(series, default):
    for sub, tol in TOLERANCES.items():
        if sub in series:
            return max(tol, default)
    return default


def compare_history(runs, tolerance=DEFAULT_TOLERANCE):
    """Regressions: for every series, the newest run carrying it vs the
    best earlier run carrying it. First samples pass vacuously."""
    regressions = []
    series_names = {}
    for _tag, series in runs:
        series_names.update({k: None for k in series})
    for name in series_names:
        samples = [(tag, series[name][0], series[name][1])
                   for tag, series in runs if name in series]
        if len(samples) < 2:
            continue
        tag, current, direction = samples[-1]
        prior = samples[:-1]
        if direction == "up":
            best_tag, best = max(((t, v) for t, v, _ in prior),
                                 key=lambda x: x[1])
        else:
            best_tag, best = min(((t, v) for t, v, _ in prior),
                                 key=lambda x: x[1])
        tol = _tolerance_for(name, tolerance)
        bad = (current < best * (1.0 - tol) if direction == "up"
               else current > best * (1.0 + tol))
        if bad:
            regressions.append({
                "kind": "history", "series": name, "current": current,
                "current_run": tag, "best": best, "best_run": best_tag,
                "direction": direction, "tolerance": tol})
    return regressions


# ---------------------------------------------------------- result gates
_GATED_PCT_KEY = re.compile(
    r"(analytic_overhead_pct|warm_overhead_pct)$")


def check_result_gates(results_dir=None):
    """Re-check the acceptance gates recorded inside
    benchmarks/results/*.json: boolean ``gate_*``/``*_pass`` flags must
    be truthy, and every ``*analytic_overhead_pct`` /
    ``warm_overhead_pct`` must sit under its dict's ``gate_pct``."""
    failures = []
    d = results_dir if results_dir is not None else \
        os.path.join(REPO, "benchmarks", "results")

    def walk(node, fname, where):
        if not isinstance(node, dict):
            return
        gate_pct = node.get("gate_pct")
        for key, val in node.items():
            here = f"{where}.{key}" if where else key
            if isinstance(val, dict):
                walk(val, fname, here)
                continue
            if isinstance(val, bool) and \
                    (key.startswith("gate_") or key.endswith("_pass")):
                if not val:
                    failures.append({"kind": "gate", "file": fname,
                                     "key": here, "value": val,
                                     "reason": "recorded gate is false"})
            elif isinstance(gate_pct, (int, float)) and \
                    isinstance(val, (int, float)) and \
                    _GATED_PCT_KEY.search(key):
                if val >= gate_pct:
                    failures.append({
                        "kind": "gate", "file": fname, "key": here,
                        "value": val, "gate_pct": gate_pct,
                        "reason": f"{val:.3f}% >= {gate_pct}% gate"})

    for path in sorted(glob.glob(os.path.join(d, "*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            failures.append({"kind": "gate", "file": path, "key": "",
                             "value": None, "reason": "unreadable"})
            continue
        walk(doc if isinstance(doc, dict) else {},
             os.path.basename(path), "")
    return failures


# ------------------------------------------------------------------ main
def run(history_dir=None, results_dir=None, payloads=(),
        tolerance=DEFAULT_TOLERANCE, check_gates=True,
        fleet_reports=()):
    """The whole watchdog pass; returns (regressions, n_series, n_runs)."""
    runs = load_history(history_dir, payloads)
    runs += load_fleet_reports(fleet_reports)
    regressions = compare_history(runs, tolerance)
    if check_gates:
        regressions += check_result_gates(results_dir)
    n_series = len({name for _t, s in runs for name in s})
    return regressions, n_series, len(runs)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="Fail on perf regressions across bench history and "
                    "recorded benchmark gates.")
    p.add_argument("--check", action="store_true",
                   help="run the repo-default watchdog pass (the CI "
                        "gate; implied when no other input is given)")
    p.add_argument("--payload", action="append", default=[],
                   metavar="FILE",
                   help="bench payload(s) to append as the newest "
                        "run(s) — a bench.py stdout capture works")
    p.add_argument("--fleet", action="append", default=[],
                   metavar="FILE",
                   help="fleetstat --json report(s) to append as runs "
                        "— tracks the fleet-health series "
                        "(step.wall.p99_over_p50) across sessions")
    p.add_argument("--history", default=None, metavar="DIR",
                   help="directory holding BENCH_r*.json "
                        "(default: the repo root)")
    p.add_argument("--results", default=None, metavar="DIR",
                   help="benchmarks/results dir for the recorded-gate "
                        "re-check (default: the repo's)")
    p.add_argument("--no-gates", action="store_true",
                   help="skip the benchmarks/results gate re-check")
    p.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                   help="relative regression tolerance "
                        f"(default {DEFAULT_TOLERANCE})")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable report on stdout")
    args = p.parse_args(argv)

    try:
        regressions, n_series, n_runs = run(
            history_dir=args.history, results_dir=args.results,
            payloads=args.payload, tolerance=args.tolerance,
            check_gates=not args.no_gates, fleet_reports=args.fleet)
    except ValueError as exc:
        print(f"perfwatch: {exc}", file=sys.stderr)
        return 2

    if args.as_json:
        print(json.dumps({"runs": n_runs, "series": n_series,
                          "regressions": regressions}, indent=2))
    else:
        for r in regressions:
            if r["kind"] == "history":
                arrow = "below best" if r["direction"] == "up" \
                    else "above best"
                print(f"REGRESSION {r['series']}: {r['current']:g} "
                      f"({r['current_run']}) {arrow} {r['best']:g} "
                      f"({r['best_run']}) beyond "
                      f"{r['tolerance'] * 100:.0f}% tolerance")
            else:
                print(f"GATE FAIL {r['file']}: {r['key']} — "
                      f"{r['reason']}")
        status = "FAIL" if regressions else "OK"
        print(f"perfwatch {status}: {n_series} series over {n_runs} "
              f"runs, {len(regressions)} regression(s)")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
