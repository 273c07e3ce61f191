#!/usr/bin/env python
"""Merge per-rank telemetry dumps into one deterministic fleet report.

A 3-worker dist run leaves three disjoint telemetry surfaces — three
jsonl logs, three crash reports, three live ops endpoints — and no way
to ask fleet-level questions ("which rank is the straggler?", "is rank
2 diverging?", "when did rank 1 die?") without hand-diffing files. This
tool is that missing merge:

    python tools/fleetstat.py rank0.jsonl rank1.jsonl rank2.jsonl
    python tools/fleetstat.py --scrape http://h0:9100 --scrape http://h1:9100
    python tools/fleetstat.py dumps/*.jsonl --json > FLEET.json

Inputs are auto-detected per file: a telemetry jsonl log (the ``meta``
first line carries rank/host/generation identity), a ``fleet.snapshot()``
JSON document, or a flight-recorder crash report. ``--scrape`` GETs
``/fleetz`` (+ ``/healthz``) from live ``telemetry.opsd`` endpoints.

The report is byte-identical across reruns of the same inputs (sorted
ranks, sorted series, no wall-clock reads):

* **per-rank step-time table** with cross-rank straggler attribution —
  which rank is slow, and which phase (data_wait/assemble/dispatch/
  device/other) carries the excess;
* **metric-divergence detection** — per-rank loss/eval-metric/grad-norm
  drift past a leave-one-out z-score threshold (a diverging rank means
  a bad data shard or silent corruption, not load);
* **training-health attribution** — each rank's ok/degraded/diverged
  state and fired rules from the ``train.health.*`` gauges, plus which
  rank's detector fired *first* (``train.health.first_firing`` carries
  the firing's step index, so the origin is ordered even after the
  blast radius trips every peer);
* **dead-rank timeline** — dump-staleness gaps (wall-clock meta),
  ``dead_node`` events from survivors, ``recovery.*`` counters and the
  re-exec generation per rank;
* **serving rollups** — fleet request/shed/queue/occupancy totals with
  per-rank breakdown.

The registry merge itself (counter sums, gauge min/max/mean, bucket-wise
histogram merge) is ``mxnet_tpu.telemetry.fleet.merge`` — this tool only
adapts file formats onto it and renders text. ``--json`` emits the
same report as a machine-readable document (``series`` holds
``step.wall.p99_over_p50``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DEFAULT_Z = 3.0
DEFAULT_GAP_S = 30.0
STRAGGLER_PCT = 20.0     # mean-wall excess over fleet median that flags
_DISPERSION_FLOOR = 0.05  # leave-one-out z denominator floor (fraction)

# divergence is judged on correctness-shaped series only (loss, eval
# metrics, monitored tensors, anomaly trips, the training-health plane's
# live per-step stats) — load-shaped series (queue depths, walls)
# differ across ranks legitimately
_DIVERGENCE_GAUGES = ("monitor.stat", "train.health.grad_norm",
                      "train.health.update_ratio", "train.health.loss")
_DIVERGENCE_COUNTERS = ("sentinel.anomalies", "train.health.firings")

_HEALTH_STATE_NAMES = {0: "ok", 1: "degraded", 2: "diverged"}


def _fleet_mod():
    from mxnet_tpu.telemetry import fleet
    return fleet


def _fmt_us(us):
    us = float(us)
    if us < 1000:
        return f"{us:.0f} us"
    if us < 1e6:
        return f"{us / 1e3:.1f} ms"
    return f"{us / 1e6:.2f} s"


# ---------------------------------------------------------------- loading
def _blank_rank(source):
    return {"rank": 0, "host": "", "generation": 0, "num_workers": 1,
            "source": source, "time_unix": None,
            "counters": [], "gauges": [], "histograms": [],
            "events": [], "steps": [], "had_meta": False}


def _hist_from_jsonl(rec):
    """jsonl/crash histogram record ({'buckets': {str(le): cum}}) ->
    schema-v1 histogram fields (sorted bound/count lists)."""
    buckets = rec.get("buckets") or {}
    pairs = sorted(((float(le), c) for le, c in buckets.items()),
                   key=lambda p: p[0])
    return {"buckets": [le for le, _c in pairs],
            "bucket_counts": [c for _le, c in pairs],
            "count": rec.get("count", 0), "sum": rec.get("sum", 0.0),
            "min": rec.get("min"), "max": rec.get("max"),
            "exemplars": rec.get("exemplars") or {}}


def _parse_jsonl(text, source):
    r = _blank_rank(source)
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        t = rec.get("type")
        if t == "meta":
            r["rank"] = int(rec.get("rank", 0))
            r["host"] = rec.get("host", "")
            r["generation"] = int(rec.get("generation", 0))
            r["num_workers"] = int(rec.get("num_workers", 1))
            r["time_unix"] = rec.get("time_unix")
            r["had_meta"] = True
        elif t == "event":
            r["events"].append(rec)
        elif t == "step":
            r["steps"].append(rec)
        elif t == "counter":
            r["counters"].append({"name": rec.get("name", "?"),
                                  "labels": rec.get("labels") or {},
                                  "value": rec.get("value", 0)})
        elif t == "gauge":
            r["gauges"].append({"name": rec.get("name", "?"),
                                "labels": rec.get("labels") or {},
                                "value": rec.get("value", 0.0)})
        elif t == "histogram":
            r["histograms"].append(
                {"name": rec.get("name", "?"),
                 "labels": rec.get("labels") or {},
                 **_hist_from_jsonl(rec)})
    return r


def _parse_snapshot(doc, source):
    r = _blank_rank(source)
    r["rank"] = int(doc.get("rank", 0))
    r["host"] = doc.get("host", "")
    r["generation"] = int(doc.get("generation", 0))
    r["num_workers"] = int(doc.get("num_workers", 1))
    r["time_unix"] = doc.get("time_unix")
    r["counters"] = list(doc.get("counters", ()))
    r["gauges"] = list(doc.get("gauges", ()))
    r["histograms"] = list(doc.get("histograms", ()))
    r["had_meta"] = True
    return r


def _series_records(by_series):
    out = []
    for series, value in (by_series or {}).items():
        name, _, rest = series.partition("{")
        labels = {}
        for part in rest.rstrip("}").split(","):
            if "=" in part:
                k, _, v = part.partition("=")
                labels[k.strip()] = v.strip().strip('"')
        out.append({"name": name, "labels": labels, "value": value})
    return out


def _parse_crash(doc, source):
    r = _blank_rank(source)
    r["rank"] = int(doc.get("rank", 0))
    r["host"] = doc.get("host", "")
    r["time_unix"] = doc.get("time_unix")
    r["had_meta"] = "rank" in doc
    env = doc.get("env") or {}
    try:
        r["generation"] = int(env.get("MXNET_RECOVERY_GENERATION", 0) or 0)
    except ValueError:
        pass
    metrics = doc.get("metrics") or {}
    r["counters"] = _series_records(metrics.get("counters"))
    r["gauges"] = _series_records(metrics.get("gauges"))
    hists = []
    for series, rec in (metrics.get("histograms") or {}).items():
        name, _, rest = series.partition("{")
        labels = {}
        for part in rest.rstrip("}").split(","):
            if "=" in part:
                k, _, v = part.partition("=")
                labels[k.strip()] = v.strip().strip('"')
        hists.append({"name": name, "labels": labels,
                      **_hist_from_jsonl(rec)})
    r["histograms"] = hists
    # ring records double as the event feed (dead_node / recovery.*)
    for rec in doc.get("ring") or []:
        kind = rec.get("kind", "")
        if kind == "dead_node" or kind.startswith("recovery."):
            r["events"].append({"type": "event", "kind": kind, **{
                k: v for k, v in rec.items() if k != "kind"}})
    return r


def load_file(path):
    """One per-rank record from a jsonl log / snapshot / crash report."""
    with open(path) as f:
        text = f.read()
    source = os.path.basename(path)
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict):
        if doc.get("type") == "crash_report":
            return _parse_crash(doc, source)
        if "counters" in doc and "schema" in doc:
            return _parse_snapshot(doc, source)
    return _parse_jsonl(text, source)


def scrape(url, timeout=5):
    """One per-rank record from a live ops endpoint (/fleetz +
    /healthz)."""
    import urllib.error
    import urllib.request

    base = url.rstrip("/")

    def get(route):
        try:
            with urllib.request.urlopen(base + route,
                                        timeout=timeout) as resp:
                return json.loads(resp.read().decode())
        except urllib.error.HTTPError as e:     # /healthz is 503 when
            try:                                 # unhealthy — still JSON
                return json.loads(e.read().decode())
            except Exception:
                return None
        except Exception:
            return None

    snap = get("/fleetz")
    if snap is None:
        raise OSError(f"no /fleetz at {base}")
    r = _parse_snapshot(snap, base)
    health = get("/healthz")
    if health is not None:
        r["health"] = health
        for dead in health.get("kvstore", {}).get("dead_nodes", []):
            r["events"].append({"type": "event", "kind": "dead_node",
                                "ranks": [dead]})
    return r


# ---------------------------------------------------------------- analysis
def _pct(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def step_table(ranks, fleet):
    """Per-rank step stats + straggler attribution.

    Prefers the per-step ``step`` records (exact walls + phase split);
    falls back to the ``module.fit.batch.seconds`` histogram when a dump
    carries only the registry."""
    per_rank = {}
    for r in sorted(ranks, key=lambda x: x["rank"]):
        key = str(r["rank"])
        walls = sorted(s.get("wall_us", 0) / 1e3 for s in r["steps"])
        if walls:
            p50 = _pct(walls, 0.50)
            p99 = _pct(walls, 0.99)
            phases = {}
            for s in r["steps"]:
                for p, us in (s.get("phases_us") or {}).items():
                    phases[p] = phases.get(p, 0.0) + us / 1e3
            n = len(walls)
            per_rank[key] = {
                "steps": n, "p50_ms": p50, "p99_ms": p99,
                "mean_ms": sum(walls) / n,
                "p99_over_p50": (p99 / p50) if p50 else None,
                "phase_mean_ms": {p: v / n for p, v in
                                  sorted(phases.items())}}
            continue
        for h in r["histograms"]:
            if h["name"] == "module.fit.batch.seconds" and h["count"]:
                p50 = (fleet.hist_quantile(h, 0.50) or 0) * 1e3
                p99 = (fleet.hist_quantile(h, 0.99) or 0) * 1e3
                per_rank[key] = {
                    "steps": h["count"], "p50_ms": p50, "p99_ms": p99,
                    "mean_ms": (h["sum"] / h["count"]) * 1e3,
                    "p99_over_p50": (p99 / p50) if p50 else None,
                    "phase_mean_ms": {}}
                break
    doc = {"per_rank": per_rank, "spread_p99_over_p50": None,
           "spread_rank": None, "straggler": None}
    spreads = [(v["p99_over_p50"], k) for k, v in per_rank.items()
               if v["p99_over_p50"] is not None]
    if spreads:
        spread, rank = max(spreads)
        doc["spread_p99_over_p50"] = spread
        doc["spread_rank"] = rank
    # straggler: a rank whose mean wall sits past the fleet median
    means = sorted((v["mean_ms"], k) for k, v in per_rank.items())
    if len(means) >= 2:
        med = means[len(means) // 2][0] if len(means) % 2 else \
            (means[len(means) // 2 - 1][0] + means[len(means) // 2][0]) / 2
        worst_ms, worst = means[-1]
        if med > 0 and (worst_ms - med) / med * 100.0 >= STRAGGLER_PCT:
            excess_pct = (worst_ms - med) / med * 100.0
            phase, phase_pct = None, 0.0
            worst_phases = per_rank[worst]["phase_mean_ms"]
            for p, v in worst_phases.items():
                others = sorted(per_rank[k]["phase_mean_ms"].get(p, 0.0)
                                for k in per_rank if k != worst)
                base = _pct(others, 0.5) or 0.0
                delta = (v - base) / med * 100.0
                if delta > phase_pct:
                    phase, phase_pct = p, delta
            doc["straggler"] = {"rank": worst, "excess_pct": excess_pct,
                                "phase": phase, "phase_pct": phase_pct}
    return doc


def _divergence_values(ranks):
    """{series: {rank: value}} over the correctness-shaped surfaces."""
    out = {}
    for r in ranks:
        key = str(r["rank"])
        for rec in r["gauges"]:
            if rec["name"] in _DIVERGENCE_GAUGES:
                inner = ",".join(
                    f'{k}="{v}"' for k, v in sorted(rec["labels"].items()))
                series = rec["name"] + (f"{{{inner}}}" if inner else "")
                out.setdefault(series, {})[key] = float(rec["value"])
        for rec in r["counters"]:
            if rec["name"] in _DIVERGENCE_COUNTERS:
                out.setdefault(rec["name"], {})[key] = float(rec["value"])
        last = {}
        for e in r["events"]:
            if e.get("kind") != "epoch_end":
                continue
            for k, v in e.items():
                if k in ("type", "kind", "ts_us", "epoch"):
                    continue
                if "time" in k or k.endswith("_s"):
                    continue    # wall-time keys are load, not correctness
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                last[f"epoch_end.{k}"] = float(v)
        for series, v in last.items():
            out.setdefault(series, {})[key] = v
    return out


def divergence(ranks, z_threshold=DEFAULT_Z):
    """Leave-one-out z-score drift over loss/eval/monitor series.

    For each rank's value the reference is the *other* ranks' mean, and
    the denominator is their std floored at 5% of the reference mean —
    a plain z-score saturates at (n-1)/sqrt(n) for small fleets (3
    ranks cap at |z|=1.15), so an outlier could never cross a 3.0
    threshold; the leave-one-out form has no such cap."""
    flags = []
    for series, by_rank in sorted(_divergence_values(ranks).items()):
        if len(by_rank) < 3:
            continue
        for rank in sorted(by_rank, key=int):
            v = by_rank[rank]
            others = [by_rank[k] for k in by_rank if k != rank]
            mean = sum(others) / len(others)
            var = sum((o - mean) ** 2 for o in others) / len(others)
            floor = max(var ** 0.5, _DISPERSION_FLOOR * abs(mean), 1e-12)
            z = (v - mean) / floor
            if abs(z) >= z_threshold:
                flags.append({"series": series, "rank": rank,
                              "value": v, "fleet_mean": mean, "z": z})
    return flags


def train_health(ranks):
    """Per-rank training-health attribution from the ``train.health.*``
    gauges every snapshot/jsonl/crash dump carries: each rank's
    ok/degraded/diverged state, its fired rules, and — the question an
    operator actually asks — WHICH rank's detector fired first.
    ``train.health.first_firing{rule=...}`` records the observation
    (step) index of a rule's first firing on that rank, so the fleet
    minimum names the sick rank even when the blast radius later trips
    every peer."""
    doc = {"by_rank": {}, "first": None}
    for r in ranks:
        key = str(r["rank"])
        state = None
        rules = {}
        for rec in r["gauges"]:
            if rec["name"] == "train.health.state":
                state = int(rec["value"])
            elif rec["name"] == "train.health.first_firing":
                rule = rec["labels"].get("rule", "?")
                rules[rule] = int(rec["value"])
        if state is None and not rules:
            continue
        state = state or 0
        doc["by_rank"][key] = {
            "state": state,
            "name": _HEALTH_STATE_NAMES.get(state, str(state)),
            "rules": rules}
    firsts = [(n, key, rule)
              for key, rec in doc["by_rank"].items()
              for rule, n in rec["rules"].items()]
    if firsts:
        n, rank, rule = min(firsts)
        doc["first"] = {"rank": rank, "rule": rule, "observation": n}
    return doc


def dead_rank_timeline(ranks, gap_seconds=DEFAULT_GAP_S):
    """Stale dumps + survivor-reported deaths + recovery counters."""
    doc = {"stale_ranks": [], "lag_seconds": {}, "reported_dead": [],
           "events": [], "recovery": {}, "generations": {}}
    stamped = [(r["time_unix"], str(r["rank"])) for r in ranks
               if r["time_unix"] is not None]
    if stamped:
        newest = max(t for t, _r in stamped)
        for t, rank in sorted(stamped, key=lambda x: (x[1], x[0])):
            lag = newest - t
            doc["lag_seconds"][rank] = round(lag, 3)
            if lag > gap_seconds:
                doc["stale_ranks"].append(rank)
    reported = set()
    for r in sorted(ranks, key=lambda x: x["rank"]):
        for e in r["events"]:
            kind = e.get("kind", "")
            if kind == "dead_node" or kind.startswith("recovery."):
                dead = e.get("ranks") or e.get("dead") or []
                if isinstance(dead, (int, float, str)):
                    dead = [dead]
                reported.update(str(int(d)) for d in dead
                                if f"{d}".lstrip("-").isdigit())
                doc["events"].append(
                    {"observer": str(r["rank"]), "kind": kind,
                     **{k: v for k, v in e.items()
                        if k not in ("type", "kind", "ts_us")}})
        counts = {}
        for rec in r["counters"]:
            if rec["name"].startswith("recovery."):
                short = rec["name"][len("recovery."):]
                counts[short] = counts.get(short, 0) + rec["value"]
        if counts:
            doc["recovery"][str(r["rank"])] = counts
        doc["generations"][str(r["rank"])] = r["generation"]
    doc["reported_dead"] = sorted(reported, key=int)
    return doc


def serving_rollup(ranks, merged):
    """Fleet serving/decode rollups from the merged registry."""
    doc = {"counters": {}, "queue_depth_by_rank": {},
           "occupancy_mean": None}
    wanted = ("serve.requests", "serve.responses", "serve.shed",
              "serve.rejected", "serve.errors", "serve.decode.requests",
              "serve.decode.responses", "serve.decode.tokens",
              "serve.decode.migrations")
    for key, slot in merged.get("counters", {}).items():
        if slot["name"] in wanted:
            doc["counters"][key] = {"total": slot["total"],
                                    "by_rank": dict(slot["by_rank"])}
    occs = []
    for key, slot in merged.get("gauges", {}).items():
        if slot["name"].endswith("queue.depth"):
            for rank, v in slot["by_rank"].items():
                doc["queue_depth_by_rank"][rank] = \
                    doc["queue_depth_by_rank"].get(rank, 0) + v
        elif slot["name"] in ("serve.batch.occupancy",
                              "serve.decode.occupancy"):
            occs.extend(slot["by_rank"].values())
    if occs:
        doc["occupancy_mean"] = sum(occs) / len(occs)
    return doc


# ------------------------------------------------------------------ report
def build(ranks, z_threshold=DEFAULT_Z, gap_seconds=DEFAULT_GAP_S):
    """All analyses over loaded per-rank records -> one fleet document."""
    fleet = _fleet_mod()
    ranks = sorted(ranks, key=lambda r: (r["rank"], r["source"]))
    snaps = [{"schema": fleet.SCHEMA_VERSION, "rank": r["rank"],
              "host": r["host"], "num_workers": r["num_workers"],
              "generation": r["generation"], "counters": r["counters"],
              "gauges": r["gauges"], "histograms": r["histograms"]}
             for r in ranks]
    merged = fleet.merge(snaps)
    steps = step_table(ranks, fleet)
    doc = {
        "schema": fleet.SCHEMA_VERSION,
        "ranks": merged["ranks"],
        "sources": {str(r["rank"]): r["source"] for r in ranks},
        "hosts": merged["hosts"],
        "generations": {str(r["rank"]): r["generation"] for r in ranks},
        "step": steps,
        "divergence": divergence(ranks, z_threshold),
        "train_health": train_health(ranks),
        "dead": dead_rank_timeline(ranks, gap_seconds),
        "serving": serving_rollup(ranks, merged),
        "merged": merged,
        "series": {},
    }
    if steps["spread_p99_over_p50"] is not None:
        doc["series"]["step.wall.p99_over_p50"] = \
            steps["spread_p99_over_p50"]
    if doc["train_health"]["by_rank"]:
        # worst rank's health state as a fleet series (0 ok /
        # 1 degraded / 2 diverged)
        doc["series"]["train.health.state.max"] = float(max(
            rec["state"] for rec in doc["train_health"]["by_rank"].values()))
    return doc


def render(doc, z_threshold=DEFAULT_Z, gap_seconds=DEFAULT_GAP_S):
    """Fleet document -> deterministic report text."""
    out = ["=" * 64, f"FLEET REPORT — {len(doc['ranks'])} rank(s)",
           "=" * 64]
    for rank in doc["ranks"]:
        r = str(rank)
        out.append(f"rank {r}  host {doc['hosts'].get(r) or '?'}  "
                   f"gen {doc['generations'].get(r, 0)}  "
                   f"source {doc['sources'].get(r, '?')}")
    out.append("")

    steps = doc["step"]
    if steps["per_rank"]:
        out.append("step times:")
        out.append(f"  {'rank':<6}{'steps':>7}{'p50':>12}{'p99':>12}"
                   f"{'p99/p50':>10}")
        for rank in sorted(steps["per_rank"], key=int):
            s = steps["per_rank"][rank]
            spread = f"{s['p99_over_p50']:.2f}" \
                if s["p99_over_p50"] is not None else "?"
            out.append(
                f"  {rank:<6}{s['steps']:>7}"
                f"{_fmt_us(s['p50_ms'] * 1e3):>12}"
                f"{_fmt_us(s['p99_ms'] * 1e3):>12}{spread:>10}")
        if steps["spread_p99_over_p50"] is not None:
            out.append(f"  fleet spread: max p99/p50 "
                       f"{steps['spread_p99_over_p50']:.2f} "
                       f"(rank {steps['spread_rank']})")
        st = steps["straggler"]
        if st:
            phase = f" — dominated by {st['phase']} " \
                    f"(+{st['phase_pct']:.1f}% of median wall)" \
                if st["phase"] else ""
            out.append(f"  STRAGGLER: rank {st['rank']} "
                       f"+{st['excess_pct']:.1f}% mean wall vs fleet "
                       f"median{phase}")
        else:
            out.append("  no straggler flagged")
    else:
        out.append("step times: no step records or batch histograms")
    out.append("")

    out.append(f"metric divergence (leave-one-out |z| >= "
               f"{z_threshold:g}):")
    if doc["divergence"]:
        for f in doc["divergence"]:
            out.append(f"  RANK {f['rank']} DIVERGING: {f['series']} = "
                       f"{f['value']:g} vs fleet mean "
                       f"{f['fleet_mean']:g} (z={f['z']:+.1f})")
    else:
        out.append("  none")
    out.append("")

    th = doc.get("train_health") or {}
    if th.get("by_rank"):
        out.append("training health:")
        for rank in sorted(th["by_rank"], key=int):
            rec = th["by_rank"][rank]
            rules = ", ".join(
                f"{rule}@{rec['rules'][rule]}"
                for rule in sorted(rec["rules"],
                                   key=lambda x: rec["rules"][x])) \
                or "no rules fired"
            tag = rec["name"].upper() if rec["state"] else rec["name"]
            out.append(f"  rank {rank}: {tag} ({rules})")
        if th.get("first"):
            f = th["first"]
            out.append(f"  FIRST DIVERGED: rank {f['rank']} — "
                       f"{f['rule']} at observation {f['observation']}")
        out.append("")

    dead = doc["dead"]
    out.append("dead-rank timeline:")
    lines_before = len(out)
    for rank in sorted(dead["lag_seconds"], key=int):
        lag = dead["lag_seconds"][rank]
        if rank in dead["stale_ranks"]:
            out.append(f"  rank {rank}: last dump {lag:.1f}s behind the "
                       f"newest — STALE (heartbeat gap > "
                       f"{gap_seconds:g}s)")
        elif lag > 0:
            out.append(f"  rank {rank}: last dump {lag:.1f}s behind "
                       f"the newest")
    if dead["reported_dead"]:
        out.append(f"  reported dead by survivors: rank(s) "
                   f"{', '.join(dead['reported_dead'])}")
    for e in dead["events"][:8]:
        desc = {k: v for k, v in e.items() if k not in ("observer",
                                                        "kind")}
        out.append(f"  rank {e['observer']} saw {e['kind']} {desc}")
    for rank in sorted(dead["recovery"], key=int):
        counts = dead["recovery"][rank]
        inner = ", ".join(f"{k}={int(v)}" for k, v in
                          sorted(counts.items()))
        out.append(f"  rank {rank} recovery counters: {inner}")
    gens = {r: g for r, g in dead["generations"].items() if g}
    if gens:
        out.append("  re-exec generations: " + ", ".join(
            f"rank {r} gen {gens[r]}" for r in sorted(gens, key=int)))
    if len(out) == lines_before:
        out.append("  all ranks current; no deaths reported")
    out.append("")

    serving = doc["serving"]
    if (serving["counters"] or serving["queue_depth_by_rank"] or
            serving["occupancy_mean"] is not None):
        out.append("serving rollup:")
        for key in sorted(serving["counters"]):
            slot = serving["counters"][key]
            per = ", ".join(
                f"rank {r}: {slot['by_rank'][r]:g}"
                for r in sorted(slot["by_rank"], key=int))
            out.append(f"  {key}: {slot['total']:g} ({per})")
        if serving["queue_depth_by_rank"]:
            per = ", ".join(
                f"rank {r}: {serving['queue_depth_by_rank'][r]:g}"
                for r in sorted(serving["queue_depth_by_rank"], key=int))
            out.append(f"  queue depth: {per}")
        if serving["occupancy_mean"] is not None:
            out.append(f"  occupancy mean: "
                       f"{serving['occupancy_mean']:.1%}")
        out.append("")

    fleet = _fleet_mod()
    wall = None
    for key, slot in doc["merged"]["histograms"].items():
        if slot["name"] == "module.fit.batch.seconds":
            wall = slot["merged"]
    if wall and wall["count"]:
        p50 = fleet.hist_quantile(wall, 0.50)
        p99 = fleet.hist_quantile(wall, 0.99)
        out.append(f"fleet batch wall (merged histogram): p50 "
                   f"{_fmt_us((p50 or 0) * 1e6)} / p99 "
                   f"{_fmt_us((p99 or 0) * 1e6)} over "
                   f"{wall['count']} batches")
    n_series = (len(doc["merged"]["counters"]) +
                len(doc["merged"]["gauges"]) +
                len(doc["merged"]["histograms"]))
    out.append(f"merged registry: {n_series} series across "
               f"{len(doc['ranks'])} rank(s)")
    return "\n".join(out)


# -------------------------------------------------------------------- main
def main(argv=None):
    p = argparse.ArgumentParser(
        description="Merge per-rank telemetry dumps (jsonl / snapshot / "
                    "crash report) or live endpoints into one fleet "
                    "report.")
    p.add_argument("files", nargs="*",
                   help="per-rank dump files (format auto-detected)")
    p.add_argument("--scrape", action="append", default=[],
                   metavar="URL",
                   help="live ops endpoint base URL (repeatable)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the machine-readable fleet document")
    p.add_argument("--z-threshold", type=float, default=DEFAULT_Z,
                   help=f"divergence flag threshold "
                        f"(default {DEFAULT_Z})")
    p.add_argument("--gap-seconds", type=float, default=DEFAULT_GAP_S,
                   help=f"dump staleness considered a heartbeat gap "
                        f"(default {DEFAULT_GAP_S:g}s)")
    args = p.parse_args(argv)
    if not args.files and not args.scrape:
        p.error("give dump files and/or --scrape URLs")

    ranks = []
    for path in args.files:
        try:
            ranks.append(load_file(path))
        except OSError as e:
            print(f"fleetstat: {path}: {e}", file=sys.stderr)
            return 2
    for url in args.scrape:
        try:
            ranks.append(scrape(url))
        except OSError as e:
            print(f"fleetstat: {e}", file=sys.stderr)
            return 2
    if not ranks:
        print("fleetstat: nothing loaded", file=sys.stderr)
        return 2

    doc = build(ranks, z_threshold=args.z_threshold,
                gap_seconds=args.gap_seconds)
    if args.as_json:
        slim = {k: v for k, v in doc.items() if k != "merged"}
        print(json.dumps(slim, indent=2, sort_keys=True))
    else:
        print(render(doc, z_threshold=args.z_threshold,
                     gap_seconds=args.gap_seconds))
    return 0


if __name__ == "__main__":
    sys.exit(main())
