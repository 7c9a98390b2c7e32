"""perf-report: the bench-artifact trajectory and its regression gate.

The repo root has accumulated five rounds of bench artifacts in four
generations of ad-hoc shapes (driver ``{"parsed": ...}`` wrappers,
JSONL lane files, ``{"lanes": [...]}`` sweeps, ``{"rules", "points"}``
service sweeps) — and the one question that matters each round ("did
the code get slower, or did the environment change?") had to be
re-derived by hand. Round 5's 40× "regression" was a ~100ms
host↔device round trip; the evidence (``device_rtt_ms``) was on the
artifact, but nothing read it.

This module is the reader:

* **normalize** every ``BENCH_*`` / ``MULTICHIP_*`` / ``SERVICE_*``
  artifact — all legacy shapes plus the versioned ``bench_schema``
  lines new benches emit (``runtime/provenance.py``) — into one entry
  schema;
* **build the trajectory**: per metric, the best value per round with
  its provenance/environment markers;
* **diff rounds and classify** each worsening beyond the threshold as
  *environment change* (provenance mismatch, cpu↔accelerator hint, or
  an RTT signal that moved ≥4×) vs *code regression* (no environment
  signal explains it);
* **gate CI**: exit non-zero when the NEWEST round transition contains
  an unexplained code regression (historic transitions are reported
  but do not fail — they are already shipped history), when a staging
  metric busts the absolute ``--stage-budget-ms`` budget, or when a
  sharded multichip lane records more ledger collectives per compiled
  block than the budget it declared on the bench line
  (:func:`collective_budget_violations` — the structural guard
  against a per-byte-collective regression), or when a lane's
  measured provenance-consumption overhead exceeds the budget it
  declared (:func:`provenance_budget_violations` — the ≤2%
  explain-plane cost contract).

Faces: ``cilium-tpu perf-report``, ``python -m cilium_tpu.perf_report``,
``make perf-report`` (writes ``PERF_TRAJECTORY.json``, part of
``make check``). Docs: docs/OBSERVABILITY.md "Bench provenance & the
perf trajectory".
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

from cilium_tpu.runtime.provenance import BENCH_SCHEMA

#: PERF_TRAJECTORY.json schema version
TRAJECTORY_SCHEMA = 1

#: artifact filename globs the report consumes (repo root)
ARTIFACT_GLOBS = ("BENCH_*.json", "BENCH_*.jsonl", "MULTICHIP_*.json",
                  "SERVICE_*.json")

#: a worsening beyond this factor-over-1 needs an explanation
#: (0.5 = more than 1.5× slower round-over-round)
DEFAULT_THRESHOLD = 0.5

#: two RTT signals this far apart (×) explain any slowdown as
#: environment — a remote transport appearing/disappearing moves RTT
#: by 100×+
RTT_FACTOR = 4.0

_ROUND_RE = re.compile(r"_r(\d+)([a-z]?)")
_BACKEND_HINT_RE = re.compile(r"Platform '(\w+)' is experimental")
#: transient-infrastructure error smells (the r05 kafka lane's
#: ``remote_compile`` connection reset is the type specimen)
TRANSIENT_RE = re.compile(
    r"connection reset|connection dropped|read body|UNAVAILABLE|"
    r"DEADLINE_EXCEEDED|timed out|Connection refused|EOF|"
    r"ConnectionResetError|ConnectionError|BrokenPipe", re.I)


# -- normalized entry -------------------------------------------------------

def _round_of(filename: str) -> Tuple[Optional[int], str]:
    """``BENCH_ALL_cpu_r04b.json`` → (4, "r04b")."""
    m = _ROUND_RE.search(filename)
    if m is None:
        return None, ""
    return int(m.group(1)), f"r{m.group(1).zfill(2)}{m.group(2)}"


def _direction(unit: str, metric: str) -> str:
    u = (unit or "").lower()
    if "/s" in u or "efficiency" in u:
        return "higher"
    if "ms" in u:
        return "lower"
    if metric.startswith(("service_", "policy_regen")):
        return "lower"
    return "higher"


_EXTRA_KEYS = ("device_rtt_ms", "device_rtt_max_ms", "stage_ms",
               "stage_phases_ms", "p50_ms", "p99_ms",
               "device_verdicts_per_sec", "capture_records",
               "unique_rows", "stream", "chunk", "cardinality",
               "platform", "attribution", "compile_ms", "lane",
               "attempts", "transient", "memo", "memo_fill_ms",
               "memo_hits", "memo_misses", "dedup_ratio",
               "stage_warm_ms", "stage_warm_phases_ms",
               "capture_write_ms", "capture_open_ms",
               "provenance_overhead_pct", "provenance_budget_pct",
               # serve-fleet lane (ISSUE 16): the failover trajectory
               "hosts", "handoffs", "host_deaths", "rejoins",
               "spilled_streams", "shed_rate", "p99_ratio",
               "rejoin_warm_restores",
               # fleet observability plane (ISSUE 17): trace
               # stitching, flow export, journal, obs-overhead budget
               "stitch_coverage", "handoff_replays",
               "flows_aggregated", "flow_keys", "journal_events",
               "failover_p99_ms", "obs_overhead_pct",
               "obs_budget_pct",
               # canary lane (ISSUE 20): shadow double-dispatch cost
               # and the verdict-diff gate's evidence
               "canary_overhead_pct", "canary_budget_pct",
               "canary_samples", "canary_diffs", "diff_caught",
               "diff_fraction", "bad_verdicts_served")


def _entry(source: str, kind: str, obj: Dict,
           env_hint: Optional[str], metric: Optional[str] = None,
           value=None, unit: Optional[str] = None) -> Dict:
    metric = metric if metric is not None else obj.get("metric", "")
    unit = unit if unit is not None else obj.get("unit", "")
    value = value if value is not None else obj.get("value")
    rnd, label = _round_of(source)
    failed = isinstance(metric, str) and metric.startswith("bench_failed")
    extras = {k: obj[k] for k in _EXTRA_KEYS if k in obj}
    return {
        "schema": TRAJECTORY_SCHEMA,
        "source": source,
        "round": rnd,
        "round_label": label,
        "kind": kind,
        "metric": metric,
        "value": value,
        "unit": unit,
        "direction": _direction(unit, metric or ""),
        "status": "failed" if failed else "ok",
        "error": obj.get("error"),
        "env_hint": env_hint,
        "extras": extras,
        "provenance": obj.get("provenance"),
        "bench_schema": obj.get("bench_schema"),
    }


def _env_hint(filename: str, tail: str = "") -> Optional[str]:
    if "cpu" in filename.lower():
        return "cpu"
    m = _BACKEND_HINT_RE.search(tail or "")
    if m:
        return m.group(1)
    return None


def _service_points(source: str, points: List[Dict],
                    env_hint: Optional[str],
                    artifact: Optional[Dict] = None) -> List[Dict]:
    pipelined = "_pipelined" in source
    carry = {}  # artifact-level provenance rides every point entry
    if artifact:
        carry = {k: artifact[k] for k in ("provenance", "bench_schema")
                 if k in artifact}
    out = []
    for pt in points:
        pt = dict(pt, **carry)
        lane = pt.get("lane")
        suffix = "_pipelined" if pipelined else ""
        if lane == "stream":
            metric = (f"service_stream_p99_"
                      f"{int(pt.get('offered_records_s', 0))}rps")
        elif lane == "open_loop":
            metric = (f"service_open_p99_d"
                      f"{pt.get('deadline_ms')}ms_"
                      f"{int(pt.get('offered_rps', 0))}rps")
        elif lane == "cpp_shim_kafka":
            metric = "service_shim_kafka_p99"
        elif pt.get("failed"):
            out.append(_entry(source, "service", dict(pt, error=pt.get(
                "error"), metric=f"bench_failed_service_{lane}"),
                env_hint, unit="point failed"))
            continue
        else:
            metric = f"service_closed_p99_d{pt.get('deadline_ms')}ms"
        if not pt.get("samples"):
            continue  # no quantile — nothing comparable on this point
        out.append(_entry(source, "service", pt, env_hint,
                          metric=metric + suffix,
                          value=pt.get("p99_ms"),
                          unit="ms p99"))
    return out


def normalize_artifact(path: str) -> List[Dict]:
    """One artifact file → normalized entries (empty when the file is
    not a bench artifact this report understands)."""
    source = os.path.basename(path)
    with open(path) as fp:
        raw = fp.read().strip()
    if not raw:
        return []
    try:
        obj = json.loads(raw)
        objs: Optional[List[Dict]] = None
    except json.JSONDecodeError:
        try:  # JSONL: one bench line per row
            objs = [json.loads(line) for line in raw.splitlines()
                    if line.strip()]
            obj = None
        except json.JSONDecodeError:
            return [_entry(source, "invalid",
                           {"metric": "bench_failed_parse",
                            "error": "unparseable artifact",
                            "unit": "invalid json"}, None)]

    kind = ("multichip" if source.startswith("MULTICHIP")
            else "service" if source.startswith("SERVICE")
            else "bench")
    if objs is not None:
        hint = _env_hint(source)
        return [_entry(source, kind, o, hint) for o in objs
                if isinstance(o, dict) and "metric" in o]

    assert obj is not None
    if not isinstance(obj, dict):
        return []
    # driver wrapper: {"n", "cmd", "rc", "tail", "parsed"}
    if "parsed" in obj and isinstance(obj.get("parsed"), dict):
        hint = _env_hint(source, obj.get("tail", ""))
        return [_entry(source, kind, obj["parsed"], hint)]
    # dryrun wrapper: {"n_devices", "rc", "ok", "skipped", "tail"}
    if "ok" in obj and "n_devices" in obj and "metric" not in obj:
        hint = _env_hint(source, obj.get("tail", ""))
        n = obj.get("n_devices")
        return [_entry(source, "dryrun",
                       {"metric": f"multichip_dryrun_{n}dev",
                        "value": 1.0 if obj.get("ok") else 0.0,
                        "unit": "dryrun ok"}, hint)]
    # sweep: {"protocol", "lanes": [...]}
    if "lanes" in obj:
        hint = _env_hint(source)
        return [_entry(source, kind, lane, hint)
                for lane in obj["lanes"]
                if isinstance(lane, dict) and "metric" in lane]
    # service sweep: {"rules", "points": [...]}
    if "points" in obj and "metric" not in obj:
        return _service_points(source, obj.get("points") or [],
                               _env_hint(source), artifact=obj)
    # single bench line (possibly with multichip points riding along)
    if "metric" in obj:
        hint = _env_hint(source) or obj.get("platform")
        entry = _entry(source, kind, obj, hint)
        if "points" in obj:
            entry["extras"]["points"] = [
                {k: p.get(k) for k in ("lane", "devices",
                                       "verdicts_per_sec",
                                       "weak_scaling_efficiency",
                                       "constant_silicon_efficiency",
                                       "strong_scaling_efficiency",
                                       "overhead_fraction",
                                       "collectives",
                                       "collective_count_per_block",
                                       "collective_budget_per_block",
                                       "xla_collectives")
                 if k in p}
                for p in obj["points"] if isinstance(p, dict)]
        return [entry]
    return []


def validate_entry(entry: Dict) -> List[str]:
    """Schema errors for one normalized entry. Legacy entries (no
    ``bench_schema``) get the loose contract; new-schema entries must
    carry a complete provenance fingerprint."""
    errs = []
    if entry["status"] == "failed":
        return errs
    if entry["kind"] == "invalid":
        return [f"{entry['source']}: unparseable artifact"]
    if not entry["metric"]:
        errs.append(f"{entry['source']}: entry without a metric name")
    if entry["value"] is None or not isinstance(
            entry["value"], (int, float)):
        errs.append(f"{entry['source']}:{entry['metric']}: "
                    f"non-numeric value {entry['value']!r}")
    if entry.get("bench_schema") is not None:
        if entry["bench_schema"] > BENCH_SCHEMA:
            errs.append(f"{entry['source']}:{entry['metric']}: "
                        f"bench_schema {entry['bench_schema']} is newer "
                        f"than this reader ({BENCH_SCHEMA})")
        prov = entry.get("provenance")
        if not isinstance(prov, dict):
            errs.append(f"{entry['source']}:{entry['metric']}: "
                        f"bench_schema line without provenance")
        else:
            for key in ("host_platform", "python", "git_rev",
                        "backend", "device_count", "rtt_p50_ms"):
                if key not in prov:
                    errs.append(
                        f"{entry['source']}:{entry['metric']}: "
                        f"provenance missing {key!r}")
    return errs


def derive_stage_entries(entries: List[Dict]) -> List[Dict]:
    """Synthetic lower-is-better staging metrics derived from every
    bench lane that carries a ``stage_ms`` wall — the ISSUE-7 staging
    budget's trajectory. Each derived entry keeps its parent's
    provenance/RTT extras, so an honest environment change (a remote
    transport appearing, backend swap) classifies a staging slowdown as
    environment exactly like a throughput one; an unexplained staging
    regression in the newest round fails the gate like any other
    code_regression."""
    out: List[Dict] = []
    for e in entries:
        if e["kind"] != "bench" or e["status"] != "ok":
            continue
        sm = e["extras"].get("stage_ms")
        if not isinstance(sm, (int, float)):
            continue
        if str(e["metric"]).startswith("stage_ms"):
            continue  # bench-stage lanes are already stage metrics
        d = dict(e)
        d["metric"] = f"{e['metric']}_stage_ms"
        d["value"] = float(sm)
        d["unit"] = "ms session staging"
        d["direction"] = "lower"
        out.append(d)
    return out


def normalize_all(root: str) -> Tuple[List[Dict], List[str]]:
    """Normalize every artifact under ``root`` → (entries, schema
    errors). ``PERF_TRAJECTORY.json`` itself is never an input."""
    entries: List[Dict] = []
    errors: List[str] = []
    seen = set()
    for pattern in ARTIFACT_GLOBS:
        for path in sorted(glob.glob(os.path.join(root, pattern))):
            if os.path.basename(path) in seen:
                continue
            seen.add(os.path.basename(path))
            try:
                found = normalize_artifact(path)
            except (OSError, ValueError) as e:
                errors.append(f"{os.path.basename(path)}: {e}")
                continue
            for entry in found:
                errors.extend(validate_entry(entry))
            entries.extend(found)
    entries.extend(derive_stage_entries(entries))
    return entries, errors


def collective_budget_violations(entries: List[Dict],
                                 newest: Optional[int]) -> List[Dict]:
    """The collective-budget gate (ISSUE 12): every sharded bench lane
    that DECLARES a per-block collective budget on its point
    (``collective_budget_per_block``) is held to it against the
    ledger's recorded rows (``collectives``: count_per_block per
    site). A lane regressing back to per-byte collectives — the
    MULTICHIP_PERF_r05 TP shape — is caught structurally here, not by
    wall-clock noise. Only the NEWEST round gates (history is already
    shipped); lanes without a declared budget (tp, the documented
    per-byte fallback) are not judged."""
    out = []
    for e in entries:
        if e["status"] != "ok" or e["round"] != newest:
            continue
        for p in e["extras"].get("points") or []:
            budget = p.get("collective_budget_per_block")
            rows = p.get("collectives")
            if budget is None or rows is None:
                continue
            total = sum(int(r.get("count_per_block", 0))
                        for r in rows if isinstance(r, dict))
            if total <= budget:
                continue
            sites = ", ".join(
                f"{r.get('site')}:{r.get('count_per_block')}"
                for r in rows if isinstance(r, dict))
            out.append({
                "metric": f"{e['metric']}[{p.get('lane')}]",
                "kind": e["kind"],
                "from": e["round_label"],
                "to": e["round_label"],
                "from_value": float(budget),
                "to_value": float(total),
                "direction": "lower",
                "worse_factor": round(total / max(budget, 1), 4),
                "classification": "code_regression",
                "reason": (f"lane {p.get('lane')!r} records {total} "
                           f"ledger collective(s) per compiled block "
                           f"({sites}) over its declared budget "
                           f"{budget} — per-block collective "
                           f"structure regressed"),
            })
    return out


def provenance_budget_violations(entries: List[Dict],
                                 newest: Optional[int]) -> List[Dict]:
    """The provenance-overhead gate (ISSUE 14): every bench lane that
    DECLARES a provenance budget on its line
    (``provenance_budget_pct``) is held to it against the measured
    ``provenance_overhead_pct`` — the marginal cost of consuming the
    attribution/provenance surfaces vs verdict-only windows. The
    e2e capture-replay lane declares 2.0%. Only the NEWEST round
    gates; lanes without a declared budget are not judged."""
    out = []
    for e in entries:
        if e["status"] != "ok" or e["round"] != newest:
            continue
        budget = e["extras"].get("provenance_budget_pct")
        measured = e["extras"].get("provenance_overhead_pct")
        if budget is None or measured is None:
            continue
        if float(measured) <= float(budget):
            continue
        out.append({
            "metric": f"{e['metric']}[provenance]",
            "kind": e["kind"],
            "from": e["round_label"],
            "to": e["round_label"],
            "from_value": float(budget),
            "to_value": float(measured),
            "direction": "lower",
            "worse_factor": round(
                float(measured) / max(float(budget), 1e-9), 4),
            "classification": "code_regression",
            "reason": (f"provenance-lane overhead "
                       f"{float(measured):g}% over its declared "
                       f"budget {float(budget):g}% — consuming the "
                       f"attribution surfaces got expensive"),
        })
    return out


def obs_budget_violations(entries: List[Dict],
                          newest: Optional[int]) -> List[Dict]:
    """The fleet-observability overhead gate (ISSUE 17): a lane that
    DECLARES an observability budget (``obs_budget_pct`` — the
    serve-fleet soak declares 2.0%) is held to its measured
    ``obs_overhead_pct``, the wall fraction spent on trace stitching,
    flow aggregation and journal/roll-up bookkeeping. Only the NEWEST
    round gates; lanes without a declared budget are not judged."""
    out = []
    for e in entries:
        if e["status"] != "ok" or e["round"] != newest:
            continue
        budget = e["extras"].get("obs_budget_pct")
        measured = e["extras"].get("obs_overhead_pct")
        if budget is None or measured is None:
            continue
        if float(measured) <= float(budget):
            continue
        out.append({
            "metric": f"{e['metric']}[observability]",
            "kind": e["kind"],
            "from": e["round_label"],
            "to": e["round_label"],
            "from_value": float(budget),
            "to_value": float(measured),
            "direction": "lower",
            "worse_factor": round(
                float(measured) / max(float(budget), 1e-9), 4),
            "classification": "code_regression",
            "reason": (f"fleet observability overhead "
                       f"{float(measured):g}% over its declared "
                       f"budget {float(budget):g}% — the stitching/"
                       f"flow-export/journal plane got expensive"),
        })
    return out


def canary_budget_violations(entries: List[Dict],
                             newest: Optional[int]) -> List[Dict]:
    """The canary double-dispatch gate (ISSUE 20): a lane that
    DECLARES a canary budget (``canary_budget_pct`` — the canary
    rollout lane declares 5.0%) is held to its measured
    ``canary_overhead_pct``, the pack-cycle wall fraction spent
    shadow-dispatching sampled traffic through the staged generation.
    A lane that declares a budget must also have CAUGHT its planted
    bad rollout (``diff_caught``) — a canary plane that is cheap but
    blind fails the gate too. Only the NEWEST round gates; lanes
    without a declared budget are not judged."""
    out = []
    for e in entries:
        if e["status"] != "ok" or e["round"] != newest:
            continue
        budget = e["extras"].get("canary_budget_pct")
        if budget is None:
            continue
        measured = e["extras"].get("canary_overhead_pct")
        if measured is not None and float(measured) > float(budget):
            out.append({
                "metric": f"{e['metric']}[canary]",
                "kind": e["kind"],
                "from": e["round_label"],
                "to": e["round_label"],
                "from_value": float(budget),
                "to_value": float(measured),
                "direction": "lower",
                "worse_factor": round(
                    float(measured) / max(float(budget), 1e-9), 4),
                "classification": "code_regression",
                "reason": (f"canary double-dispatch overhead "
                           f"{float(measured):g}% over its declared "
                           f"budget {float(budget):g}% — shadow "
                           f"evaluation got expensive"),
            })
        caught = e["extras"].get("diff_caught")
        if caught is False:
            out.append({
                "metric": f"{e['metric']}[canary-gate]",
                "kind": e["kind"],
                "from": e["round_label"],
                "to": e["round_label"],
                "from_value": 1.0,
                "to_value": 0.0,
                "direction": "higher",
                "worse_factor": 0.0,
                "classification": "code_regression",
                "reason": ("the planted bad-policy rollout was NOT "
                           "refused by the verdict-diff gate — the "
                           "canary plane went blind"),
            })
    return out


# -- trajectory + classification --------------------------------------------

def _effective_rtt(entry: Dict) -> Tuple[Optional[float], str]:
    """The best RTT signal an entry carries: a measured
    ``device_rtt_ms``, the provenance probe, or — for
    completion-forced bench lanes — the per-chunk p50 as an upper
    bound (a forced chunk includes ≥ one RTT)."""
    rtt = entry["extras"].get("device_rtt_ms")
    if isinstance(rtt, (int, float)):
        return float(rtt), "measured"
    prov = entry.get("provenance") or {}
    rtt = prov.get("rtt_p50_ms")
    if isinstance(rtt, (int, float)):
        return float(rtt), "provenance"
    if entry["kind"] == "bench":
        p50 = entry["extras"].get("p50_ms")
        if isinstance(p50, (int, float)) and p50 > 0:
            return float(p50), "p50-bound"
    return None, ""


_PROV_IDENT = ("backend", "device_kind", "device_count", "jax_version",
               "host_platform")


def classify_delta(old: Dict, new: Dict,
                   threshold: float = DEFAULT_THRESHOLD) -> Dict:
    """Classify one round transition of one metric."""
    direction = new["direction"]
    ov, nv = float(old["value"]), float(new["value"])
    if ov <= 0 or nv <= 0:
        worse = 1.0
    elif direction == "higher":
        worse = ov / nv
    else:
        worse = nv / ov
    delta = {
        "metric": new["metric"],
        "kind": new["kind"],
        "from": old["round_label"] or f"r{old['round']}",
        "to": new["round_label"] or f"r{new['round']}",
        "from_value": ov,
        "to_value": nv,
        "direction": direction,
        "worse_factor": round(worse, 4),
    }
    if worse <= 1.0 + threshold:
        delta["classification"] = "ok"
        delta["reason"] = ("improved" if worse < 1.0 else
                           "within threshold")
        return delta
    # worsened beyond threshold — look for an environment explanation
    if old.get("env_hint") and new.get("env_hint") \
            and old["env_hint"] != new["env_hint"]:
        delta["classification"] = "environment"
        delta["reason"] = (f"backend hint changed "
                           f"{old['env_hint']} → {new['env_hint']}")
        return delta
    po, pn = old.get("provenance") or {}, new.get("provenance") or {}
    for key in _PROV_IDENT:
        if po.get(key) is not None and pn.get(key) is not None \
                and po[key] != pn[key]:
            delta["classification"] = "environment"
            delta["reason"] = (f"provenance {key} changed "
                               f"{po[key]!r} → {pn[key]!r}")
            return delta
    r_old, src_old = _effective_rtt(old)
    r_new, src_new = _effective_rtt(new)
    if r_old is not None and r_new is not None and \
            min(r_old, r_new) > 0 and \
            max(r_old, r_new) / min(r_old, r_new) >= RTT_FACTOR:
        delta["classification"] = "environment"
        delta["reason"] = (f"device RTT moved {r_old}ms ({src_old}) → "
                           f"{r_new}ms ({src_new})")
        return delta
    delta["classification"] = "code_regression"
    delta["reason"] = (f"{delta['worse_factor']}× worse with no "
                       f"environment signal (rtt "
                       f"{r_old}/{r_new}, provenance "
                       f"{'present' if po and pn else 'absent'})")
    return delta


def build_trajectory(entries: List[Dict],
                     threshold: float = DEFAULT_THRESHOLD,
                     stage_budget_ms: Optional[float] = None) -> Dict:
    """Entries → per-metric round trajectory + classified deltas +
    failure ledger. Deterministic for a fixed artifact set."""
    failures = []
    by_metric: Dict[str, Dict[int, Dict]] = {}
    for entry in entries:
        if entry["status"] == "failed":
            err = entry.get("error") or entry.get("unit") or ""
            failures.append({
                "source": entry["source"],
                "round_label": entry["round_label"],
                "metric": entry["metric"],
                "error": err,
                "transient": bool(TRANSIENT_RE.search(str(err))),
                "lane": entry["extras"].get("lane"),
                "attempts": entry["extras"].get("attempts"),
            })
            continue
        if entry["round"] is None or entry["kind"] in ("dryrun",
                                                       "invalid"):
            continue
        if not isinstance(entry["value"], (int, float)):
            continue
        rounds = by_metric.setdefault(entry["metric"], {})
        cur = rounds.get(entry["round"])
        better = (cur is None
                  or (entry["direction"] == "higher"
                      and entry["value"] > cur["value"])
                  or (entry["direction"] == "lower"
                      and entry["value"] < cur["value"]))
        if better:
            rounds[entry["round"]] = entry

    trajectory = []
    deltas = []
    for metric in sorted(by_metric):
        rounds = by_metric[metric]
        ordered = [rounds[r] for r in sorted(rounds)]
        trajectory.append({
            "metric": metric,
            "kind": ordered[-1]["kind"],
            "unit": ordered[-1]["unit"],
            "direction": ordered[-1]["direction"],
            "rounds": [{
                "round": e["round"],
                "round_label": e["round_label"],
                "source": e["source"],
                "value": e["value"],
                "env_hint": e["env_hint"],
                "rtt_ms": _effective_rtt(e)[0],
                "provenance": e.get("provenance"),
                "extras": e["extras"],
            } for e in ordered],
        })
        for old, new in zip(ordered, ordered[1:]):
            deltas.append(classify_delta(old, new, threshold))

    # a derived stage_ms delta rides the SAME artifacts as its parent
    # e2e lane — when the parent transition over the same rounds is
    # explained by the environment (device RTT, backend hint), the
    # staging slowdown shares that explanation (legacy artifacts often
    # carry the environment evidence only on fields the parent metric
    # reads)
    def _round_int(label: str) -> Optional[int]:
        m = re.match(r"r(\d+)", label or "")
        return int(m.group(1)) if m else None

    parent_of = {}
    for d in deltas:
        if not d["metric"].endswith("_stage_ms"):
            parent_of[(d["metric"], _round_int(d["from"]),
                       _round_int(d["to"]))] = d
    for d in deltas:
        if d["metric"].endswith("_stage_ms") \
                and d["classification"] == "code_regression":
            parent = parent_of.get(
                (d["metric"][:-len("_stage_ms")],
                 _round_int(d["from"]), _round_int(d["to"])))
            if parent is not None \
                    and parent["classification"] == "environment":
                d["classification"] = "environment"
                d["reason"] = (f"parent lane classified environment "
                               f"({parent['reason']})")

    newest = max((e["round"] for m in by_metric.values() for e in
                  m.values()), default=None)
    gate = [d for d in deltas
            if d["classification"] == "code_regression"
            and newest is not None
            and d["to"].startswith(f"r{str(newest).zfill(2)}")]
    # absolute stage_ms budget (--stage-budget-ms /
    # CILIUM_TPU_BENCH_STAGE_BUDGET_MS): any newest-round staging
    # metric over the budget gates like a code regression — the
    # trajectory classifier catches relative regressions, the budget
    # pins the absolute ISSUE-7 target (stage ≤ budget on the tier-1
    # config) so a slow creep across rounds can't stay under the
    # per-transition threshold forever
    budget_violations = []
    if stage_budget_ms is not None and newest is not None:
        for m in trajectory:
            if not (m["metric"].endswith("_stage_ms")
                    or m["metric"].startswith("stage_ms")):
                continue
            last = m["rounds"][-1]
            if last["round"] == newest \
                    and float(last["value"]) > stage_budget_ms:
                budget_violations.append({
                    "metric": m["metric"],
                    "kind": m["kind"],
                    "from": last["round_label"],
                    "to": last["round_label"],
                    "from_value": float(last["value"]),
                    "to_value": float(last["value"]),
                    "direction": "lower",
                    "worse_factor": round(
                        float(last["value"]) / stage_budget_ms, 4),
                    "classification": "code_regression",
                    "reason": (f"stage_ms {last['value']:g} exceeds "
                               f"the budget {stage_budget_ms:g}ms"),
                })
    collective_violations = collective_budget_violations(entries,
                                                         newest)
    provenance_violations = provenance_budget_violations(entries,
                                                         newest)
    obs_violations = obs_budget_violations(entries, newest)
    canary_violations = canary_budget_violations(entries, newest)
    return {
        "schema": TRAJECTORY_SCHEMA,
        "threshold": threshold,
        "stage_budget_ms": stage_budget_ms,
        "newest_round": newest,
        "metrics": len(trajectory),
        "trajectory": trajectory,
        "deltas": deltas,
        "failures": failures,
        "gate_regressions": (gate + budget_violations
                             + collective_violations
                             + provenance_violations
                             + obs_violations
                             + canary_violations),
    }


# -- CLI --------------------------------------------------------------------

def _summarize(report: Dict, verbose: bool = False) -> str:
    lines = [f"perf-report: {report['metrics']} metrics across rounds "
             f"(newest r{report['newest_round']}), "
             f"{len(report['deltas'])} transitions, "
             f"{len(report['failures'])} failed lanes"]
    for d in report["deltas"]:
        if d["classification"] == "ok" and not verbose:
            continue
        lines.append(
            f"  {d['metric']}: {d['from']}→{d['to']} "
            f"{d['from_value']:g} → {d['to_value']:g} "
            f"[{d['classification']}] {d['reason']}")
    for f in report["failures"]:
        lines.append(
            f"  FAILED {f['metric']} ({f['source']}"
            + (f", retried {f['attempts']}x" if f.get("attempts")
               else "")
            + f"): {'transient' if f['transient'] else 'hard'} — "
            + str(f["error"])[:120])
    gate = report["gate_regressions"]
    if gate:
        lines.append(f"perf-report: GATE FAILED — "
                     f"{len(gate)} unexplained regression(s) in the "
                     f"newest round:")
        for d in gate:
            lines.append(f"    {d['metric']}: {d['reason']}")
    else:
        lines.append("perf-report: gate OK (no unexplained regression "
                     "in the newest round)")
    return "\n".join(lines)


def run_cli(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="cilium-tpu perf-report",
        description="normalize bench artifacts into a trajectory, "
                    "classify round-over-round deltas as code vs "
                    "environment, gate CI on unexplained regressions "
                    "(docs/OBSERVABILITY.md)")
    ap.add_argument("--root", default=None,
                    help="artifact directory (default: the repo root "
                         "containing this package)")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="write the trajectory JSON artifact here "
                         "(PERF_TRAJECTORY.json in CI)")
    ap.add_argument("--threshold", type=float, default=None,
                    help=f"worse-factor-over-1 needing explanation "
                         f"(default {DEFAULT_THRESHOLD}; env "
                         f"CILIUM_TPU_BENCH_PERF_THRESHOLD)")
    ap.add_argument("--strict", action="store_true",
                    help="gate on code regressions in EVERY round "
                         "transition, not just the newest")
    ap.add_argument("--stage-budget-ms", type=float, default=None,
                    dest="stage_budget_ms",
                    help="absolute staging budget: any newest-round "
                         "stage_ms metric above this fails the gate "
                         "(env CILIUM_TPU_BENCH_STAGE_BUDGET_MS)")
    ap.add_argument("--no-fail", action="store_true",
                    help="always exit 0 (report-only mode)")
    ap.add_argument("--format", choices=("text", "json"),
                    default="text")
    ap.add_argument("--verbose", action="store_true",
                    help="also print unchanged/improved transitions")
    args = ap.parse_args(argv)

    root = args.root or os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    threshold = args.threshold
    if threshold is None:
        threshold = float(os.environ.get(
            "CILIUM_TPU_BENCH_PERF_THRESHOLD", DEFAULT_THRESHOLD))
    stage_budget = args.stage_budget_ms
    if stage_budget is None:
        env_budget = os.environ.get(
            "CILIUM_TPU_BENCH_STAGE_BUDGET_MS", "")
        stage_budget = float(env_budget) if env_budget else None
    entries, schema_errors = normalize_all(root)
    if not entries:
        print(f"perf-report: no bench artifacts under {root}",
              file=sys.stderr)
        return 2
    report = build_trajectory(entries, threshold,
                              stage_budget_ms=stage_budget)
    report["schema_errors"] = schema_errors
    if args.out:
        with open(args.out, "w") as fp:
            json.dump(report, fp, indent=1, sort_keys=False)
            fp.write("\n")
    if args.format == "json":
        print(json.dumps(report, indent=1))
    else:
        print(_summarize(report, verbose=args.verbose))
        for err in schema_errors:
            print(f"  SCHEMA {err}")
    if schema_errors:
        return 0 if args.no_fail else 2
    # strict widens the gate to every transition; budget violations
    # (absolute stage_ms, already in gate_regressions) gate either way
    gate = (report["deltas"] + report["gate_regressions"]
            if args.strict else report["gate_regressions"])
    bad = [d for d in gate if d["classification"] == "code_regression"]
    if bad and not args.no_fail:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(run_cli())
