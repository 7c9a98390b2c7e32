"""perf-report (cilium_tpu/perf_report.py): legacy-artifact
normalization, provenance fingerprinting, the round trajectory, and
the code-vs-environment regression classifier — including the
acceptance fact that an r04→r05 delta whose device round trip moved
40× classifies as environment change, not code regression."""

import json
import os

from cilium_tpu.perf_report import (
    build_trajectory,
    classify_delta,
    normalize_all,
    normalize_artifact,
    run_cli,
    validate_entry,
)
from cilium_tpu.runtime.provenance import (
    BENCH_SCHEMA,
    fingerprint,
    stamp,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- provenance fingerprint -------------------------------------------------

def test_fingerprint_carries_identity_and_schema():
    fp = fingerprint(rtt=False)
    assert fp["schema"] == BENCH_SCHEMA
    assert fp["host_platform"]
    assert fp["python"]
    # this test runs inside the git checkout
    assert fp["git_rev"]
    # rtt skipped → explicit Nones, not missing keys
    assert fp["rtt_p50_ms"] is None and fp["rtt_max_ms"] is None


def test_fingerprint_rtt_probe_on_cpu_backend():
    fp = fingerprint(rtt=True)
    assert fp["backend"] == "cpu"
    assert fp["device_count"] >= 1
    assert fp["jax_version"]
    assert fp["rtt_p50_ms"] is not None and fp["rtt_p50_ms"] >= 0


def test_stamp_never_breaks_the_line():
    line = {"metric": "x", "value": 1.0, "unit": "u"}
    out = stamp(line, rtt=False)
    assert out is line
    assert line["bench_schema"] == BENCH_SCHEMA
    assert isinstance(line["provenance"], dict)
    assert json.loads(json.dumps(line))  # still JSON-serializable


# -- legacy-shape normalization ---------------------------------------------

def _write(tmp_path, name, obj, jsonl=False):
    p = tmp_path / name
    if jsonl:
        p.write_text("\n".join(json.dumps(o) for o in obj) + "\n")
    else:
        p.write_text(json.dumps(obj))
    return str(p)


def test_normalize_driver_wrapper(tmp_path):
    path = _write(tmp_path, "BENCH_r04.json", {
        "n": 4, "cmd": "python bench.py", "rc": 0,
        "tail": "Platform 'tpu' is experimental\n{...}",
        "parsed": {"metric": "e2e_capture_replay_http_1000rules",
                   "value": 2e8, "unit": "verdicts/s",
                   "vs_baseline": 20.0, "p50_ms": 0.33}})
    (entry,) = normalize_artifact(path)
    assert entry["round"] == 4 and entry["round_label"] == "r04"
    assert entry["metric"] == "e2e_capture_replay_http_1000rules"
    assert entry["direction"] == "higher"
    assert entry["env_hint"] == "tpu"   # inferred from the tail
    assert entry["status"] == "ok"
    assert not validate_entry(entry)


def test_normalize_jsonl_and_lanes_and_failures(tmp_path):
    lanes = [
        {"metric": "l7_verdicts_per_sec_http_1000rules", "value": 1e6,
         "unit": "verdicts/s", "p50_ms": 100.0,
         "device_rtt_ms": 90.0},
        {"metric": "bench_failed_run_kafka", "value": 0,
         "unit": "JaxRuntimeError",
         "error": "remote_compile: read body: connection reset"},
    ]
    p1 = _write(tmp_path, "BENCH_ALL_r05.jsonl", lanes, jsonl=True)
    p2 = _write(tmp_path, "BENCH_ALL_r05b.json",
                {"protocol": "x", "lanes": lanes})
    for path in (p1, p2):
        entries = normalize_artifact(path)
        assert len(entries) == 2
        ok, failed = entries
        assert ok["extras"]["device_rtt_ms"] == 90.0
        assert failed["status"] == "failed"


def test_normalize_service_points_and_pipelined_suffix(tmp_path):
    points = [
        {"deadline_ms": 2.0, "samples": 800, "p99_ms": 8.5,
         "throughput_rps": 100.0},
        {"lane": "open_loop", "deadline_ms": 8.0, "offered_rps": 4000,
         "samples": 500, "p99_ms": 30.0},
        {"lane": "stream", "offered_records_s": 200000, "samples": 80,
         "p99_ms": 170.0},
        {"lane": "cpp_shim_kafka", "samples": 200, "p99_ms": 4.4},
        {"deadline_ms": 0.5, "samples": 0, "p99_ms": 0.0},  # no data
    ]
    path = _write(tmp_path, "SERVICE_LATENCY_r04_pipelined.json",
                  {"rules": 1000, "points": points})
    entries = normalize_artifact(path)
    metrics = {e["metric"] for e in entries}
    assert "service_closed_p99_d2.0ms_pipelined" in metrics
    assert "service_open_p99_d8.0ms_4000rps_pipelined" in metrics
    assert "service_stream_p99_200000rps_pipelined" in metrics
    assert all(e["direction"] == "lower" for e in entries)
    assert len(entries) == 4  # the samples=0 point is dropped


def test_normalize_dryrun_wrapper(tmp_path):
    path = _write(tmp_path, "MULTICHIP_r03.json",
                  {"n_devices": 8, "rc": 0, "ok": True,
                   "skipped": False, "tail": ""})
    (entry,) = normalize_artifact(path)
    assert entry["kind"] == "dryrun"
    assert entry["value"] == 1.0


def test_new_schema_validation_requires_provenance(tmp_path):
    good = stamp({"metric": "m", "value": 1.0, "unit": "verdicts/s"},
                 rtt=False)
    bad = {"metric": "m", "value": 1.0, "unit": "verdicts/s",
           "bench_schema": BENCH_SCHEMA}  # schema tag, no provenance
    p = _write(tmp_path, "BENCH_ALL_r06.jsonl", [good, bad],
               jsonl=True)
    e_good, e_bad = normalize_artifact(p)
    assert not validate_entry(e_good)
    errs = validate_entry(e_bad)
    assert errs and "provenance" in errs[0]


# -- classification ---------------------------------------------------------

def _entry(round_, value, direction="higher", extras=None, prov=None,
           env_hint=None, metric="m"):
    return {"metric": metric, "kind": "bench", "round": round_,
            "round_label": f"r{round_:02d}", "value": value,
            "unit": "verdicts/s" if direction == "higher" else "ms",
            "direction": direction, "status": "ok", "env_hint": env_hint,
            "extras": extras or {}, "provenance": prov, "error": None,
            "source": f"B_r{round_:02d}.json", "schema": 1,
            "bench_schema": None}


def test_classify_rtt_move_is_environment():
    old = _entry(4, 2e8, extras={"p50_ms": 0.33})
    new = _entry(5, 5e6, extras={"device_rtt_ms": 89.0,
                                 "p50_ms": 124.0})
    d = classify_delta(old, new)
    assert d["classification"] == "environment"
    assert "RTT" in d["reason"]


def test_classify_provenance_mismatch_is_environment():
    old = _entry(4, 2e8, prov={"backend": "tpu", "device_count": 1})
    new = _entry(5, 5e6, prov={"backend": "cpu", "device_count": 1})
    d = classify_delta(old, new)
    assert d["classification"] == "environment"
    assert "backend" in d["reason"]


def test_classify_unexplained_drop_is_code_regression():
    old = _entry(4, 2e8, extras={"p50_ms": 0.33},
                 prov={"backend": "tpu"})
    new = _entry(5, 5e6, extras={"p50_ms": 0.40},
                 prov={"backend": "tpu"})
    d = classify_delta(old, new)
    assert d["classification"] == "code_regression"


def test_classify_within_threshold_is_ok():
    d = classify_delta(_entry(4, 100.0), _entry(5, 80.0),
                       threshold=0.5)
    assert d["classification"] == "ok"
    # lower-is-better direction flips the worse factor
    d = classify_delta(_entry(4, 10.0, direction="lower"),
                       _entry(5, 40.0, direction="lower"))
    assert d["classification"] == "code_regression"


def test_trajectory_gates_only_newest_round():
    entries = [
        _entry(3, 100.0), _entry(4, 10.0),   # old unexplained drop
        _entry(4, 50.0, metric="n"), _entry(5, 60.0, metric="n"),
    ]
    report = build_trajectory(entries)
    kinds = [d["classification"] for d in report["deltas"]]
    assert "code_regression" in kinds
    # the regression is r03→r04; newest round is 5 → gate is clean
    assert report["newest_round"] == 5
    assert report["gate_regressions"] == []


def test_failures_record_transience():
    entries = [{"metric": "bench_failed_run_kafka", "kind": "bench",
                "round": 5, "round_label": "r05", "value": 0,
                "unit": "JaxRuntimeError", "direction": "higher",
                "status": "failed", "env_hint": None,
                "error": "remote_compile: connection reset",
                "extras": {"lane": "kafka", "attempts": 2},
                "provenance": None, "source": "B_r05.json",
                "schema": 1, "bench_schema": None}]
    report = build_trajectory(entries)
    (f,) = report["failures"]
    assert f["transient"] is True
    assert f["lane"] == "kafka" and f["attempts"] == 2


# -- a five-round artifact tree in every legacy shape -----------------------

_METRICS = [f"l7_verdicts_per_sec_lane{i}" for i in range(10)]
_E2E = "e2e_capture_replay_http_1000rules"


def _history(tmp_path):
    """Rounds r01-r05 of bench artifacts, one legacy shape per round:
    a driver ``{"parsed"}`` wrapper, a JSONL lane file, a
    ``{"lanes"}`` sweep, a dryrun wrapper and a service sweep. The
    http e2e lane drops 40x into r05 while its device round trip moves
    2 ms -> 90 ms, and r05's kafka lane dies on a connection reset."""
    def lanes(r, e2e, rtt):
        out = [{"metric": m, "value": 1e6 * (1 + 0.05 * r + i),
                "unit": "verdicts/s", "p50_ms": 1.0}
               for i, m in enumerate(_METRICS)]
        out.append({"metric": _E2E, "value": e2e, "unit": "verdicts/s",
                    "p50_ms": 0.3, "device_rtt_ms": rtt})
        return out

    _write(tmp_path, "BENCH_r01.json",
           {"n": 1, "rc": 0, "tail": "",
            "parsed": {"metric": _E2E, "value": 1.5e8,
                       "unit": "verdicts/s", "p50_ms": 0.4}})
    _write(tmp_path, "BENCH_ALL_r01.jsonl", lanes(1, 1.5e8, 2.0),
           jsonl=True)
    _write(tmp_path, "BENCH_ALL_r02.jsonl", lanes(2, 1.8e8, 2.0),
           jsonl=True)
    _write(tmp_path, "BENCH_ALL_r03.json",
           {"protocol": "x", "lanes": lanes(3, 1.9e8, 2.0)})
    _write(tmp_path, "MULTICHIP_r03.json",
           {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
            "tail": ""})
    _write(tmp_path, "SERVICE_LATENCY_r03.json",
           {"rules": 1000, "points": [
               {"deadline_ms": 2.0, "samples": 800, "p99_ms": 8.5,
                "throughput_rps": 100.0}]})
    _write(tmp_path, "BENCH_ALL_r04.jsonl", lanes(4, 2e8, 2.0),
           jsonl=True)
    r05 = lanes(5, 5e6, 90.0)
    r05.append({"metric": "bench_failed_run_kafka", "value": 0,
                "unit": "JaxRuntimeError",
                "error": "remote_compile: read body: connection reset"})
    _write(tmp_path, "BENCH_ALL_r05.jsonl", r05, jsonl=True)
    return str(tmp_path)


def test_artifact_tree_normalizes_every_round(tmp_path):
    entries, errors = normalize_all(_history(tmp_path))
    assert not errors, errors
    assert len(entries) > 50  # five rounds of artifacts normalize
    rounds = {e["round"] for e in entries if e["round"]}
    assert {1, 2, 3, 4, 5} <= rounds


def test_r04_to_r05_rtt_move_classifies_as_environment(tmp_path):
    """The 40x r04->r05 e2e drop with a 45x device round-trip move
    classifies as environment change, not code regression."""
    entries, _ = normalize_all(_history(tmp_path))
    report = build_trajectory(entries)
    deltas = [d for d in report["deltas"]
              if d["metric"] == _E2E and d["to"].startswith("r05")]
    assert deltas, "no r05 transition for the http e2e lane"
    for d in deltas:
        assert d["classification"] == "environment", d
        assert "RTT" in d["reason"]
    # and the r05 kafka lane death is on the failure ledger, transient
    kafka = [f for f in report["failures"]
             if f["metric"] == "bench_failed_run_kafka"]
    assert kafka and all(f["transient"] for f in kafka)


def test_cli_writes_trajectory_and_gates_clean(tmp_path, capsys):
    (tmp_path / "root").mkdir()
    root = _history(tmp_path / "root")
    out = str(tmp_path / "PERF_TRAJECTORY.json")
    rc = run_cli(["--root", root, "--out", out])
    assert rc == 0  # the newest drop is explained by the environment
    report = json.load(open(out))
    assert report["schema"] == 1
    assert report["metrics"] > 10
    assert report["trajectory"] and report["deltas"]
    assert report["gate_regressions"] == []
    text = capsys.readouterr().out
    assert "gate OK" in text


def test_cli_fails_on_newest_unexplained_regression(tmp_path):
    _write(tmp_path, "BENCH_ALL_r01.jsonl",
           [{"metric": "m", "value": 100.0, "unit": "verdicts/s"}],
           jsonl=True)
    _write(tmp_path, "BENCH_ALL_r02.jsonl",
           [{"metric": "m", "value": 5.0, "unit": "verdicts/s"}],
           jsonl=True)
    assert run_cli(["--root", str(tmp_path)]) == 1
    assert run_cli(["--root", str(tmp_path), "--no-fail"]) == 0
    # a huge threshold explains everything away
    assert run_cli(["--root", str(tmp_path),
                    "--threshold", "100"]) == 0


def test_cli_empty_root_is_an_error(tmp_path):
    assert run_cli(["--root", str(tmp_path)]) == 2


# -- golden replay acceptance (slow: a real bench.py capture-lane run) ------

import subprocess
import sys

import pytest


@pytest.mark.slow
def test_golden_replay_artifact_attribution_and_provenance(tmp_path):
    """ISSUE 6 acceptance: a golden replay bench run emits an artifact
    whose attributed phase time covers ≥ 90% of the measured chunk
    wall, carries the stage_ms phase split, and is stamped with the
    provenance fingerprint under the versioned schema."""
    bench = os.path.join(REPO_ROOT, "bench.py")
    cap = str(tmp_path / "golden.bin")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "CILIUM_TPU_BENCH_BACKOFF": "0",
                "CILIUM_TPU_BENCH_RETRIES": "1"})
    r = subprocess.run(
        [sys.executable, bench, "--config", "fqdn", "--rules", "4",
         "--flows", "256", "--iters", "2", "--lat-iters", "8",
         "--warmup", "1", "--from-capture", cap,
         "--capture-flows", "2000", "--replay-chunk", "512"],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["metric"].startswith("e2e_capture_replay_fqdn")
    # provenance fingerprint under the versioned schema
    assert rec["bench_schema"] == BENCH_SCHEMA
    assert rec["provenance"]["backend"] == "cpu"
    assert rec["provenance"]["git_rev"]
    # the stage_ms split accounts for the staging wall
    split = rec["stage_phases_ms"]
    assert set(split) == {"tables", "featurize", "dedup", "table-h2d"}
    assert sum(split.values()) > 0
    assert sum(split.values()) <= rec["stage_ms"] * 1.05
    # attributed phase time covers >= 90% of the measured chunk wall
    att = rec["attribution"]
    assert att["coverage"] >= 0.9, att
    for phase in ("h2d", "gather", "mapstate", "resolve"):
        assert att["phases_ms"][phase] > 0
    assert att["compile_ms"] >= 0 and att["execute_ms"] > 0
    # and perf-report accepts the new-schema line without schema errors
    art = tmp_path / "BENCH_ALL_r99.jsonl"
    art.write_text(json.dumps(rec) + "\n")
    entries = normalize_artifact(str(art))
    assert entries and not validate_entry(entries[0])


# -- collective-budget gate (ISSUE 12) ---------------------------------------

def _multichip_line(lane_points):
    return {"metric": "multichip_weak_scaling_8dev", "value": 1.0,
            "unit": "DP constant-silicon efficiency", "platform": "cpu",
            "points": lane_points}


def test_collective_budget_within_budget_is_clean(tmp_path):
    _write(tmp_path, "MULTICHIP_PERF_r07.json", _multichip_line([
        {"lane": "cp", "collective_budget_per_block": 1,
         "collectives": [{"site": "cp.carry_exchange",
                          "op": "all_gather", "axis": "seq",
                          "count_per_block": 1}]},
        {"lane": "dp", "collective_budget_per_block": 0,
         "collectives": []},
        # no declared budget → not judged, however many it records
        {"lane": "tp", "collectives": [
            {"site": "tp.scan_step", "op": "psum",
             "count_per_block": 64}]},
    ]))
    entries, errs = normalize_all(str(tmp_path))
    report = build_trajectory(entries)
    assert report["gate_regressions"] == []


def test_collective_budget_violation_gates_newest_round(tmp_path):
    # the regression shape this gate exists for: the CP lane slid
    # back to a collective per scanned byte
    _write(tmp_path, "MULTICHIP_PERF_r07.json", _multichip_line([
        {"lane": "cp", "collective_budget_per_block": 1,
         "collectives": [{"site": "cp.carry_exchange",
                          "op": "ppermute", "axis": "seq",
                          "count_per_block": 64}]},
    ]))
    entries, _ = normalize_all(str(tmp_path))
    report = build_trajectory(entries)
    gate = report["gate_regressions"]
    assert len(gate) == 1, gate
    assert gate[0]["classification"] == "code_regression"
    assert "cp" in gate[0]["metric"]
    assert "declared budget 1" in gate[0]["reason"]
    assert "64" in gate[0]["reason"]


def test_collective_budget_old_rounds_do_not_gate(tmp_path):
    # an over-budget lane in a SHIPPED round reports nothing: only
    # the newest round gates (consistent with the delta classifier)
    _write(tmp_path, "MULTICHIP_PERF_r05.json", _multichip_line([
        {"lane": "tp", "collective_budget_per_block": 1,
         "collectives": [{"site": "tp.scan_step", "op": "psum",
                          "count_per_block": 64}]},
    ]))
    _write(tmp_path, "MULTICHIP_PERF_r07.json", _multichip_line([
        {"lane": "cp", "collective_budget_per_block": 1,
         "collectives": [{"site": "cp.carry_exchange",
                          "op": "all_gather",
                          "count_per_block": 1}]},
    ]))
    entries, _ = normalize_all(str(tmp_path))
    report = build_trajectory(entries)
    assert report["newest_round"] == 7
    assert report["gate_regressions"] == []


# -- provenance-overhead gate (ISSUE 14) -------------------------------------

def _e2e_prov_line(overhead, budget=2.0):
    return [{"metric": "e2e_capture_replay_http_100rules",
             "value": 1e7, "unit": "verdicts/s",
             "provenance_overhead_pct": overhead,
             "provenance_budget_pct": budget}]


def test_provenance_overhead_within_budget_is_clean(tmp_path):
    _write(tmp_path, "BENCH_ALL_r08.jsonl", _e2e_prov_line(0.7),
           jsonl=True)
    entries, _ = normalize_all(str(tmp_path))
    report = build_trajectory(entries)
    assert report["gate_regressions"] == []


def test_provenance_overhead_violation_gates_newest_round(tmp_path):
    _write(tmp_path, "BENCH_ALL_r08.jsonl", _e2e_prov_line(4.5),
           jsonl=True)
    entries, _ = normalize_all(str(tmp_path))
    report = build_trajectory(entries)
    gate = report["gate_regressions"]
    assert len(gate) == 1, gate
    assert gate[0]["classification"] == "code_regression"
    assert "[provenance]" in gate[0]["metric"]
    assert "4.5" in gate[0]["reason"]


def test_provenance_overhead_old_rounds_do_not_gate(tmp_path):
    _write(tmp_path, "BENCH_ALL_r05.jsonl", _e2e_prov_line(9.0),
           jsonl=True)
    _write(tmp_path, "BENCH_ALL_r08.jsonl", _e2e_prov_line(0.5),
           jsonl=True)
    entries, _ = normalize_all(str(tmp_path))
    report = build_trajectory(entries)
    assert report["newest_round"] == 8
    assert report["gate_regressions"] == []


def test_provenance_overhead_undeclared_not_judged(tmp_path):
    # a lane without a declared budget (pre-ISSUE-14 lines) is not
    # judged, whatever it measured
    _write(tmp_path, "BENCH_ALL_r08.jsonl",
           [{"metric": "e2e_capture_replay_http_100rules",
             "value": 1e7, "unit": "verdicts/s",
             "provenance_overhead_pct": 9.9}], jsonl=True)
    entries, _ = normalize_all(str(tmp_path))
    report = build_trajectory(entries)
    assert report["gate_regressions"] == []


def test_real_multichip_artifact_budgets_hold():
    """The committed r06 artifact's declared budgets hold through the
    same reader CI runs — the acceptance pin, not a fixture."""
    path = os.path.join(REPO_ROOT, "MULTICHIP_PERF_r06.json")
    if not os.path.exists(path):
        import pytest

        pytest.skip("MULTICHIP_PERF_r06.json not captured yet")
    entries = normalize_artifact(path)
    assert entries
    pts = entries[0]["extras"]["points"]
    lanes = {p.get("lane"): p for p in pts}
    for lane in ("dp", "ep", "cp"):
        assert lane in lanes, lanes.keys()
        assert "collective_budget_per_block" in lanes[lane]
    report = build_trajectory(entries)
    assert report["gate_regressions"] == []
    # the r05 indictment numbers, reversed: the cp lane records <=1
    # collective per compiled block and stays within overhead budget
    cp = lanes["cp"]
    assert sum(r["count_per_block"]
               for r in cp["collectives"]) <= 1
    assert cp["overhead_fraction"] <= 0.1
    assert lanes["ep"]["overhead_fraction"] <= 0.1
