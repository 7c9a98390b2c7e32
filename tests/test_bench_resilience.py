"""bench.py survives transient backend failure (VERDICT r2 item 1).

Round 2's official BENCH capture was lost to one transient
``UNAVAILABLE`` during backend init. These tests inject that failure
via CILIUM_TPU_BENCH_FAIL_FILE and assert the outer re-exec loop
(probe → fresh inner process → bounded retry) both recovers from a
transient failure and, on total failure, still emits ONE parseable
JSON line instead of a traceback.
"""

import json
import os
import subprocess
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


def _run(tmp_path, fail_count, retries):
    fail_file = tmp_path / "failures"
    fail_file.write_text(str(fail_count))
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "CILIUM_TPU_BENCH_FAIL_FILE": str(fail_file),
        "CILIUM_TPU_BENCH_BACKOFF": "0",
        "CILIUM_TPU_BENCH_RETRIES": str(retries),
        "CILIUM_TPU_BENCH_PROBE_TIMEOUT": "120",
    })
    return subprocess.run(
        [sys.executable, BENCH, "--config", "fqdn", "--rules", "4",
         "--flows", "256", "--iters", "2", "--warmup", "1",
         # keep the retry-machinery test cheap: the default-on e2e
         # capture lane would stage/replay a 200k-record capture on
         # CPU inside this subprocess's timeout
         "--from-capture", "none"],
        capture_output=True, text=True, env=env, timeout=300)


def test_recovers_from_transient_backend_failure(tmp_path):
    r = _run(tmp_path, fail_count=1, retries=3)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    # fqdn rides the e2e capture lane by default as of round 5
    assert rec["metric"].startswith(
        ("e2e_capture_replay_fqdn", "l7_verdicts_per_sec_fqdn"))
    assert rec["value"] > 0
    # the injected failure actually happened (probe attempt #1 died,
    # the outer announced a retry)
    assert "backend attempt 2/" in r.stderr


def test_total_backend_failure_emits_parseable_line(tmp_path):
    r = _run(tmp_path, fail_count=99, retries=2)
    assert r.returncode == 1
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])  # the driver's `parsed` must be non-null
    assert rec["metric"] == "bench_failed_backend_fqdn"
    assert rec["vs_baseline"] == 0.0
    assert "unit" in rec and "value" in rec
    # structured lane-failure record (perf ledger)
    assert rec["lane"] == "fqdn"
    assert rec["attempts"] == 2
    assert rec["transient"] is True


def _run_lane(tmp_path, run_fail_count, retries=3):
    """Inject a TRANSIENT MID-RUN failure (the r05 kafka
    `remote_compile` reset regime) after backend init succeeds."""
    fail_file = tmp_path / "run_failures"
    fail_file.write_text(str(run_fail_count))
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "CILIUM_TPU_BENCH_RUN_FAIL_FILE": str(fail_file),
        "CILIUM_TPU_BENCH_BACKOFF": "0",
        "CILIUM_TPU_BENCH_RETRIES": str(retries),
        "CILIUM_TPU_BENCH_PROBE_TIMEOUT": "120",
    })
    return subprocess.run(
        [sys.executable, BENCH, "--config", "fqdn", "--rules", "4",
         "--flows", "256", "--iters", "2", "--warmup", "1",
         "--from-capture", "none"],
        capture_output=True, text=True, env=env, timeout=300)


def test_transient_lane_failure_gets_one_retry(tmp_path):
    """Lane isolation: a mid-run transient connection error costs one
    retry, then the lane completes — and the line is stamped with the
    provenance fingerprint under the versioned schema."""
    r = _run_lane(tmp_path, run_fail_count=1)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["metric"].startswith("l7_verdicts_per_sec_fqdn")
    assert rec["value"] > 0
    assert "transient lane failure, one retry" in r.stderr
    # provenance fingerprint (perf ledger acceptance): the line
    # carries the versioned schema + environment identity
    assert rec["bench_schema"] == 1
    prov = rec["provenance"]
    assert prov["backend"] == "cpu"
    assert prov["device_count"] >= 1
    assert prov["rtt_p50_ms"] is not None


def test_persistent_lane_failure_is_structured_and_bounded(tmp_path):
    """A lane that keeps dying gets exactly ONE retry (not the whole
    backend budget) and leaves a structured per-lane failure record."""
    r = _run_lane(tmp_path, run_fail_count=99, retries=5)
    assert r.returncode != 0
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["metric"] == "bench_failed_run_fqdn"
    assert rec["lane"] == "fqdn"
    assert rec["attempts"] == 2       # original + one lane retry
    assert rec["transient"] is True
    assert "connection reset" in rec["error"]
