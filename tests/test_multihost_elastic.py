"""Multi-process elasticity evidence (VERDICT r1 item 8).

Two real OS processes form a ``jax.distributed`` CPU cluster, run a
cross-process collective, stage the same content-hashed policy, and
split the flow stream. One worker is then killed (``os._exit`` — no
clean shutdown) and the fleet restarts: the restarted workers re-stage
the IDENTICAL cached artifact (no recompile — mtimes unchanged) and
the reformed cluster produces the same verdicts. This is the
reference's restart property: agents derive all state from the common
rule store; nothing is exchanged between peers.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_once(tmp_path, tag: str, crash_pid, timeout):
    port = _free_port()
    outs = [str(tmp_path / f"{tag}-p{i}.json") for i in range(2)]
    cache = str(tmp_path / "artifact-cache")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO,  # `python tests/worker.py` puts tests/
                                 # on sys.path, not the repo root
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, f"127.0.0.1:{port}", "2", str(i),
             cache, outs[i],
             "crash" if i == crash_pid else "clean"],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for i in range(2)
    ]
    results = []
    for i, p in enumerate(procs):
        try:
            _, stderr = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            return None, f"worker {i} hung in round {tag}", True
        want_rc = 1 if i == crash_pid else 0
        if p.returncode != want_rc:
            text = stderr.decode()[-2000:]
            # ONLY the coordination-service startup/exit-polling
            # misfires seen under host load are retryable; any other
            # wrong exit code is a real failure and must fail fast
            retryable = ("coordination" in text.lower()
                         or "UNAVAILABLE" in text
                         or "DEADLINE" in text)
            return None, (f"worker {i} rc={p.returncode} (want "
                          f"{want_rc})\n{text}"), retryable
        with open(outs[i]) as fp:
            results.append(json.load(fp))
    return results, "", False


def _launch_round(tmp_path, tag: str, crash_pid=None, timeout=180):
    # under a fully loaded host the coordination service's startup
    # barrier / exit polling can misfire spuriously; retry THOSE only
    # — real worker failures fail fast, and the result assertions
    # stay strict
    err = ""
    for attempt in range(3):
        results, err, retryable = _launch_once(
            tmp_path, f"{tag}-a{attempt}", crash_pid, timeout)
        if results is not None:
            return results
        if not retryable:
            break
    pytest.fail(f"round {tag} failed: {err}")


def _cache_mtimes(tmp_path):
    cache = tmp_path / "artifact-cache"
    return {p.name: p.stat().st_mtime_ns for p in cache.glob("*.pkl")}


def test_two_process_cluster_kill_and_rejoin(tmp_path):
    # round 1: healthy cluster; worker 1 is killed after staging
    r1 = _launch_round(tmp_path, "r1", crash_pid=1)
    for r in r1:
        assert r["psum"] == 3.0, "cross-process psum must see both"
    assert r1[0]["artifacts"] == r1[1]["artifacts"]
    # content-addressed banks (PR 8) stage as bankart-* files beside
    # exactly ONE policy artifact
    policy_arts = [a for a in r1[0]["artifacts"]
                   if not a.startswith("bankart-")]
    assert len(policy_arts) == 1, (
        "both processes must stage ONE content-addressed policy "
        f"artifact, got {r1[0]['artifacts']}")
    assert r1[0]["slice"] == [0, 2] and r1[1]["slice"] == [1, 2]

    # round 2: fleet restart (the killed worker rejoins a fresh
    # cluster); the cached artifact is re-staged, NOT recompiled.
    # mtimes are read from the cache dir after each round: in round 1
    # both cold processes compile and write the same artifact, so one
    # worker's mid-round view can predate the other's write
    after_r1 = _cache_mtimes(tmp_path)
    r2 = _launch_round(tmp_path, "r2")
    for r in r2:
        assert r["psum"] == 3.0, "restarted cluster must reform"
    assert r2[0]["artifacts"] == r1[0]["artifacts"]
    assert _cache_mtimes(tmp_path) == after_r1, (
        "restart must reuse the content-hashed artifact (recompile "
        "would rewrite it)")
    # same stream slices → same verdicts as before the kill
    assert r2[0]["verdicts"] == r1[0]["verdicts"]
    assert r2[1]["verdicts"] == r1[1]["verdicts"]
