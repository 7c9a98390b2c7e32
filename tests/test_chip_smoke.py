"""CPU rehearsal of ``chip_smoke.py``: its phase functions at a tiny
size (20 rules, 256 flows) must give zero oracle mismatches on every
lane, the served path must show no fallback, the multi-chip phases
must agree with one device on the virtual CPU mesh, and the script
itself must refuse to run without a TPU."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke as cs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK = 64


@pytest.fixture(scope="module")
def meter():
    m = cs.CompileMeter()
    yield m
    m.close()


@pytest.fixture(scope="module")
def workdir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("smoke"))


@pytest.fixture(scope="module")
def http(workdir, meter):
    per_identity, scenario = cs.http_world(n_rules=20, n_flows=256)
    report = {}
    replayed = cs.phase_replay("capture", per_identity, scenario.flows,
                               workdir, jax.devices()[0], meter, report,
                               chunk=CHUNK)
    return per_identity, scenario.flows, replayed, report


def test_capture_phase_matches_oracle_on_every_lane(http):
    _, flows, replayed, report = http
    assert len(replayed["oracle_idx"]) == len(flows)  # no sampling here
    for lane in cs.ORACLE_LANES:
        np.testing.assert_array_equal(replayed["lanes"][lane],
                                      replayed["oracle"][lane])
    # the replay window reuses the warm-up's compile
    assert report["capture.replay"]["window_compiles"] == 0
    assert {"wall_s", "compile_s", "execute_s"} <= set(
        report["capture.stage"])


def test_families_and_forced_nfa_arm_match_oracle(workdir, meter):
    per_identity, scenario = cs.families_world(n_mixed=200, n_proto=56)
    replayed = cs.phase_replay("families", per_identity, scenario.flows,
                               workdir, jax.devices()[0], meter, {},
                               chunk=CHUNK)
    # the l7proto frontend traffic rides the l7g automaton
    assert any(f.generic is not None for f in scenario.flows)
    arm = cs.phase_nfa_arm(per_identity, scenario.flows, replayed,
                           workdir, jax.devices()[0], meter, {})
    assert "path" in arm and "l7g" in arm


def test_served_phase_streams_and_checks_without_fallback(
        http, workdir, meter):
    per_identity, flows, replayed, _ = http
    audit = cs.phase_served(
        per_identity, flows, replayed["lanes"]["verdict"], workdir,
        jax.devices()[0], meter, {},
        oracle_idx=replayed["oracle_idx"],
        oracle_verdict=replayed["oracle"]["verdict"])
    assert audit["grants"] >= cs.STREAMS and audit["packs"] >= 1
    assert audit["fallback_verdicts"] == 0 and audit["pack_failures"] == 0


def test_no_fallback_audit_fails_on_a_device_fault(http, workdir, meter):
    """One injected dispatch fault is survived by production — a
    retried pack or an oracle-lane answer, correct verdicts either
    way — and the smoke must still fail on it."""
    from cilium_tpu.runtime import faults
    from cilium_tpu.runtime.faults import FaultPlan, FaultRule

    per_identity, flows, replayed, _ = http
    with faults.inject(FaultPlan([FaultRule("engine.dispatch", times=1)],
                                 seed=0)):
        with pytest.raises(cs.SmokeFailure,
                           match="fallback|breaker|retried"):
            cs.phase_served(per_identity, flows,
                            replayed["lanes"]["verdict"], workdir,
                            jax.devices()[0], meter, {},
                            streams=2, chunks=1, checks=4)


def test_fleet_phase_pins_one_replica_per_device(http, workdir, meter):
    per_identity, flows, replayed, _ = http
    out = cs.phase_fleet(per_identity, flows,
                         replayed["lanes"]["verdict"],
                         jax.devices()[:4], workdir, meter, {})
    assert out["hosts"] == 4


def test_dp_phase_bit_equal_to_one_device(http, workdir, meter):
    per_identity, flows, _, _ = http
    _, engine = cs.stage_engine(per_identity, workdir, jax.devices()[0])
    cs.phase_dp(engine, flows, jax.devices()[:4], meter, {})


def test_compare_lanes_counts_mismatches():
    got = {"verdict": np.array([1, 2, 2, 5])}
    cs.compare_lanes("ok", got, {"verdict": np.array([2, 5])},
                     ("verdict",), idx=np.array([1, 3]))
    with pytest.raises(cs.SmokeFailure, match="1 mismatches"):
        cs.compare_lanes("bad", got, {"verdict": np.array([1, 1, 2, 5])},
                         ("verdict",))


def test_device_gate_exits_nonzero_on_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
    unset, the cache is ``<repo>/.jax_cache``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if from_env:
        want = str(tmp_path / "jcc")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    code = (
        "import json, jax, jax.numpy as jnp\n"
        "from cilium_tpu.runtime import xla_cache\n"
        "xla_cache.enable_persistent_cache()\n"
        "jax.config.update("
        "'jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.cumsum(x) * 3)(jnp.arange(7.0))"
        ".block_until_ready()\n"
        "print(json.dumps([jax.config.jax_compilation_cache_dir,"
        " xla_cache.cache_dir()]))\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    configured, reported = json.loads(proc.stdout.strip().splitlines()[-1])
    assert configured == reported == want
    assert os.listdir(want), "the compile landed in the cache"
