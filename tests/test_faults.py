"""Chaos suite: the deterministic fault-injection layer
(runtime/faults.py) and the degraded-mode verdict pipeline it proves
(TPU→oracle circuit breaker, atomic loader swap with rollback,
stream reconnect-with-resume, isolated kvstore/clustermesh/dnsproxy
failures).

The fast tests here run in tier-1. Tests marked ``chaos`` (the
golden-corpus replays under injected failures) are also ``slow`` —
the ``make chaos`` lane runs them seeded and standalone so chaos cost
never rides the tier-1 timing budget.
"""

import threading
import time

import numpy as np
import pytest

from cilium_tpu.core.config import Config
from cilium_tpu.core.flow import Flow, Protocol, TrafficDirection
from cilium_tpu.runtime import faults, simclock
from cilium_tpu.runtime.faults import FaultInjected, FaultPlan, FaultRule
from cilium_tpu.runtime.loader import Loader
from cilium_tpu.runtime.metrics import (
    BREAKER_FALLBACK_VERDICTS,
    BREAKER_RECOVERIES,
    BREAKER_TRIPS,
    DNSPROXY_FALLBACKS,
    FAULTS_INJECTED,
    KVSTORE_WATCH_ERRORS,
    LOADER_ROLLBACKS,
    METRICS,
    STREAM_RECONNECTS,
)
from cilium_tpu.runtime.service import CircuitBreaker, VerdictService


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """A leaked armed plan would fail unrelated tests — enforce."""
    assert faults.active() is None
    yield
    faults.clear()


def _metric(name, labels=None):
    return METRICS.get(name, labels)


# ---------------------------------------------------------------------------
# FaultPlan determinism


def test_plan_fires_deterministically_per_seed():
    def run(seed):
        plan = FaultPlan([FaultRule("p", prob=0.5)], seed=seed)
        for _ in range(300):
            plan.check("p")
        return plan.trace()["p"]

    assert run(7) == run(7)
    assert run(7) != run(8)
    fires = sum(f for _, f in run(7))
    assert 80 < fires < 220  # prob 0.5 actually samples


def test_plan_times_after_and_counts():
    plan = FaultPlan([FaultRule("p", times=2, after=3)], seed=0)
    fired = [plan.check("p") is not None for _ in range(10)]
    assert fired == [False] * 3 + [True, True] + [False] * 5
    assert plan.counts("p") == (10, 2)
    assert plan.counts("unknown") == (0, 0)


def test_plan_trace_is_thread_order_free():
    """Per-point decisions depend only on per-point hit order, so two
    points hammered from interleaved threads still produce the same
    per-point traces as a serial run."""
    def run(threaded):
        plan = FaultPlan([FaultRule("a", prob=0.3),
                          FaultRule("b", prob=0.7)], seed=42)
        if threaded:
            ts = [threading.Thread(
                target=lambda p: [plan.check(p) for _ in range(200)],
                args=(p,)) for p in ("a", "b")]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        else:
            for _ in range(200):
                plan.check("a")
            for _ in range(200):
                plan.check("b")
        return plan.trace()

    assert run(False) == run(True)


def test_maybe_fail_noop_without_plan_and_raises_with():
    faults.maybe_fail("engine.dispatch")  # disarmed: no-op
    plan = FaultPlan([FaultRule("engine.dispatch", times=1)])
    before = _metric(FAULTS_INJECTED, {"point": "engine.dispatch"})
    with faults.inject(plan):
        with pytest.raises(FaultInjected):
            faults.maybe_fail("engine.dispatch")
        faults.maybe_fail("engine.dispatch")  # times exhausted
    assert faults.active() is None
    assert _metric(FAULTS_INJECTED,
                   {"point": "engine.dispatch"}) == before + 1


def test_plan_chooses_the_exception_type():
    plan = FaultPlan([FaultRule("x", exc=ConnectionError)])
    with faults.inject(plan):
        with pytest.raises(ConnectionError):
            faults.maybe_fail("x")


def test_registered_points_cover_the_documented_seams():
    # points register at the owning module's import — pull in the seams
    import cilium_tpu.clustermesh  # noqa: F401
    import cilium_tpu.engine.verdict  # noqa: F401
    import cilium_tpu.fqdn.dnsproxy  # noqa: F401
    import cilium_tpu.identity_kvstore  # noqa: F401
    import cilium_tpu.kvstore  # noqa: F401
    import cilium_tpu.policy.compiler.bankplan  # noqa: F401
    import cilium_tpu.runtime.canary  # noqa: F401
    import cilium_tpu.runtime.fleetserve  # noqa: F401
    import cilium_tpu.runtime.stream  # noqa: F401
    import cilium_tpu.runtime.tenant  # noqa: F401

    pts = faults.registered_points()
    for p in ("engine.dispatch", "loader.swap", "loader.bank_compile",
              "stream.frame.server",
              "stream.frame.client", "stream.credit", "service.admit",
              "service.drain", "kvstore.watch", "kvstore.churn_storm",
              "clustermesh.session", "dnsproxy.query",
              "fleet.heartbeat", "fleet.handoff",
              "canary.dispatch", "tenant.quota"):
        assert p in pts, p


# ---------------------------------------------------------------------------
# CircuitBreaker state machine (fake clock — no sleeping)


def test_breaker_trips_after_consecutive_failures_and_recovers():
    now = [0.0]
    br = CircuitBreaker(failure_threshold=3, probe_interval=5.0,
                        clock=lambda: now[0])
    trips0 = _metric(BREAKER_TRIPS)
    recov0 = _metric(BREAKER_RECOVERIES)
    # two failures + a success: consecutive counter resets
    br.record_failure()
    br.record_failure()
    br.record_success()
    assert br.state == CircuitBreaker.CLOSED
    for _ in range(3):
        assert br.allow_primary()
        br.record_failure()
    assert br.state == CircuitBreaker.OPEN
    assert _metric(BREAKER_TRIPS) == trips0 + 1
    # OPEN: no probe until the interval elapses
    assert not br.allow_primary()
    now[0] = 5.1
    assert br.allow_primary()          # the single HALF_OPEN probe
    assert br.state == CircuitBreaker.HALF_OPEN
    assert not br.allow_primary()      # concurrent caller keeps falling back
    br.record_failure()                # probe failed → OPEN, timer re-armed
    assert br.state == CircuitBreaker.OPEN
    assert not br.allow_primary()
    now[0] = 10.3
    assert br.allow_primary()
    br.record_success()                # probe succeeded → CLOSED
    assert br.state == CircuitBreaker.CLOSED
    assert _metric(BREAKER_RECOVERIES) == recov0 + 1
    assert _metric(BREAKER_TRIPS) == trips0 + 1  # no double trip
    assert [e for e, _ in br.events] == [
        "trip", "probe", "probe-failed", "probe", "recover"]


# ---------------------------------------------------------------------------
# Loader: atomic swap with rollback


def _tiny_policy(port):
    from cilium_tpu.core.identity import IdentityAllocator
    from cilium_tpu.core.labels import LabelSet
    from cilium_tpu.policy.api import (
        EndpointSelector,
        IngressRule,
        PortProtocol,
        PortRule,
        Rule,
    )
    from cilium_tpu.policy.mapstate import PolicyResolver
    from cilium_tpu.policy.repository import Repository
    from cilium_tpu.policy.selectorcache import SelectorCache

    rules = [Rule(
        endpoint_selector=EndpointSelector.from_labels(app="db"),
        ingress=(IngressRule(
            from_endpoints=(EndpointSelector.from_labels(app="web"),),
            to_ports=(PortRule(ports=(
                PortProtocol(port, Protocol.TCP),)),)),),
    )]
    alloc = IdentityAllocator()
    db = alloc.allocate(LabelSet.from_dict({"app": "db"}))
    web = alloc.allocate(LabelSet.from_dict({"app": "web"}))
    cache = SelectorCache(alloc)
    repo = Repository()
    repo.add(rules, sanitize=False)
    per_identity = {db: PolicyResolver(repo, cache).resolve(
        alloc.lookup(db))}
    return per_identity, db, web


def _flow(web, db, port):
    return Flow(src_identity=web, dst_identity=db, dport=port,
                protocol=Protocol.TCP,
                direction=TrafficDirection.INGRESS)


@pytest.mark.parametrize("offload", [False, True])
def test_loader_swap_rollback_keeps_previous_revision(offload):
    cfg = Config()
    cfg.enable_tpu_offload = offload
    cfg.loader.enable_cache = False
    loader = Loader(cfg)
    per1, db, web = _tiny_policy(5432)
    loader.regenerate(per1, revision=1)
    engine1 = loader.engine
    rollbacks0 = _metric(LOADER_ROLLBACKS)

    per2, _, _ = _tiny_policy(6000)
    with faults.inject(FaultPlan([FaultRule("loader.swap", times=1)])):
        with pytest.raises(FaultInjected):
            loader.regenerate(per2, revision=2)
        # mid-swap crash: the PREVIOUS table serves, not a torn state
        assert loader.engine is engine1
        assert loader.revision == 1
        assert loader.per_identity is per1
        out = loader.engine.verdict_flows([_flow(web, db, 5432)])
        assert int(out["verdict"][0]) == 1  # rev-1 semantics intact
        assert _metric(LOADER_ROLLBACKS) == rollbacks0 + 1
        # injection exhausted (times=1): the retry succeeds
        loader.regenerate(per2, revision=2)
    assert loader.revision == 2
    out = loader.engine.verdict_flows(
        [_flow(web, db, 5432), _flow(web, db, 6000)])
    assert [int(v) for v in out["verdict"]] == [2, 1]


def test_loader_fallback_engine_tracks_revision():
    from cilium_tpu.policy.oracle import OracleVerdictEngine

    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.enable_cache = False
    loader = Loader(cfg)
    per1, db, web = _tiny_policy(5432)
    loader.regenerate(per1, revision=1)
    fb1 = loader.fallback_engine
    assert isinstance(fb1, OracleVerdictEngine)
    assert fb1 is loader.fallback_engine  # cached per revision
    out = fb1.verdict_flows([_flow(web, db, 5432)])
    assert int(out["verdict"][0]) == 1
    per2, _, _ = _tiny_policy(6000)
    loader.regenerate(per2, revision=2)
    fb2 = loader.fallback_engine
    assert fb2 is not fb1
    assert int(fb2.verdict_flows(
        [_flow(web, db, 5432)])["verdict"][0]) == 2
    # gate off: the active oracle IS the fallback (no duplicate build)
    loader2 = Loader(Config())
    loader2.regenerate(per1, revision=1)
    assert loader2.fallback_engine is loader2.engine


# ---------------------------------------------------------------------------
# Service: breaker-guarded verdict paths


def _service(tmp_path, per_identity, offload=True, threshold=2,
             probe_interval=60.0):
    cfg = Config()
    cfg.enable_tpu_offload = offload
    cfg.loader.enable_cache = False
    cfg.breaker.failure_threshold = threshold
    cfg.breaker.probe_interval = probe_interval
    loader = Loader(cfg)
    loader.regenerate(per_identity, revision=1)
    svc = VerdictService(loader, str(tmp_path / "svc.sock"))
    svc.start()
    return svc


def test_service_device_failure_degrades_to_oracle(tmp_path):
    """Repeated engine.dispatch faults: every answer stays CORRECT
    (served by the oracle), the breaker trips, and when injection
    stops the half-open probe recovers the device lane."""
    from cilium_tpu.runtime.service import VerdictClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, threshold=2, probe_interval=0.05)
    probe_advance = 1.0   # > probe_interval: the timer reads expired
    want = {5432: 1, 5433: 2}
    trips0 = _metric(BREAKER_TRIPS)
    recov0 = _metric(BREAKER_RECOVERIES)
    fallb0 = _metric(BREAKER_FALLBACK_VERDICTS)
    try:
        client = VerdictClient(svc.socket_path)
        plan = FaultPlan([FaultRule("engine.dispatch", times=2)], seed=1)
        with faults.inject(plan):
            for port, w in list(want.items()) * 3:
                resp = client.call({"op": "verdict", "flows": [
                    {"source": {"identity": int(web)},
                     "destination": {"identity": int(db)},
                     "l4": {"TCP": {"destination_port": port}},
                     "traffic_direction": "INGRESS"}]})
                assert resp["verdicts"] == [w], (port, resp)
            assert plan.counts("engine.dispatch")[1] == 2
        assert _metric(BREAKER_TRIPS) == trips0 + 1
        assert _metric(BREAKER_FALLBACK_VERDICTS) > fallb0
        # injection over: advance the breaker's clock past the probe
        # interval (no wall-clock sleep — ISSUE-10 virtual time); the
        # next request half-open probes the device lane and recovers
        svc.verdictor.breaker.clock = \
            lambda: simclock.now() + probe_advance
        resp = client.call({"op": "verdict", "flows": [
            {"source": {"identity": web},
             "destination": {"identity": db},
             "l4": {"TCP": {"destination_port": 5432}},
             "traffic_direction": "INGRESS"}]})
        assert resp["verdicts"] == [1]
        assert svc.verdictor.breaker.state == CircuitBreaker.CLOSED
        assert _metric(BREAKER_RECOVERIES) == recov0 + 1
        client.close()
    finally:
        svc.stop()


def test_microbatcher_check_survives_device_faults(tmp_path):
    """The per-request MicroBatcher path ('check' op) serves correct
    verdicts from the oracle while the device lane is down."""
    from cilium_tpu.runtime.service import VerdictClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, threshold=1, probe_interval=60.0)
    try:
        client = VerdictClient(svc.socket_path)
        with faults.inject(FaultPlan(
                [FaultRule("engine.dispatch")], seed=0)):  # always fail
            for port, w in ((5432, 1), (5433, 2), (5432, 1)):
                resp = client.call({"op": "check", "flow": {
                    "source": {"identity": int(web)},
                    "destination": {"identity": int(db)},
                    "l4": {"TCP": {"destination_port": port}},
                    "traffic_direction": "INGRESS"}})
                assert resp["verdict"] == w
        assert svc.verdictor.breaker.state == CircuitBreaker.OPEN
        client.close()
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# Stream: per-chunk degradation + client reconnect-with-resume


def _stream_flows(web, db, n=64):
    return [_flow(web, db, 5432 if i % 2 == 0 else 5433)
            for i in range(n)]


def test_stream_server_chunk_fault_fails_only_its_seq(tmp_path):
    from cilium_tpu.runtime.stream import StreamClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, offload=False)
    try:
        client = StreamClient(svc.socket_path, timeout=30.0)
        flows = _stream_flows(web, db, 32)
        with faults.inject(FaultPlan(
                [FaultRule("stream.frame.server", times=1)], seed=0)):
            seqs = [client.send_flows(flows) for _ in range(4)]
            client.finish()
        errors, ok = 0, 0
        for seq in seqs:
            try:
                v = client.result(seq)
                ok += 1
                assert list(v) == [1, 2] * 16
            except RuntimeError:
                errors += 1
        assert (errors, ok) == (1, 3)  # exactly the faulted seq failed
        client.close()
    finally:
        svc.stop()


def test_stream_device_fault_degrades_chunk_to_oracle(tmp_path):
    """With the TPU gate on, an engine.dispatch fault inside a stream
    chunk serves THAT chunk from the oracle — same verdicts, no error
    frame, breaker accounting engaged."""
    from cilium_tpu.runtime.stream import StreamClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, threshold=2, probe_interval=60.0)
    fallb0 = _metric(BREAKER_FALLBACK_VERDICTS)
    try:
        client = StreamClient(svc.socket_path, timeout=60.0)
        flows = _stream_flows(web, db, 32)
        with faults.inject(FaultPlan(
                [FaultRule("engine.dispatch", times=3)], seed=0)):
            seqs = [client.send_flows(flows) for _ in range(6)]
            client.finish()
            for seq in seqs:
                assert list(client.result(seq)) == [1, 2] * 16
        assert _metric(BREAKER_FALLBACK_VERDICTS) >= fallb0 + 3 * 32
        client.close()
    finally:
        svc.stop()


def test_stream_client_reconnects_and_resumes(tmp_path):
    """An injected connection drop mid-stream: the client re-dials
    with backoff, re-handshakes, re-sends unacked chunks, and every
    verdict lands — zero mismatches, reconnect counted."""
    from cilium_tpu.runtime.stream import StreamClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, offload=False)
    rec0 = _metric(STREAM_RECONNECTS)
    try:
        client = StreamClient(svc.socket_path, timeout=60.0,
                              reconnect=True, backoff_base=0.01)
        flows = _stream_flows(web, db, 16)
        # drop the connection on the 2nd received frame
        with faults.inject(FaultPlan([FaultRule(
                "stream.frame.client", after=1, times=1,
                exc=ConnectionError)], seed=3)):
            seqs = [client.send_flows(flows) for _ in range(5)]
            client.finish()
            for seq in seqs:
                assert list(client.result(seq)) == [1, 2] * 8
        assert _metric(STREAM_RECONNECTS) == rec0 + 1
        client.close()
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# kvstore / clustermesh / dnsproxy isolation


def test_kvstore_watch_fault_is_isolated_from_the_writer():
    from cilium_tpu.kvstore import KVStore

    store = KVStore()
    seen = []
    store.watch_prefix("k/", lambda ev: seen.append(ev.key),
                       replay=False)
    errs0 = _metric(KVSTORE_WATCH_ERRORS)
    with faults.inject(FaultPlan(
            [FaultRule("kvstore.watch", times=1)], seed=0)):
        store.set("k/1", "a")   # delivery faulted — writer unaffected
        store.set("k/2", "b")   # next event delivers normally
    assert store.get("k/1") == "a"  # the COMMIT was never at risk
    assert seen == ["k/2"]
    assert _metric(KVSTORE_WATCH_ERRORS) == errs0 + 1


def test_clustermesh_session_fault_drops_one_event_not_the_session():
    from cilium_tpu.clustermesh import IP_PREFIX, RemoteCluster
    from cilium_tpu.core.identity import IdentityAllocator
    from cilium_tpu.ipcache import IPCache
    from cilium_tpu.kvstore import KVStore

    alloc = IdentityAllocator()
    ipcache = IPCache(alloc)
    store = KVStore()
    rc = RemoteCluster("c1", store, alloc, ipcache).connect()
    with faults.inject(FaultPlan(
            [FaultRule("clustermesh.session", times=1)], seed=0)):
        store.set(IP_PREFIX + "c1/10.1.0.1/32",
                  '{"prefix": "10.1.0.1/32", "labels": ["k8s:app=a"]}')
        store.set(IP_PREFIX + "c1/10.1.0.2/32",
                  '{"prefix": "10.1.0.2/32", "labels": ["k8s:app=b"]}')
    # first event was eaten by the fault; the session survived and
    # ingested the second
    assert rc.num_entries() == 1
    assert ipcache.lookup("10.1.0.2") is not None
    rc.disconnect()


def test_dnsproxy_device_fault_falls_back_to_regex():
    from cilium_tpu.fqdn.dnsproxy import DNSProxy
    from cilium_tpu.policy.api.l7 import PortRuleDNS

    proxy = DNSProxy(use_tpu=True)
    proxy.update_allowed(1, 53, [PortRuleDNS(match_pattern="*.corp.io")])
    qnames = ["a.corp.io", "evil.net", "b.corp.io"]
    fb0 = _metric(DNSPROXY_FALLBACKS)
    with faults.inject(FaultPlan(
            [FaultRule("dnsproxy.query")], seed=0)):  # device always sick
        got = proxy.check_batch(1, 53, qnames)
    assert list(got) == [True, False, True]
    assert _metric(DNSPROXY_FALLBACKS) == fb0 + 1
    # healthy again: the banked path answers identically
    assert list(proxy.check_batch(1, 53, qnames)) == [True, False, True]


# ---------------------------------------------------------------------------
# The acceptance chaos replay: golden corpus under injected device
# failures — zero verdict mismatches, breaker trips + recovers, and
# the same plan + seed reproduces the identical event trace twice.


def _chaos_corpus_replay(seed):
    """One full degraded-mode replay of the golden corpus with a
    manually-advanced breaker clock (no wall-clock in the loop — the
    whole event sequence is a pure function of the plan). Returns
    (verdicts, fault trace, breaker events, counter deltas)."""
    from cilium_tpu.agent import Agent
    from cilium_tpu.runtime.service import ResilientVerdictor
    from tests.test_controlplane_golden import build_agent, build_flows

    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.configure_logging = False
    agent, ids = build_agent(Agent(cfg))
    try:
        clock = [0.0]
        breaker = CircuitBreaker(failure_threshold=2,
                                 probe_interval=5.0,
                                 clock=lambda: clock[0])
        verdictor = ResilientVerdictor(agent.loader, breaker=breaker)
        flows = build_flows(ids)
        chunks = [flows[i:i + 8] for i in range(0, len(flows), 8)]
        # fires on device-dispatch hits 1..4: hits 1-2 trip the
        # breaker, the probes at chunks 6 and 10 fail (hits 3-4), the
        # probe at chunk 14 succeeds — recovery mid-replay
        plan = FaultPlan([FaultRule("engine.dispatch", times=4)],
                         seed=seed)
        t0 = _metric(BREAKER_TRIPS)
        r0 = _metric(BREAKER_RECOVERIES)
        f0 = _metric(BREAKER_FALLBACK_VERDICTS)
        verdicts = []
        with faults.inject(plan):
            for i, chunk in enumerate(chunks):
                if i in (6, 10, 14):
                    clock[0] += 10.0  # probe timer expires
                verdicts.extend(verdictor.verdicts(chunk))
        deltas = (_metric(BREAKER_TRIPS) - t0,
                  _metric(BREAKER_RECOVERIES) - r0,
                  _metric(BREAKER_FALLBACK_VERDICTS) - f0)
        return verdicts, plan.trace(), list(breaker.events), deltas
    finally:
        agent.stop()


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_corpus_zero_mismatch_trip_and_recover():
    import json
    import os

    golden_path = os.path.join(os.path.dirname(__file__), "golden",
                               "corpus_verdicts.json")
    with open(golden_path) as fp:
        golden = json.load(fp)["verdicts"]

    v1, trace1, events1, (trips, recoveries, fallbacks) = \
        _chaos_corpus_replay(seed=11)
    # the headline: repeated device-dispatch failures during the
    # replay and NOT ONE wrong verdict
    assert v1 == golden
    assert trips >= 1, "breaker never tripped under injected failures"
    assert recoveries >= 1, "breaker never recovered after injection"
    assert fallbacks >= 8, "no verdicts actually rode the oracle lane"
    assert ("trip", "open") in events1
    assert ("recover", "closed") in events1
    assert events1[-1] == ("recover", "closed")

    # replayability: same plan + seed → identical fault trace AND
    # identical breaker transition sequence
    v2, trace2, events2, _ = _chaos_corpus_replay(seed=11)
    assert v2 == golden
    assert trace2 == trace1
    assert events2 == events1


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_stream_replay_with_drops_and_device_faults(tmp_path):
    """The online stream under BOTH failure modes at once: connection
    drops (client resumes) and device faults (chunks degrade to the
    oracle) — the drained verdicts still match the oracle bit-for-bit."""
    from cilium_tpu.runtime.stream import StreamClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, threshold=2, probe_interval=0.02)
    try:
        flows = _stream_flows(web, db, 64)
        oracle = svc.loader.fallback_engine
        want = [int(v) for v in
                oracle.verdict_flows(flows)["verdict"]]
        client = StreamClient(svc.socket_path, timeout=60.0,
                              reconnect=True, backoff_base=0.01,
                              reconnect_seed=5)
        plan = FaultPlan([
            FaultRule("engine.dispatch", prob=0.4, times=5),
            FaultRule("stream.frame.client", after=2, times=2,
                      exc=ConnectionError),
        ], seed=23)
        got = {}
        with faults.inject(plan):
            seqs = [client.send_flows(flows) for _ in range(10)]
            client.finish()
            for seq in seqs:
                got[seq] = list(client.result(seq))
        for seq in seqs:
            assert got[seq] == want, f"verdict mismatch in seq {seq}"
        client.close()
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# ISSUE 5: overload/drain fault points + the drain→restart warm cycle


def test_admission_fault_forces_an_explicit_shed(tmp_path):
    """An injected service.admit fault is a SHED — the request is
    refused explicitly (counted, flagged), never half-admitted."""
    from cilium_tpu.runtime.service import VerdictClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, offload=False)
    try:
        client = VerdictClient(svc.socket_path)
        flow = {"source": {"identity": int(web)},
                "destination": {"identity": int(db)},
                "l4": {"TCP": {"destination_port": 5432}},
                "traffic_direction": "INGRESS"}
        plan = FaultPlan([FaultRule("service.admit", times=1)], seed=3)
        with faults.inject(plan):
            shed = client.call({"op": "check", "flow": flow})
            assert shed["shed"] is True and shed["reason"] == "fault"
            # the fault budget is spent: the next request serves
            ok = client.call({"op": "check", "flow": flow})
            assert ok["verdict"] == 1 and "shed" not in ok
        assert plan.counts("service.admit") == (2, 1)
        client.close()
    finally:
        svc.stop()


def test_drain_fault_leaves_gate_draining_and_retry_succeeds(tmp_path):
    """A crash between stop-admitting and the flush (service.drain
    point): the drain op errors, the gate STAYS draining (fail-safe:
    no half-open re-admission), and a retried drain completes."""
    from cilium_tpu.runtime.service import VerdictClient

    per, _db, _web = _tiny_policy(5432)
    svc = _service(tmp_path, per, offload=False)
    try:
        client = VerdictClient(svc.socket_path)
        with faults.inject(FaultPlan(
                [FaultRule("service.drain", times=1)], seed=9)):
            resp = client.call({"op": "drain"})
            assert "error" in resp
            assert svc.gate.draining  # fail-safe: still draining
            retry = client.call({"op": "drain"})
            assert retry["ok"] is True
        client.close()
    finally:
        svc.stop()


def test_stream_credit_grant_loss_degrades_not_corrupts(tmp_path):
    """An injected stream.credit fault LOSES one grant: the client's
    window shrinks by one but every verdict still lands and matches —
    credit loss costs pacing, never correctness."""
    from cilium_tpu.runtime.stream import StreamClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, offload=False)
    try:
        flows = _stream_flows(web, db, 32)
        want = [int(v) for v in
                svc.loader.engine.verdict_flows(flows)["verdict"]]
        client = StreamClient(svc.socket_path, timeout=30.0)
        window = client._credits
        assert window and window > 1
        plan = FaultPlan([FaultRule("stream.credit", times=1)], seed=4)
        with faults.inject(plan):
            seqs = [client.send_flows(flows) for _ in range(6)]
            client.finish()
            for seq in seqs:
                assert list(client.result(seq)) == want
        assert plan.counts("stream.credit")[1] == 1
        # exactly one grant was lost → steady-state window is one low
        with client._cond:
            assert client._credits == window - 1
        client.close()
    finally:
        svc.stop()


def test_drain_restart_cycle_is_verdict_clean_and_warm(tmp_path):
    """THE ISSUE 5 acceptance cycle: requests in flight when the drain
    begins finish with REAL verdicts (zero ERRORs); the warm snapshot
    lands; a fresh loader (new process stand-in, same cache dir)
    restores it with ZERO recompilation and reproduces the golden
    corpus verdict-identically."""
    from cilium_tpu.runtime.metrics import WARM_RESTORES
    from cilium_tpu.runtime.service import VerdictClient

    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = str(tmp_path / "cache")
    per, db, web = _tiny_policy(5432)
    loader = Loader(cfg)
    loader.regenerate(per, revision=7)
    svc = VerdictService(loader, str(tmp_path / "svc.sock"))
    svc.start()

    corpus = [{"source": {"identity": int(web)},
               "destination": {"identity": int(db)},
               "l4": {"TCP": {"destination_port": p}},
               "traffic_direction": "INGRESS"}
              for p in (5432, 5433, 80, 5432, 9999)]
    try:
        client = VerdictClient(svc.socket_path)
        golden = client.call({"op": "verdict", "flows": corpus})
        assert "verdicts" in golden

        # in-flight requests racing the drain: every ADMITTED check
        # resolves with a real verdict, sheds are explicit
        results = []
        lock = threading.Lock()

        def caller():
            c = VerdictClient(svc.socket_path)
            for _ in range(12):
                r = c.call({"op": "check", "flow": corpus[0]})
                with lock:
                    results.append(r)
            c.close()

        threads = [threading.Thread(target=caller) for _ in range(4)]
        for t in threads:
            t.start()
        drained = svc.drain()
        for t in threads:
            t.join(timeout=30.0)
        assert drained["ok"] and drained["warm_snapshot"] is True
        admitted = [r for r in results if not r.get("shed")]
        shed = [r for r in results if r.get("shed")]
        assert all(r["verdict"] == 1 for r in admitted), admitted[:5]
        assert all(r["reason"] for r in shed)
        client.close()
    finally:
        svc.stop()

    # "restart": a fresh loader over the same artifact cache — no
    # policy replay, no fingerprint walk, no compile
    compiles0 = METRICS.histo_count("cilium_tpu_compile_seconds")
    warm0 = _metric(WARM_RESTORES)
    cfg2 = Config()
    cfg2.enable_tpu_offload = True
    cfg2.loader.cache_dir = str(tmp_path / "cache")
    loader2 = Loader(cfg2)
    assert loader2.restore_warm() is True
    assert loader2.revision == 7
    assert _metric(WARM_RESTORES) == warm0 + 1
    assert METRICS.histo_count("cilium_tpu_compile_seconds") \
        == compiles0, "warm restore recompiled"

    svc2 = VerdictService(loader2, str(tmp_path / "svc2.sock"))
    svc2.start()
    try:
        client2 = VerdictClient(svc2.socket_path)
        replay = client2.call({"op": "verdict", "flows": corpus})
        assert replay["verdicts"] == golden["verdicts"]
        client2.close()
    finally:
        svc2.stop()


# ---------------------------------------------------------------------------
# ISSUE 7: verdict-memo staleness across loader swap / rollback /
# warm restore — a policy commit can never serve a memoized verdict
# computed under a previous revision.


def _memo_session(loader, cfg, flows):
    from cilium_tpu.engine.verdict import CaptureReplay
    from cilium_tpu.ingest.columnar import flows_to_columns

    cols = flows_to_columns(flows)
    replay = CaptureReplay(loader.engine, cols.l7, cols.offsets,
                           cols.blob, cfg.engine, gen=cols.gen,
                           loader=loader)
    replay.stage_rows(cols.rec, cols.l7)
    replay.stage_unique()
    return replay, cols


def test_memo_invalidates_across_swap_rollback_warm_restore(tmp_path):
    """One replay session with a HOT memo, driven through every
    serving-state transition: revision swap (verdicts follow the new
    policy), rollback (verdicts stay with the surviving revision),
    snapshot/warm-restore (verdicts return with the restored
    revision) — each CONTENT-changing transition invalidates the
    touched memo rows (bank-scoped since ISSUE 8: a CNP change drops
    only rows of the identities it selects, counted under
    reason=bank-swap; a rollback stays a full policy-swap drop) and
    every answer is bit-equal to the serving engine's verdict_flows."""
    from cilium_tpu.runtime.metrics import VERDICT_MEMO_INVALIDATIONS

    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    per1, db, web = _tiny_policy(5432)
    loader.regenerate(per1, revision=1)
    flows = [_flow(web, db, 5432), _flow(web, db, 6000)] * 6

    replay, cols = _memo_session(loader, cfg, flows)

    def session_verdicts():
        out = replay.verdict_chunk(cols.rec, cols.l7)
        return [int(v) for v in out["verdict"]]

    def engine_verdicts():
        return [int(v) for v in
                loader.engine.verdict_flows(flows)["verdict"]]

    # memo hot under rev 1: 5432 allowed, 6000 dropped
    assert session_verdicts() == [1, 2] * 6 == engine_verdicts()
    memo = replay.memo
    inv0 = memo.invalidations
    bsw0 = _metric(VERDICT_MEMO_INVALIDATIONS,
                   {"reason": "bank-swap"})

    # CNP change: only 6000 allowed now — the hot memo must flip WITH
    # the swap, not serve rev-1 answers. The db identity's fingerprint
    # changed, so the invalidation is bank-scoped, not a full drop.
    per2, _, _ = _tiny_policy(6000)
    loader.regenerate(per2, revision=2)
    assert session_verdicts() == [2, 1] * 6 == engine_verdicts()
    assert replay.memo.invalidations >= inv0 + 1
    assert _metric(VERDICT_MEMO_INVALIDATIONS,
                   {"reason": "bank-swap"}) >= bsw0 + 1

    # mid-swap crash: rollback restores rev 2 — the session keeps
    # answering rev-2 semantics, never a torn state (a rollback is a
    # conservative FULL drop: reason=policy-swap)
    psw0 = _metric(VERDICT_MEMO_INVALIDATIONS,
                   {"reason": "policy-swap"})
    with faults.inject(FaultPlan([FaultRule("loader.swap", times=1)])):
        with pytest.raises(FaultInjected):
            loader.regenerate(per1, revision=3)
        assert loader.revision == 2
        assert session_verdicts() == [2, 1] * 6 == engine_verdicts()
    assert _metric(VERDICT_MEMO_INVALIDATIONS,
                   {"reason": "policy-swap"}) >= psw0 + 1

    # drain-style snapshot at rev 2, move on to rev 3, then warm
    # restore: the session must follow BACK to the restored revision
    assert loader.snapshot_warm() is True
    loader.regenerate(per1, revision=3)
    assert session_verdicts() == [1, 2] * 6 == engine_verdicts()
    assert loader.restore_warm() is True
    assert loader.revision == 2
    assert session_verdicts() == [2, 1] * 6 == engine_verdicts()


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_memo_golden_corpus_stable_across_cnp_change():
    """The acceptance replay for the verdict memo: the golden corpus
    replays through a memo-hot session, a policy re-commit lands
    mid-session, and the corpus verdicts are IDENTICAL before and
    after, matching the serving engine both times. Since ISSUE 8 the
    re-commit of a BYTE-IDENTICAL snapshot is a no-change delta: the
    memo must survive it UNTOUCHED (zero invalidations, hits keep
    accruing) — the churn-proof half of the staleness contract."""
    from cilium_tpu.agent import Agent
    from cilium_tpu.auth import AUTH_UNENFORCED
    from tests.test_controlplane_golden import build_agent, build_flows

    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.configure_logging = False
    agent, ids = build_agent(Agent(cfg))
    try:
        flows = build_flows(ids)
        loader = agent.loader
        replay, cols = _memo_session(loader, cfg, flows)

        def session_verdicts():
            out = replay.verdict_chunk(
                cols.rec, cols.l7, authed_pairs=AUTH_UNENFORCED)
            return [int(v) for v in out["verdict"]]

        def engine_verdicts():
            return [int(v) for v in loader.engine.verdict_flows(
                flows, authed_pairs=AUTH_UNENFORCED)["verdict"]]

        before = session_verdicts()
        assert before == engine_verdicts()
        assert replay.memo is not None and replay.memo.hits > 0
        inv0 = replay.memo.invalidations
        hits0 = replay.memo.hits

        # the SAME snapshot re-commits under a new revision (identity
        # churn that netted out): a no-change delta — the memo keeps
        # serving, bit-identically, without a drop or a refill
        loader.regenerate(loader.per_identity,
                          revision=loader.revision + 1)
        after = session_verdicts()
        assert after == before, "memo served stale verdicts after swap"
        assert after == engine_verdicts()
        assert replay.memo.invalidations == inv0, \
            "no-change commit dropped the memo"
        assert replay.memo.hits > hits0
    finally:
        agent.stop()


# ---------------------------------------------------------------------------
# ISSUE 8: churn-proof policy plane — per-bank compile failure
# isolation, identity churn-storm delivery loss, and the warm-restart
# memo-retention regression.


def _paths_policy(paths):
    """_tiny_policy with an HTTP path allow-list (drives DFA banks)."""
    from cilium_tpu.core.identity import IdentityAllocator
    from cilium_tpu.core.labels import LabelSet
    from cilium_tpu.policy.api import (
        EndpointSelector,
        IngressRule,
        PortProtocol,
        PortRule,
        Rule,
    )
    from cilium_tpu.policy.api.l7 import L7Rules, PortRuleHTTP
    from cilium_tpu.policy.mapstate import PolicyResolver
    from cilium_tpu.policy.repository import Repository
    from cilium_tpu.policy.selectorcache import SelectorCache

    rules = [Rule(
        endpoint_selector=EndpointSelector.from_labels(app="db"),
        ingress=(IngressRule(
            from_endpoints=(EndpointSelector.from_labels(app="web"),),
            to_ports=(PortRule(
                ports=(PortProtocol(80, Protocol.TCP),),
                rules=L7Rules(http=tuple(
                    PortRuleHTTP(path=p, method="GET")
                    for p in paths))),)),),
    )]
    alloc = IdentityAllocator()
    db = alloc.allocate(LabelSet.from_dict({"app": "db"}))
    web = alloc.allocate(LabelSet.from_dict({"app": "web"}))
    cache = SelectorCache(alloc)
    repo = Repository()
    repo.add(rules, sanitize=False)
    return ({db: PolicyResolver(repo, cache).resolve(
        alloc.lookup(db))}, db, web)


def _http_flow(web, db, path):
    from cilium_tpu.core.flow import HTTPInfo, L7Type

    return Flow(src_identity=web, dst_identity=db, dport=80,
                protocol=Protocol.TCP,
                direction=TrafficDirection.INGRESS, l7=L7Type.HTTP,
                http=HTTPInfo(method="GET", path=path))


def test_bank_compile_fault_quarantines_only_its_bank(tmp_path):
    """loader.bank_compile fires on the one changed bank of a CNP
    add: the regeneration COMMITS (no abort, no rollback), every
    unchanged bank serves golden verdicts bit-identically, the
    quarantine is counted, and the TTL retry recovers the bank."""
    from cilium_tpu.runtime.metrics import BANK_QUARANTINED

    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.engine.bank_size = 4
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    paths = [f"/p{i}/.*" for i in range(16)]
    per1, db, web = _paths_policy(paths)
    loader.regenerate(per1, revision=1)
    golden_flows = [_flow(web, db, 5432)] + \
        [_http_flow(web, db, f"/p{i}/x") for i in range(16)] + \
        [_http_flow(web, db, "/nope")]
    golden = [int(v) for v in
              loader.engine.verdict_flows(golden_flows)["verdict"]]
    rollbacks0 = _metric(LOADER_ROLLBACKS)
    q0 = _metric(BANK_QUARANTINED, {"field": "path"})

    per2, db, web = _paths_policy(paths + ["/fresh/.*"])
    with faults.inject(FaultPlan(
            [FaultRule("loader.bank_compile", times=1)])):
        loader.regenerate(per2, revision=2)  # commits despite the fault
    assert loader.revision == 2
    assert _metric(LOADER_ROLLBACKS) == rollbacks0, \
        "bank failure escalated to a full rollback"
    assert _metric(BANK_QUARANTINED, {"field": "path"}) == q0 + 1
    # unchanged banks: bit-identical golden verdicts
    after = [int(v) for v in
             loader.engine.verdict_flows(golden_flows)["verdict"]]
    assert after == golden
    # the failed bank's new pattern fails CLOSED while quarantined
    out = loader.engine.verdict_flows([_http_flow(web, db, "/fresh/x")])
    assert int(out["verdict"][0]) == 2
    # TTL retry: recompile succeeds, the new pattern enforces
    for q in loader.bank_registry._quarantine.values():
        q.until = 0.0
    loader.regenerate(per2, revision=3)
    out = loader.engine.verdict_flows([_http_flow(web, db, "/fresh/x")])
    assert int(out["verdict"][0]) == 5
    assert not loader._degraded


def test_compile_worker_death_retries_then_serves_correctly(tmp_path):
    """ISSUE 13: a compile.worker death mid-regeneration is absorbed
    by the queue's retry — the CNP add COMMITS, the new rule enforces,
    nothing quarantines, and the respawn counter moved."""
    from cilium_tpu.runtime.metrics import COMPILE_WORKER_DEATHS

    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.engine.bank_size = 4
    cfg.compile.workers = 1
    cfg.compile.backoff_base_s = 0.01
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    paths = [f"/p{i}/.*" for i in range(8)]
    per1, db, web = _paths_policy(paths)
    loader.regenerate(per1, revision=1)
    deaths0 = _metric(COMPILE_WORKER_DEATHS)

    per2, db, web = _paths_policy(paths + ["/fresh/.*"])
    with faults.inject(FaultPlan(
            [FaultRule("compile.worker", times=1)])):
        loader.regenerate(per2, revision=2)
    assert loader.revision == 2
    assert _metric(COMPILE_WORKER_DEATHS) == deaths0 + 1
    assert not loader._degraded, \
        "a single worker death must be retried, not quarantined"
    out = loader.engine.verdict_flows([_http_flow(web, db, "/fresh/x")])
    assert int(out["verdict"][0]) == 5
    loader.close()


def test_compile_worker_death_exhaustion_quarantines_with_cover(
        tmp_path):
    """Retry budget exhausted by repeated worker deaths: the bank
    quarantines — its NEW pattern fails CLOSED, unchanged banks serve
    bit-identically — and the exhausted-fault recovery recompiles."""
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.engine.bank_size = 4
    cfg.compile.workers = 1
    cfg.compile.max_retries = 1
    cfg.compile.backoff_base_s = 0.01
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    paths = [f"/p{i}/.*" for i in range(8)]
    per1, db, web = _paths_policy(paths)
    loader.regenerate(per1, revision=1)
    golden_flows = [_http_flow(web, db, f"/p{i}/x") for i in range(8)]
    golden = [int(v) for v in
              loader.engine.verdict_flows(golden_flows)["verdict"]]

    per2, db, web = _paths_policy(paths + ["/fresh/.*"])
    with faults.inject(FaultPlan(
            [FaultRule("compile.worker", times=10)])):
        loader.regenerate(per2, revision=2)
    assert loader.revision == 2
    assert loader._degraded, "exhausted retries must quarantine"
    after = [int(v) for v in
             loader.engine.verdict_flows(golden_flows)["verdict"]]
    assert after == golden, "unchanged banks must serve bit-identically"
    out = loader.engine.verdict_flows([_http_flow(web, db, "/fresh/x")])
    assert int(out["verdict"][0]) == 2, "uncovered pattern fails CLOSED"
    # recovery: TTL lapse + regenerate with the fault exhausted
    for q in loader.bank_registry._quarantine.values():
        q.until = 0.0
    loader.regenerate(per2, revision=3)
    out = loader.engine.verdict_flows([_http_flow(web, db, "/fresh/x")])
    assert int(out["verdict"][0]) == 5
    assert not loader._degraded
    loader.close()


def test_artifact_fetch_fault_degrades_to_recompile_not_crash(
        tmp_path):
    """ISSUE 13: a lost/corrupt distributed bank artifact
    (artifact.fetch fires on a fresh loader sharing the cache dir)
    recompiles — verdicts identical, nothing quarantined, fetch
    corruption counted."""
    from cilium_tpu.runtime.metrics import BANK_ARTIFACT_FETCHES

    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.engine.bank_size = 4
    cfg.loader.cache_dir = str(tmp_path / "cache")
    paths = [f"/p{i}/.*" for i in range(8)]
    per1, db, web = _paths_policy(paths)
    producer = Loader(cfg)
    producer.regenerate(per1, revision=1)
    golden_flows = [_http_flow(web, db, f"/p{i}/x") for i in range(8)]
    golden = [int(v) for v in
              producer.engine.verdict_flows(golden_flows)["verdict"]]
    producer.close()

    # a fresh "host" fetches bank artifacts instead of compiling —
    # and every fetch faults: the plane recompiles, never crashes.
    # (The whole-policy artifact is blinded so the per-bank path runs;
    # bank-artifact reads still reach the real cache.)
    consumer = Loader(cfg)
    consumer._cache.get = lambda key, _real=consumer._cache.get: (
        None if not key.startswith("bankart-") else _real(key))
    corrupt0 = _metric(BANK_ARTIFACT_FETCHES, {"result": "corrupt"})
    with faults.inject(FaultPlan(
            [FaultRule("artifact.fetch", prob=1.0, times=None)])):
        consumer.regenerate(per1, revision=1)
    assert _metric(BANK_ARTIFACT_FETCHES,
                   {"result": "corrupt"}) > corrupt0
    assert not consumer._degraded
    got = [int(v) for v in
           consumer.engine.verdict_flows(golden_flows)["verdict"]]
    assert got == golden
    consumer.close()


def test_corrupt_artifact_plus_compile_failure_quarantines_with_cover(
        tmp_path):
    """The combined ISSUE-13 outage: the distributed artifact is lost
    (artifact.fetch fires) AND the recompile fails (loader.bank_compile
    fires) — the bank must reach QUARANTINE-WITH-COVER: unchanged
    banks bit-identical, the uncovered pattern fails CLOSED, and the
    plane recovers once the faults exhaust and the TTL lapses."""
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.engine.bank_size = 4
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    paths = [f"/p{i}/.*" for i in range(8)]
    per1, db, web = _paths_policy(paths)
    loader.regenerate(per1, revision=1)
    golden_flows = [_http_flow(web, db, f"/p{i}/x") for i in range(8)]
    golden = [int(v) for v in
              loader.engine.verdict_flows(golden_flows)["verdict"]]

    per2, db, web = _paths_policy(paths + ["/fresh/.*"])
    with faults.inject(FaultPlan([
            FaultRule("artifact.fetch", times=8),
            FaultRule("loader.bank_compile", times=1)])):
        loader.regenerate(per2, revision=2)
    assert loader.revision == 2
    assert loader._degraded, "lost artifact + failed compile must " \
        "quarantine"
    after = [int(v) for v in
             loader.engine.verdict_flows(golden_flows)["verdict"]]
    assert after == golden
    out = loader.engine.verdict_flows([_http_flow(web, db, "/fresh/x")])
    assert int(out["verdict"][0]) == 2, "uncovered pattern fails CLOSED"
    for q in loader.bank_registry._quarantine.values():
        q.until = 0.0
    loader.regenerate(per2, revision=3)
    assert not loader._degraded
    out = loader.engine.verdict_flows([_http_flow(web, db, "/fresh/x")])
    assert int(out["verdict"][0]) == 5
    loader.close()


def test_fresh_loader_fetches_bank_artifacts_instead_of_compiling(
        tmp_path):
    """The distribution path itself: with a shared artifact cache, a
    restarted/remote loader serves the same policy with ZERO bank
    compiles (all groups fetched, checksum-verified)."""
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.engine.bank_size = 4
    cfg.loader.cache_dir = str(tmp_path / "cache")
    paths = [f"/p{i}/.*" for i in range(8)]
    per1, db, web = _paths_policy(paths)
    producer = Loader(cfg)
    producer.regenerate(per1, revision=1)
    assert producer.bank_registry.compiles > 0
    producer.close()

    consumer = Loader(cfg)
    # defeat the whole-policy artifact hit so the per-bank path runs
    consumer._cache.get = lambda key, _real=consumer._cache.get: (
        None if not key.startswith("bankart-") else _real(key))
    consumer.regenerate(per1, revision=1)
    assert consumer.bank_registry.compiles == 0, \
        "every bank should have been fetched, not compiled"
    assert consumer.bank_registry.artifact_hits > 0
    got = [int(v) for v in consumer.engine.verdict_flows(
        [_http_flow(web, db, "/p3/x")])["verdict"]]
    assert got == [5]
    consumer.close()


def test_kvstore_churn_storm_loses_deliveries_not_correctness():
    """kvstore.churn_storm drops identity add/delete deliveries on a
    watching allocator mid-burst: the dropped events are isolated and
    counted, the WRITER's own allocations (and the verdicts they
    drive) are untouched, and a fresh replay-then-follow converges to
    the store's true mapping."""
    from cilium_tpu.core.labels import LabelSet
    from cilium_tpu.identity_kvstore import ClusterIdentityAllocator
    from cilium_tpu.kvstore import KVStore

    store = KVStore()
    writer = ClusterIdentityAllocator(store).start()
    watcher_events = []
    watcher = ClusterIdentityAllocator(
        store, on_change=lambda nid, lbl: watcher_events.append(
            (int(nid), lbl))).start()

    fired0 = _metric(FAULTS_INJECTED, {"point": "kvstore.churn_storm"})
    errs0 = _metric(KVSTORE_WATCH_ERRORS)
    with faults.inject(FaultPlan(
            [FaultRule("kvstore.churn_storm", prob=0.4)], seed=11)):
        ids = [writer.allocate(LabelSet.from_dict({"app": f"a{i}"}))
               for i in range(24)]
    assert _metric(FAULTS_INJECTED,
                   {"point": "kvstore.churn_storm"}) > fired0
    assert _metric(KVSTORE_WATCH_ERRORS) > errs0
    # the writer itself is authoritative: every id resolves locally
    for i, nid in enumerate(ids):
        assert writer.lookup_by_labels(
            LabelSet.from_dict({"app": f"a{i}"})) == nid
    # the storm-hit watcher lost SOME deliveries but never corrupted:
    # everything it did see matches the writer's mapping
    for nid, lbl in watcher_events:
        if lbl is not None:
            assert writer.lookup_by_labels(lbl) == nid
    # a fresh replay-then-follow (restart after the storm) converges
    fresh = ClusterIdentityAllocator(store).start()
    for i, nid in enumerate(ids):
        assert fresh.lookup_by_labels(
            LabelSet.from_dict({"app": f"a{i}"})) == nid
    writer.close()
    watcher.close()
    fresh.close()


# ---------------------------------------------------------------------------
# ISSUE 16: serving-fleet fault points — heartbeat loss runs the
# suspicion clock down to a FAIL-CLOSED death, and an interrupted
# handoff never leaves a stream leased on two live hosts.


def _fleet_world(tmp_path, hosts=3):
    from cilium_tpu.ingest import synth
    from cilium_tpu.runtime.fleetserve import FleetRouter, HostReplica

    scenario = synth.scenario_by_name("http", 24, 64)
    per_identity, scenario = synth.realize_scenario(scenario)
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    loader.regenerate(per_identity, revision=1)
    cfg.serve.pack_interval_ms = 10.0
    replicas = [HostReplica(i, loader, capacity=8, lease_ttl_s=60.0)
                for i in range(hosts)]
    router = FleetRouter(replicas, heartbeat_interval_s=1.0,
                         suspicion_ttl_s=3.0, spill_headroom=0.0)
    return router, loader, scenario


def test_fleet_heartbeat_loss_suspicion_is_fail_closed(tmp_path):
    """Armed fleet.heartbeat fires eat every replica's beats: once
    the suspicion TTL lapses, the sweep declares them dead (counted),
    every lease closes (exact books), new admits shed coherently, and
    a submit against a dead placement is the TYPED resume error —
    never fail-open service from a host nobody has heard from."""
    from cilium_tpu.runtime.fleetserve import HostDead
    from cilium_tpu.runtime.metrics import FLEET_HOST_DEATHS
    from cilium_tpu.runtime.serveloop import ShedError

    clk = simclock.VirtualClock()
    with simclock.use(clk):
        router, loader, _ = _fleet_world(tmp_path)
        leases = {}
        for k in range(6):
            _host, lease = router.connect(f"s{k}")
            leases[f"s{k}"] = lease
        deaths0 = _metric(FLEET_HOST_DEATHS)
        with faults.inject(FaultPlan(
                [FaultRule("fleet.heartbeat", times=9)], seed=0)):
            died = []
            for dt in (1.0, 1.0, 1.1):  # 3 beat rounds, all lost
                clk.advance(dt)
                died += router.beat()
        assert sorted(died) == sorted(r.name for r in router.replicas)
        assert _metric(FLEET_HOST_DEATHS) == deaths0 + 3
        # fail-closed: no live host → a coherent explicit shed
        with pytest.raises(ShedError):
            router.connect("fresh")
        # a dead placement is the typed resume path, never stream-fatal
        with pytest.raises(HostDead):
            router.submit("s0", leases["s0"], None)
        assert router.books() == (0, 0)
        assert router.conservation_violation() is None
        # warm rejoin: resume re-grants exactly once, books exact
        for r in router.replicas:
            router.rejoin(r.name)
        router.connect("s0", resume=True)
        assert router.books() == (1, 1)
        assert router.conservation_violation() is None


def test_fleet_handoff_interrupt_conserves_leases(tmp_path):
    """A fleet.handoff fire interrupts the dead host's lease
    migration mid-batch: the un-re-granted remainder stays UNPLACED
    (client-resume territory) — at no instant does any stream hold
    leases on two live hosts, and the fleet books stay exact through
    the interrupt and through every later resume."""
    clk = simclock.VirtualClock()
    with simclock.use(clk):
        router, loader, _ = _fleet_world(tmp_path)
        streams = [f"h{k}" for k in range(9)]
        for s in streams:
            router.connect(s)
        counts = {}
        for s in streams:
            host = router.placements[s]
            counts[host] = counts.get(host, 0) + 1
        victim = max(counts, key=lambda h: counts[h])
        doomed = counts[victim]
        assert doomed >= 2  # the interrupt needs a batch to cut
        with faults.inject(FaultPlan(
                [FaultRule("fleet.handoff", times=1)], seed=0)):
            router.kill(victim)
        assert router.partial_handoffs == 1
        assert router.handoffs == 0  # the fire cut the whole batch
        assert router.conservation_violation() is None
        bal, occ = router.books()
        assert bal == occ
        # every stream resumes somewhere LIVE, still without a dup
        for s in streams:
            host, _lease = router.connect(s, resume=True)
            assert host != victim
        assert router.conservation_violation() is None
        assert router.books() == (len(streams), len(streams))


def test_warm_restore_same_artifact_keeps_memo(tmp_path):
    """ISSUE-8 satellite regression: a drain → warm-restore cycle
    whose artifact key is UNCHANGED must not drop the device memo or
    the unique-row buffer — the restarted service keeps its memo hit
    ratio instead of re-verdicting the whole row universe."""
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    per1, db, web = _tiny_policy(5432)
    loader.regenerate(per1, revision=1)
    flows = [_flow(web, db, 5432), _flow(web, db, 6000)] * 8

    replay, cols = _memo_session(loader, cfg, flows)
    out = replay.verdict_chunk(cols.rec, cols.l7)
    golden = [int(v) for v in out["verdict"]]
    memo = replay.memo
    assert memo is not None and memo.hits > 0
    inv0 = memo.invalidations
    misses0 = memo.misses
    hits0 = memo.hits
    uniq_buf = replay.unique_rows
    assert uniq_buf is not None

    # drain-style snapshot, then an immediate warm restore (process
    # kept, artifact unchanged — the warm-restart fast path)
    assert loader.snapshot_warm() is True
    assert loader.restore_warm() is True
    after = replay.verdict_chunk(cols.rec, cols.l7)
    assert [int(v) for v in after["verdict"]] == golden
    assert memo.invalidations == inv0, \
        "same-key warm restore dropped the memo"
    assert memo.misses == misses0, "memo refilled after warm restore"
    assert memo.hits > hits0
    assert replay.unique_rows is uniq_buf, \
        "unique-row device buffer was re-staged"


def test_warm_restore_ignores_an_old_kernel_autotune_key(tmp_path):
    """A format-1 warm snapshot written while the engine still had a
    staging autotuner carries its cache under ``kernel_autotune``. The
    key means nothing now: the snapshot still restores, and the
    restarted loader serves the snapshot's revision with the same
    verdicts."""
    from cilium_tpu.runtime.loader import WARM_STATE_KEY

    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    per1, db, web = _tiny_policy(5432)
    loader.regenerate(per1, revision=4)
    flows = [_flow(web, db, 5432), _flow(web, db, 6000)]
    golden = [int(v) for v in loader.engine.verdict_flows(flows)["verdict"]]
    assert loader.snapshot_warm() is True
    state = dict(loader._cache.get(WARM_STATE_KEY))
    assert "kernel_autotune" not in state
    state["kernel_autotune"] = {
        "('path', (7, 2048, 28), (), 256, 'tpu')": {
            "impl": "dfa-dense", "dense_ms": 1.5, "nfa_ms": None}}
    loader._cache.put(WARM_STATE_KEY, state)

    loader2 = Loader(cfg)
    assert loader2.restore_warm() is True
    assert loader2.revision == 4
    got = loader2.engine.verdict_flows(flows)["verdict"]
    assert [int(v) for v in got] == golden


# ---------------------------------------------------------------------------
# ISSUE 20: tenant.quota + canary.dispatch fault points


def test_tenant_quota_fault_falls_to_conservative_default():
    """A LOST quota read (tenant.quota fires) must return the
    conservative default share — bounded, never unbounded — counted
    ``fault-default``; once the fault exhausts, the live entry serves
    again and a lapsed TTL reads as the default too."""
    from cilium_tpu.runtime.metrics import TENANT_QUOTA_READS
    from cilium_tpu.runtime.tenant import TenantQuotas

    now = [0.0]
    quotas = TenantQuotas(default_share=0.25, ttl_s=10.0,
                          clock=lambda: now[0])
    quotas.set_share("a", 0.9)
    fd0 = _metric(TENANT_QUOTA_READS, {"result": "fault-default"})
    live0 = _metric(TENANT_QUOTA_READS, {"result": "live"})
    with faults.inject(FaultPlan([FaultRule("tenant.quota", times=1)])):
        assert quotas.share_of("a") == 0.25, \
            "faulted quota read must be the conservative default"
        assert quotas.share_of("a") == 0.9, \
            "after the fault exhausts the live entry serves"
    assert _metric(TENANT_QUOTA_READS,
                   {"result": "fault-default"}) == fd0 + 1
    assert _metric(TENANT_QUOTA_READS, {"result": "live"}) == live0 + 1
    # TTL lapse at EXACTLY the tick (closed boundary) → default
    now[0] = 10.0
    assert quotas.share_of("a") == 0.25


def test_canary_dispatch_fault_aborts_canary_serving_untouched():
    """A failed shadow dispatch (canary.dispatch fires) must ABORT the
    canary — staged generation dropped, serving generation untouched,
    commit refused as aborted — never crash the serve path."""
    from cilium_tpu.core.flow import Verdict
    from cilium_tpu.runtime.canary import (
        STATE_ABORTED,
        CanaryController,
    )
    from cilium_tpu.runtime.metrics import CANARY_COMMITS

    cfg = Config()
    cfg.enable_tpu_offload = True
    loader = Loader(cfg)
    per1, db, web = _tiny_policy(5432)
    loader.regenerate(per1, revision=1)
    flows = [_flow(web, db, 5432), _flow(web, db, 6000)]
    served = [int(v) for v in
              loader.engine.verdict_flows(flows)["verdict"]]

    canary = CanaryController(loader, sample_fraction=1.0,
                              diff_budget=0.0, min_samples=1)
    canary.stage(per1, revision=2)
    ab0 = _metric(CANARY_COMMITS, {"result": "aborted"})
    with faults.inject(FaultPlan([FaultRule("canary.dispatch",
                                            times=1)])):
        canary.observe_chunk(flows, served)  # must not raise
    assert canary.state == STATE_ABORTED
    assert loader.canary_engine is None, "staged generation dropped"
    assert loader.revision == 1, "serving generation untouched"
    assert _metric(CANARY_COMMITS, {"result": "aborted"}) == ab0 + 1
    after = [int(v) for v in
             loader.engine.verdict_flows(flows)["verdict"]]
    assert after == served
    assert Verdict(after[0]) is not None  # decodable, not ERROR junk
    loader.close()
