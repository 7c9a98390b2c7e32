"""The serving fleet (ISSUE 16): stream-affinity routing through the
rendezvous router, the FleetModel end-to-end soak at smoke scale
(kill + warm rejoin with zero losses), explain queries that follow the
stream across a failover, and the three typed fleet shed reasons
(host-draining / host-overloaded / partitioned) — each an explicit,
counted refusal, never a silent queue or fail-open service."""

import os

import pytest

from cilium_tpu.core.config import Config
from cilium_tpu.ingest import synth
from cilium_tpu.ingest.binary import (
    capture_from_bytes,
    capture_to_bytes,
)
from cilium_tpu.runtime import admission, simclock
from cilium_tpu.runtime.explain import EXPLAIN
from cilium_tpu.runtime.fleetserve import (
    FleetModel,
    FleetRouter,
    HostDead,
    HostReplica,
)
from cilium_tpu.runtime.loader import Loader
from cilium_tpu.runtime.metrics import METRICS, ADMISSION_SHED
from cilium_tpu.runtime.serveloop import ShedError
from cilium_tpu.runtime.simclock import VirtualClock
from cilium_tpu.runtime.tracing import TRACER


def _fleet_world(tmp_path, hosts=3, capacity=8):
    scenario = synth.scenario_by_name("http", 24, 64)
    per_identity, scenario = synth.realize_scenario(scenario)
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    loader.regenerate(per_identity, revision=1)
    cfg.serve.pack_interval_ms = 10.0
    replicas = [HostReplica(i, loader, capacity=capacity,
                            lease_ttl_s=60.0)
                for i in range(hosts)]
    router = FleetRouter(replicas, heartbeat_interval_s=1.0,
                         suspicion_ttl_s=3.0, spill_headroom=0.0)
    sections = capture_from_bytes(capture_to_bytes(scenario.flows[:16]))
    return router, loader, sections


@pytest.fixture(autouse=True)
def _clean_explain():
    EXPLAIN.clear()
    yield
    EXPLAIN.clear()


def _shed_count(reason):
    return METRICS.get(ADMISSION_SHED,
                       labels={"surface": "fleet",
                               "class": admission.CLASS_DATA,
                               "reason": reason})


# ------------------------------------------- routing & affinity
def test_rendezvous_affinity_is_sticky_and_spread(tmp_path):
    """Placement is deterministic per stream (reconnects route home)
    and spreads across the fleet — affinity without a coordinator."""
    clk = VirtualClock()
    with simclock.use(clk):
        router, _loader, _sections = _fleet_world(tmp_path, capacity=64)
        first = {}
        for k in range(30):
            host, _lease = router.connect(f"aff-{k}")
            first[f"aff-{k}"] = host
        assert len(set(first.values())) > 1, "everything on one host"
        # resume routes home: same host, no second grant
        for k in range(30):
            host, _lease = router.connect(f"aff-{k}", resume=True)
            assert host == first[f"aff-{k}"]
        assert router.books() == (30, 30)
        assert router.conservation_violation() is None


# ------------------------------------------- the end-to-end soak
def test_fleet_model_smoke_kill_and_rejoin_zero_losses():
    """The FleetModel at smoke scale: a mid-storm hard kill plus a
    warm rejoin, with every invariant swept per event — no
    violations, no unrecovered chunk, and the rejoin satisfied from
    the shared artifact store (zero bank recompiles)."""
    model = FleetModel(seed=0, streams=400, hosts=4, virtual_s=40.0,
                       ramp_s=10.0, storms=1, storm_size=50,
                       active_fraction=0.2, n_rules=12,
                       chunk_flows=4, pool_chunks=8)
    result = model.run()
    assert result["violations"] == []
    assert result["host_deaths"] >= 1
    assert result["rejoins"] >= 1
    assert result["unrecovered"] == 0
    assert result["resolved"] > 0
    assert result["rejoin_compiles"] == 0, \
        "warm rejoin recompiled banks despite the shared store"
    assert result["rejoin_warm_restores"] >= 1


# ------------------------------------------- explain across failover
def test_explain_follows_the_stream_across_failover(tmp_path):
    """A traced chunk's explanation stays answerable through the
    serving host's death and warm rejoin: each replica records into
    its OWN store, the store survives revival, and the router
    forwards the query to the owner — attributed to the host that
    actually served the verdict."""
    clk = VirtualClock()
    with simclock.use(clk):
        router, _loader, sections = _fleet_world(tmp_path)
        host, lease = router.connect("traced")
        TRACER.configure(enabled=True, sample_rate=1.0)
        with TRACER.trace("stream.chunk") as ctx:
            ticket = router.submit("traced", lease, sections)
            tid = ctx.trace_id
        clk.advance(0.02)
        router.step_all()
        assert ticket.done and ticket.error is None
        out = router.explain(tid)
        assert out["found"] is True
        assert out["host"] == host
        # the serving host dies and warm-rejoins: the verdict's
        # explanation is still answerable, still host-attributed
        router.kill(host)
        router.rejoin(host)
        after = router.explain(tid)
        assert after["found"] is True
        assert after["host"] == host
        assert after["served_equals_fresh"] is True
        # a miss is explicit, never a crash
        miss = router.explain("deadbeefdeadbeef")
        assert miss["found"] is False


# ------------------------------------------- typed shed reasons
def test_shed_reason_host_draining_unpins(tmp_path):
    clk = VirtualClock()
    with simclock.use(clk):
        router, _loader, _sections = _fleet_world(tmp_path)
        host, _lease = router.connect("d0")
        before = _shed_count(admission.SHED_HOST_DRAINING)
        router.begin_drain(host)
        with pytest.raises(ShedError) as ei:
            router.connect("d0", resume=True)
        assert ei.value.reason == admission.SHED_HOST_DRAINING
        assert _shed_count(admission.SHED_HOST_DRAINING) == before + 1
        # the refusal unpinned the stream: the retry re-places it on
        # a SERVING host instead of bouncing off the drain forever
        host2, _lease = router.connect("d0")
        assert host2 != host


def test_shed_reason_host_overloaded_is_fleet_coherent(tmp_path):
    clk = VirtualClock()
    with simclock.use(clk):
        router, _loader, _sections = _fleet_world(
            tmp_path, hosts=2, capacity=4)
        before = _shed_count(admission.SHED_HOST_OVERLOADED)
        admitted = 0
        shed = None
        for k in range(2 * 4 + 1):
            try:
                router.connect(f"ov-{k}")
                admitted += 1
            except ShedError as e:
                shed = e
                break
        # every slot on every live host fills before the first shed —
        # the router spills past saturated hosts rather than refusing
        # while a peer still has headroom
        assert admitted == 2 * 4
        assert shed is not None
        assert shed.reason == admission.SHED_HOST_OVERLOADED
        assert _shed_count(admission.SHED_HOST_OVERLOADED) == before + 1


def test_shed_reason_partitioned_fails_closed(tmp_path):
    """A partitioned host refuses service on its OWN — it cannot tell
    a healthy fleet from a split brain, so serving possibly-stale
    policy is off the table even before suspicion declares it dead."""
    clk = VirtualClock()
    with simclock.use(clk):
        router, _loader, sections = _fleet_world(tmp_path)
        host, lease = router.connect("p0")
        before = _shed_count(admission.SHED_PARTITIONED)
        router.partition(host)
        with pytest.raises(ShedError) as ei:
            router.submit("p0", lease, sections)
        assert ei.value.reason == admission.SHED_PARTITIONED
        assert _shed_count(admission.SHED_PARTITIONED) == before + 1
        # the router fences the pinned stream too: re-placing before
        # the death is DECLARED would leave the lease live on two
        # hosts (the double-lease window DST seed 197 caught)
        with pytest.raises(ShedError) as ei:
            router.connect("p0", resume=True)
        assert ei.value.reason == admission.SHED_PARTITIONED
        assert router.conservation_violation() is None
        # suspicion runs the host down on the virtual clock; the
        # stream's lease migrates and a resume serves it elsewhere
        for _ in range(4):
            clk.advance(1.1)
            router.beat()
        host2, lease2 = router.connect("p0", resume=True)
        assert host2 != host
        ticket = router.submit("p0", lease2, sections)
        clk.advance(0.02)
        router.step_all()
        assert ticket.done and ticket.error is None
        assert router.conservation_violation() is None


@pytest.mark.slow
@pytest.mark.skipif(os.environ.get("CILIUM_TPU_FLEET_FULL") != "1",
                    reason="full >=1M-stream scale runs via "
                           "`make serve-fleet` "
                           "(CILIUM_TPU_FLEET_FULL=1)")
def test_fleet_full_scale(tmp_path):
    """The `make serve-fleet` gate set at the real scale: >=1M
    concurrent streams, >=4 hosts, every gate armed (incl. the
    p99-vs-single-host bound)."""
    from cilium_tpu.runtime import fleetserve

    rc = fleetserve.main([
        "--streams", "1050000", "--hosts", "4",
        "--out", str(tmp_path / "BENCH_FLEET_SERVE_full.jsonl")])
    assert rc == 0


# ------------------------------------------- cross-host trace stitching
def test_trace_stitches_across_host_death(tmp_path):
    """ISSUE 17 tentpole: a traced stream killed mid-chunk on host A
    and replayed on host B keeps ONE trace id — spans from BOTH
    hosts, the `fleet.handoff` event between them, causally ordered
    by the bumped epoch."""
    clk = VirtualClock()
    with simclock.use(clk):
        router, _loader, sections = _fleet_world(tmp_path)
        TRACER.configure(enabled=True, sample_rate=1.0)
        TRACER.clear()
        host_a, lease = router.connect("st0")
        with TRACER.trace("stream.chunk") as ctx:
            ticket = router.submit("st0", lease, sections)
            tid = ctx.trace_id
        assert ticket.trace_id == tid and ticket.epoch == 0
        # host A dies with the chunk IN FLIGHT: abandoned exactly
        # once, resolved as the typed lease-closed error
        router.kill(host_a)
        assert ticket.done and ticket.error == "lease-closed"
        # the client replay: reconnect-with-resume + resubmit with NO
        # active trace context — the router stitches the stored one
        # (same id, bumped epoch) onto the replayed chunk
        host_b, lease2 = router.connect("st0", resume=True)
        assert host_b != host_a
        t2 = router.submit("st0", lease2, sections)
        assert t2.trace_id == tid
        assert t2.epoch > ticket.epoch
        clk.advance(0.02)
        router.step_all()
        assert t2.done and t2.error is None
        stitched = router.trace(tid)
        assert stitched["stitched"] is True
        assert host_a in stitched["hosts"]
        assert host_b in stitched["hosts"]
        assert stitched["epochs"] == [0, 1]
        names = [r["name"] for r in stitched["records"]]
        assert "fleet.handoff" in names
        assert "serve.abandon" in names
        # causal order: every epoch-0 record precedes every epoch-1
        # record, regardless of wall readings
        epochs = [r.get("epoch", 0) for r in stitched["records"]]
        assert epochs == sorted(epochs)
        # the explain plane links the stitched timeline
        out = router.explain(tid)
        assert out["found"] is True
        assert out["trace"]["stitched"] is True
        assert set(out["trace"]["hosts"]) >= {host_a, host_b}


# ------------------------------------------- the fleet event journal
def test_journal_folds_to_router_books(tmp_path):
    """The journal's DST invariant at test scale: after a kill, a
    partition run down by suspicion, a drain/restart, and three warm
    rejoins, folding the event journal reproduces the router's exact
    fleet books."""
    clk = VirtualClock()
    with simclock.use(clk):
        router, _loader, _sections = _fleet_world(tmp_path,
                                                  capacity=64)
        for k in range(12):
            router.connect(f"j{k}")
        a, b, c = (router.replicas[i].name for i in range(3))
        assert router.journal_consistent() is None
        router.kill(a)
        assert router.journal_consistent() is None
        router.partition(b)
        for _ in range(4):
            clk.advance(1.1)
            router.beat()
        assert router.journal_consistent() is None
        router.begin_drain(c)
        router.restart_host(c)
        router.rejoin(a)
        router.rejoin(b)
        router.rejoin(c)
        msg = router.journal_consistent()
        assert msg is None, msg
        st = router.status()
        assert st["journal"]["consistent"] is True
        counts = st["journal"]["counts"]
        assert counts.get("host-death", 0) >= 2
        assert counts.get("host-rejoin", 0) == 3
        assert counts.get("drain-begin", 0) == 1
        assert counts.get("host-restart", 0) == 1


# ------------------------------------------- continuous flow export
def test_flow_export_merges_hosts_and_round_trips_serde(tmp_path):
    """The flow aggregator feeds off the explain plane's sampled
    entries, the router merge keeps host attribution, and the JSONL
    export parses straight back through the hubble serde."""
    from cilium_tpu.ingest.hubble import read_jsonl

    clk = VirtualClock()
    with simclock.use(clk):
        router, _loader, sections = _fleet_world(tmp_path)
        TRACER.configure(enabled=True, sample_rate=1.0)
        host, lease = router.connect("fx0")
        with TRACER.trace("stream.chunk"):
            t = router.submit("fx0", lease, sections)
        clk.advance(0.02)
        router.step_all()
        assert t.done and t.error is None
        merged = router.flows()
        assert merged["records"] > 0
        assert merged["aggregated"] > 0
        assert merged["flows"], "no aggregated keys"
        assert merged["flows"][0]["hosts"], \
            "merged row lost its host attribution"
        replica = next(r for r in router.replicas if r.name == host)
        path = str(tmp_path / "flows.jsonl")
        n = replica.loop.flows.export_jsonl(path)
        assert n > 0
        assert len(list(read_jsonl(path))) == n, \
            "export did not round-trip flow_from_dict"


# ------------------------------------------- host-labeled series (S1)
def test_serve_metrics_are_host_labeled_per_replica(tmp_path):
    """Regression pin: in-process fleet replicas must not collide on
    one unlabeled series — the serve-plane families carry each
    replica's host label."""
    from cilium_tpu.runtime.metrics import SERVE_LEASE_GRANTS

    clk = VirtualClock()
    with simclock.use(clk):
        router, _loader, _sections = _fleet_world(tmp_path,
                                                  capacity=64)
        r0, r1 = router.replicas[0], router.replicas[1]
        g0 = METRICS.get(SERVE_LEASE_GRANTS, labels={"host": r0.name})
        g1 = METRICS.get(SERVE_LEASE_GRANTS, labels={"host": r1.name})
        r0.loop.connect("hl0")
        r1.loop.connect("hl1")
        r1.loop.connect("hl2")
        assert METRICS.get(SERVE_LEASE_GRANTS,
                           labels={"host": r0.name}) == g0 + 1
        assert METRICS.get(SERVE_LEASE_GRANTS,
                           labels={"host": r1.name}) == g1 + 2


def test_submit_after_silent_death_is_typed_resume(tmp_path):
    clk = VirtualClock()
    with simclock.use(clk):
        router, _loader, sections = _fleet_world(tmp_path)
        host, lease = router.connect("z0")
        router.kill(host)
        # the handoff re-granted the lease on a survivor, but THIS
        # client still holds the dead host's lease: typed resume
        replica = router.replica_of("z0")
        if replica is not None and replica.name != host:
            # migrated: the old lease object no longer matches the
            # survivor's grant — the submit is guarded by the loop
            host2, lease2 = router.connect("z0", resume=True)
            assert host2 != host
            ticket = router.submit("z0", lease2, sections)
            clk.advance(0.02)
            router.step_all()
            assert ticket.done and ticket.error is None
        else:
            with pytest.raises(HostDead):
                router.submit("z0", lease, sections)
        assert router.books()[0] == router.books()[1]


def test_failover_ledger_survives_racing_verdict_pop(tmp_path):
    """The PR-18 ledger-lock regression gate: ``_note_regrant`` and
    ``note_failover_verdict`` both touch the ``_failover`` ledger and
    race each other (handoff thread vs the client's verdict path) —
    both now mutate under ``_lock``, so a verdict pop that lands
    first makes the late regrant a clean no-op instead of stamping an
    orphaned dict. Fully simclock-driven, no sleeps."""
    clk = VirtualClock()
    with simclock.use(clk):
        router, _loader, _sections = _fleet_world(tmp_path)
        # normal order: death -> regrant -> verdict, exact latencies
        with router._lock:
            router._failover["s0"] = {"death": simclock.now()}
        clk.advance(2.0)
        router._note_regrant("s0")
        with router._lock:
            assert router._failover["s0"]["regrant"] == \
                pytest.approx(simclock.now())
        clk.advance(1.5)
        # a second regrant keeps the FIRST stamp (idempotent)
        router._note_regrant("s0")
        with router._lock:
            assert router._failover["s0"]["regrant"] == \
                pytest.approx(simclock.now() - 1.5)
        clk.advance(1.5)
        router.note_failover_verdict("s0")
        assert router.failover_samples[-1] == pytest.approx(5.0)
        with router._lock:
            assert "s0" not in router._failover
        # adversarial order: the verdict pop wins the race — the late
        # regrant must neither resurrect the entry nor record a sample
        with router._lock:
            router._failover["s1"] = {"death": simclock.now()}
        samples_before = len(router.failover_samples)
        router.note_failover_verdict("s1")
        router._note_regrant("s1")
        with router._lock:
            assert "s1" not in router._failover
        assert len(router.failover_samples) == samples_before + 1
