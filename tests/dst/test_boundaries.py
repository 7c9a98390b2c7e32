"""Timing boundary cases the schedule searcher is blind to without
explicit pins (ISSUE 10 satellite): deadline lapse at the exact tick,
quarantine TTL expiry racing a regeneration, breaker half-open under
concurrent probes, and a credit grant landing during reconnect. All
under virtual time — the boundaries are EXACT, not sleep-approximate.

ISSUE 11 adds the slot-lease boundaries of the continuously-batched
serving loop (runtime/serveloop.py): lease expiry racing a drain,
lease grant during reconnect-with-resume never double-counted, and
ring-full admission shedding with an explicit reason.
"""

import threading

import pytest

from cilium_tpu.runtime import simclock
from cilium_tpu.runtime.simclock import VirtualClock


def _serve_world(tmp_path, capacity=2, ttl=10.0):
    """A tiny real serving slice: compiled policy → ServeLoop, driven
    inline (no thread) so every boundary is an exact virtual tick."""
    from cilium_tpu.core.config import Config
    from cilium_tpu.ingest import synth
    from cilium_tpu.ingest.binary import (
        capture_from_bytes,
        capture_to_bytes,
    )
    from cilium_tpu.runtime.loader import Loader
    from cilium_tpu.runtime.serveloop import ServeLoop

    scenario = synth.scenario_by_name("http", 12, 64)
    per_identity, scenario = synth.realize_scenario(scenario)
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    loader.regenerate(per_identity, revision=1)
    sections = capture_from_bytes(
        capture_to_bytes(scenario.flows[:16]))
    loop = ServeLoop(loader, capacity=capacity, lease_ttl_s=ttl,
                     pack_interval_s=0.01)
    return loop, sections


# ---------------------------------------------------------------------------
# 1) deadline lapse at the exact tick


def test_admission_deadline_at_the_exact_tick_sheds():
    """A request whose deadline equals now() EXACTLY has zero budget:
    the gate sheds it (reason deadline) — `remaining <= 0` — and one
    virtual tick earlier it admits. The boundary is pinned closed."""
    from cilium_tpu.runtime.admission import (
        AdmissionGate,
        SHED_DEADLINE,
    )

    clk = VirtualClock(start=50.0)
    with simclock.use(clk):
        gate = AdmissionGate(max_pending=8, depth_fn=lambda: 0)
        ok, reason = gate.admit(deadline=clk.now())       # exact tick
        assert (ok, reason) == (False, SHED_DEADLINE)
        ok, _ = gate.admit(deadline=clk.now() + 1e-6)     # one tick in
        assert ok


def test_microbatcher_reaps_an_entry_expiring_at_the_exact_tick():
    """An entry whose deadline == now at dispatch is reaped (deadline
    <= now), never spent a batch slot on; one whose deadline is one
    tick later dispatches."""
    from cilium_tpu.core.flow import Flow, Verdict
    from cilium_tpu.runtime.service import MicroBatcher, _Pending

    clk = VirtualClock(start=10.0)
    with simclock.use(clk):
        served = []
        mb = MicroBatcher(lambda flows: served.append(len(flows))
                          or [int(Verdict.FORWARDED)] * len(flows),
                          batch_max=4, deadline_ms=1.0)
        exact = _Pending(Flow(), clk.now(), None)          # lapses NOW
        live = _Pending(Flow(), clk.now() + 1e-6, None)
        out = mb._reap([exact, live])
        assert out == [live]
        assert exact.box == [int(Verdict.ERROR)]
        assert exact.ev.is_set()
        mb.close()


# ---------------------------------------------------------------------------
# 2) quarantine TTL expiry racing a regeneration


def test_quarantine_ttl_expiry_races_regeneration():
    """A regeneration that starts at EXACTLY the quarantine TTL tick
    retries the bank (now >= until); one tick earlier it must keep
    serving the stale cover without a retry compile. Either way the
    pattern set served is consistent — the boundary changes WHEN the
    retry happens, never correctness."""
    from cilium_tpu.core.config import EngineConfig
    from cilium_tpu.policy.compiler.bankplan import BankRegistry
    from cilium_tpu.runtime import faults
    from cilium_tpu.runtime.faults import FaultPlan, FaultRule

    clk = VirtualClock()
    with simclock.use(clk):
        reg = BankRegistry(quarantine_ttl_s=30.0)
        cfg = EngineConfig(bank_size=2)
        pats = ["/a/.*", "/b/.*", "/c/.*", "/d/.*"]
        reg.compile_field("path", pats, cfg)        # healthy baseline
        with faults.inject(FaultPlan(
                [FaultRule("loader.bank_compile", times=1)])):
            _, stats = reg.compile_field("path", pats + ["/e/.*"],
                                         cfg)
        assert stats.quarantined, "fault must quarantine a group"
        quarantined = set(stats.quarantined)
        compiles_q = reg.compiles

        # one tick BEFORE expiry: stale cover keeps serving, no retry
        clk.advance(30.0 - 1e-3)
        assert reg.expired_quarantines() == ()
        _, stats2 = reg.compile_field("path", pats + ["/e/.*"], cfg)
        assert set(stats2.quarantined) == quarantined
        assert reg.compiles == compiles_q   # no retry compile yet

        # AT the expiry tick: the next regeneration retries + recovers
        clk.advance(1e-3)
        assert set(reg.expired_quarantines()) == quarantined
        _, stats3 = reg.compile_field("path", pats + ["/e/.*"], cfg)
        assert not stats3.quarantined
        assert reg.compiles > compiles_q    # the retry compiled


# ---------------------------------------------------------------------------
# 3) breaker half-open with concurrent probes


def test_breaker_half_open_admits_exactly_one_concurrent_probe():
    """N threads hit allow_primary at the exact probe-interval tick:
    EXACTLY one becomes the half-open probe; the rest keep falling
    back (a thundering herd onto a sick device would defeat the
    probe). A failed probe re-arms the timer at the failure instant."""
    from cilium_tpu.runtime.service import CircuitBreaker

    clk = VirtualClock()
    with simclock.use(clk):
        br = CircuitBreaker(failure_threshold=1, probe_interval=5.0)
        br.record_failure()
        assert br.state == CircuitBreaker.OPEN
        clk.advance(5.0)                     # exactly the interval
        results = []
        lock = threading.Lock()
        start = threading.Barrier(8)

        def prober():
            start.wait()
            got = br.allow_primary()
            with lock:
                results.append(got)

        ts = [threading.Thread(target=prober) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=5.0)
        assert results.count(True) == 1, results
        assert br.state == CircuitBreaker.HALF_OPEN
        # failed probe: OPEN again, timer re-armed from NOW — one tick
        # shy of the new interval stays closed to probes
        br.record_failure()                  # re-armed at now=5.0
        clk.advance_to(10.0 - 1e-6)
        assert not br.allow_primary()
        clk.advance_to(10.0)                 # exactly interval later
        assert br.allow_primary()


# ---------------------------------------------------------------------------
# 4) credit grant arriving during reconnect


def test_credit_grant_arriving_during_reconnect_is_not_lost():
    """The client's credit window is rebuilt from the re-handshake
    minus re-sent unacked chunks; a grant that lands immediately after
    (the server answering a resumed chunk) must ADD to that window —
    the reconnect must never double-count or drop it. Pure client-side
    state-machine check, driven through the same lock/condition the
    recv loop uses."""
    from cilium_tpu.runtime.stream import StreamClient

    clk = VirtualClock()
    with simclock.use(clk):
        client = StreamClient.__new__(StreamClient)   # no socket I/O
        client._lock = threading.Lock()
        client._cond = threading.Condition(client._lock)
        client.timeout = 5.0
        client._done = False
        client._credit_window = 4
        client._credits = 0                 # exhausted pre-drop
        client._unacked = {7: ("", b"img7"), 8: ("", b"img8")}
        # reconnect path: fresh window minus the 2 re-sent chunks
        with client._cond:
            client._credits = max(
                0, client._credit_window - len(client._unacked))
        assert client._credits == 2
        # the resumed session answers seq 7 AND grants a credit — the
        # recv-loop bookkeeping for a grant frame during resume:
        with client._cond:
            client._credits += 1
            client._cond.notify_all()
        with client._cond:
            client._unacked.pop(7)
        assert client._credits == 3
        # a sender blocked at zero credit wakes on the grant: window
        # accounting and the wait predicate agree
        client._acquire_credit()
        assert client._credits == 2


def test_lease_expiry_racing_a_drain_loses_no_verdict(tmp_path):
    """A lease that expires at EXACTLY the drain tick: drain packs
    pending chunks BEFORE releasing leases, so the chunk still gets a
    real verdict; the slot is released exactly once (as drained, not
    double-counted as expired), and the books stay exact."""
    clk = VirtualClock()
    with simclock.use(clk):
        loop, sections = _serve_world(tmp_path, ttl=10.0)
        lease = loop.connect("s0")
        ticket = loop.submit(lease, *sections)
        # advance to EXACTLY the lease expiry tick, then drain
        # without an intervening pack cycle — the race, pinned
        clk.advance_to(lease.expires_at)
        flushed = loop.drain()
        assert flushed == ticket.n
        assert ticket.done and ticket.error is None
        assert len(ticket.verdicts) == ticket.n
        st = loop.status()
        # released once, as a drain release — never ALSO expired
        assert (st["grants"], st["expiries"], st["releases"]) \
            == (1, 0, 1)
        assert st["occupancy"] == 0


def test_lease_expires_at_the_exact_tick_between_packs(tmp_path):
    """One tick short of the TTL the lease survives a pack cycle; AT
    the tick it expires: the slot returns, pending work resolves as
    an explicit lease-expired error (never silently lost), and a
    submit on the dead lease raises LeaseExpired."""
    from cilium_tpu.runtime.serveloop import LeaseExpired

    clk = VirtualClock()
    with simclock.use(clk):
        loop, sections = _serve_world(tmp_path, ttl=10.0)
        lease = loop.connect("s0")
        clk.advance_to(lease.expires_at - 1e-6)
        loop.step()
        assert lease.active and loop.status()["occupancy"] == 1
        ticket = loop.submit(lease, *sections)   # renews the lease
        assert lease.expires_at == clk.now() + 10.0
        clk.advance_to(lease.expires_at)         # idle to the tick
        # enqueue pending work JUST as the TTL lapses: the expiry
        # sweep must resolve it explicitly
        loop.step()
        assert not lease.active
        assert loop.status()["expiries"] == 1
        with pytest.raises(LeaseExpired):
            loop.submit(lease, *sections)
        # the renewed-then-packed first chunk was served normally
        assert ticket.done


def test_reconnect_with_resume_never_double_counts_a_grant(tmp_path):
    """Reconnect-with-resume against a LIVE lease renews and returns
    the SAME lease with no second grant; against a lease expired at
    exactly the reconnect tick it re-grants — once. The grant counter
    counts streams, not dial attempts."""
    clk = VirtualClock()
    with simclock.use(clk):
        loop, sections = _serve_world(tmp_path, ttl=10.0)
        lease = loop.connect("s0")
        assert loop.grants == 1
        # storm of re-dials against the live lease: same object, no
        # new grants, expiry deadline renewed each time
        clk.advance(5.0)
        for _ in range(4):
            again = loop.connect("s0", resume=True)
            assert again is lease
        assert loop.grants == 1
        assert lease.expires_at == clk.now() + 10.0
        # ONE tick before expiry: still a resume, still no grant
        clk.advance_to(lease.expires_at - 1e-6)
        assert loop.connect("s0", resume=True) is lease
        assert loop.grants == 1
        # AT the expiry tick: the lease is dead — resume re-grants a
        # fresh lease (counted once); books stay exact
        clk.advance_to(lease.expires_at)
        fresh = loop.connect("s0", resume=True)
        assert fresh is not lease
        assert loop.grants == 2
        st = loop.status()
        assert st["grants"] - st["expiries"] - st["releases"] \
            == st["occupancy"] == 1


def test_ring_full_sheds_with_explicit_reason(tmp_path):
    """A stream past the ring's slot capacity sheds with reason
    ``ring-full`` — explicit, counted on the admission series, and
    retryable: a released slot admits the next connect."""
    from cilium_tpu.runtime.admission import SHED_RING_FULL
    from cilium_tpu.runtime.metrics import ADMISSION_SHED, METRICS
    from cilium_tpu.runtime.serveloop import ShedError

    clk = VirtualClock()
    with simclock.use(clk):
        loop, sections = _serve_world(tmp_path, capacity=2)
        a = loop.connect("s0")
        loop.connect("s1")
        shed_before = METRICS.get(ADMISSION_SHED, labels={
            "surface": "serve", "class": "data",
            "reason": SHED_RING_FULL})
        with pytest.raises(ShedError) as exc:
            loop.connect("s2")
        assert exc.value.reason == SHED_RING_FULL
        assert METRICS.get(ADMISSION_SHED, labels={
            "surface": "serve", "class": "data",
            "reason": SHED_RING_FULL}) == shed_before + 1
        # retryable: a freed slot admits the shed stream
        loop.disconnect(a)
        lease = loop.connect("s2")
        assert lease.active


def test_acquire_credit_times_out_on_virtual_clock_without_grant():
    """A wedged consumer surfaces as TimeoutError after the VIRTUAL
    timeout — no real seconds slept."""
    from cilium_tpu.runtime.stream import StreamClient

    clk = VirtualClock()
    with simclock.use(clk):
        client = StreamClient.__new__(StreamClient)
        client._lock = threading.Lock()
        client._cond = threading.Condition(client._lock)
        client.timeout = 30.0               # 30 VIRTUAL seconds
        client._done = False
        client._credits = 0
        client._credit_window = 4
        boom = []

        def sender():
            try:
                client._acquire_credit()
            except TimeoutError:
                boom.append(True)

        t = threading.Thread(target=sender)
        t.start()
        while not clk._by_seq:
            threading.Event().wait(0.002)
        clk.advance(30.1)
        t.join(timeout=5.0)
        assert boom == [True]


# ---------------------------------------------------------------------------
# ISSUE 13: fleet compile-plane boundaries — per-bank deadline lapse
# at the exact virtual tick, worker-death retry exhaustion reaching
# quarantine-with-cover, and drain-while-compiling.


def _queued_registry(workers=1, deadline_s=10.0, max_retries=1,
                     backoff_base_s=0.5):
    from cilium_tpu.core.config import EngineConfig
    from cilium_tpu.policy.compiler.bankplan import BankRegistry
    from cilium_tpu.policy.compiler.compilequeue import CompileQueue

    cfg = EngineConfig()
    cfg.bank_size = 4
    q = CompileQueue(workers=workers, deadline_s=deadline_s,
                     max_retries=max_retries,
                     backoff_base_s=backoff_base_s)
    return BankRegistry(queue=q), cfg


def test_compile_deadline_lapses_at_the_exact_tick_serves_cover(
        tmp_path):
    """A bank compile still in flight at EXACTLY its deadline tick
    stops blocking the build: the bank is PENDING (cover for covered
    patterns, fail-closed dead bank for the rest — never an abort),
    and the late completion lands in the registry so the NEXT build
    reuses it with zero compiles."""
    import time as _time

    clk = VirtualClock(start=100.0)
    with simclock.use(clk):
        reg, cfg = _queued_registry(deadline_s=10.0)
        try:
            gate = threading.Event()
            orig = reg._compile_group

            def slow(group, opts):
                gate.wait(5.0)       # REAL stall: worker busy
                return orig(group, opts)

            reg._compile_group = slow
            pats = ["/d1/.*", "/d2/.*"]
            out = {}

            def build():
                out["res"] = reg.compile_field("path", pats, cfg)

            th = threading.Thread(target=build)
            th.start()
            # the waiter must park on the virtual heap first
            for _ in range(400):
                if clk._heap:
                    break
                _time.sleep(0.005)
            clk.advance_to(110.0)            # the EXACT deadline tick
            th.join(timeout=10.0)
            banked, stats = out["res"]
            assert stats.pending, "exact-tick lapse must mark pending"
            assert stats.quarantined == stats.pending
            assert reg.pending_serves == 1
            # no prior cover: patterns fail CLOSED via a dead bank
            assert len(banked.patterns) == len(pats)
            gate.set()
            for _ in range(400):
                if not reg._pending_keys:
                    break
                _time.sleep(0.005)
            assert not reg._pending_keys, "late result did not land"
            _, s2 = reg.compile_field("path", pats, cfg)
            assert not s2.quarantined and s2.rebuilt == ()
            assert s2.reused == len(s2.bank_keys)
        finally:
            reg.close()


def test_worker_death_backoff_gates_on_the_exact_virtual_tick():
    """The in-queue retry's backoff gate is virtual: one tick before
    ``not_before`` the retry does not run; AT the tick it does. (The
    gate also carries a REAL-time release valve so a blocked DST
    driver can't deadlock on it — the base here is large enough that
    only the virtual release is in play within this test's window.)"""
    import time as _time

    from cilium_tpu.runtime import faults

    clk = VirtualClock(start=0.0)
    with simclock.use(clk):
        from cilium_tpu.policy.compiler.compilequeue import CompileQueue

        q = CompileQueue(workers=1, backoff_base_s=5.0, max_retries=3)
        try:
            with faults.inject(faults.FaultPlan(
                    [faults.FaultRule("compile.worker", times=1)])):
                t = q.submit("k", lambda: "ok")
                # death happens promptly (real time); the retry then
                # parks until now + backoff on the VIRTUAL clock
                for _ in range(400):
                    if q.worker_deaths == 1:
                        break
                    _time.sleep(0.005)
                assert q.worker_deaths == 1
                nb = t.not_before
                assert nb > clk.now()
                clk.advance_to(nb - 0.001)
                _time.sleep(0.1)
                assert not t.done, "retry ran BEFORE its backoff gate"
                clk.advance_to(nb)           # the exact tick
                assert q.wait(t, timeout=30.0)
                assert t.result == "ok"
        finally:
            q.close()


def test_worker_death_exhaustion_reaches_quarantine_with_cover():
    """Retry exhaustion under virtual time: every retry consumed by a
    death leaves the bank quarantined; previously-compiled patterns
    ride their cover, new ones fail closed — the fail-closed pin of
    the ISSUE-13 acceptance."""
    import time as _time

    from cilium_tpu.core.flow import Verdict  # noqa: F401 — doc anchor
    from cilium_tpu.runtime import faults

    clk = VirtualClock(start=0.0)
    with simclock.use(clk):
        # a LONG deadline so the pending-lapse path cannot preempt the
        # exhaustion path; the retries release through the gate's
        # real-time valve (exactly how a blocked DST driver survives)
        reg, cfg = _queued_registry(max_retries=1, backoff_base_s=0.1,
                                    deadline_s=1000.0)
        try:
            pats = [f"/w{i}/.*" for i in range(4)]
            _, s0 = reg.compile_field("path", pats, cfg)
            assert not s0.quarantined
            grown = pats + ["/w-new/.*"]
            with faults.inject(faults.FaultPlan(
                    [faults.FaultRule("compile.worker", times=10)])):
                out = {}

                def build():
                    out["res"] = reg.compile_field("path", grown, cfg)

                th = threading.Thread(target=build)
                th.start()
                th.join(timeout=30.0)
            assert "res" in out, "build wedged on the backoff gate"
            banked, s1 = out["res"]
            assert s1.quarantined, "exhaustion must quarantine"
            assert not s1.pending, "exhaustion, not a deadline lapse"
            assert reg._quarantine
            # the changed bank's patterns: covered ones ride the old
            # cover, the new one binds to a lane (dead bank or cover)
            assert len(banked.patterns) == len(grown)
        finally:
            reg.close()


# ---------------------------------------------------------------------------
# ISSUE 16: serving-fleet handoff boundaries — a lease lapsing at the
# EXACT tick its host is declared dead, a chunk submitted to a host
# that died between admit and submit, and a rejoin racing the handoff
# of the rejoining host's own old leases.


def _fleet_world(tmp_path, hosts=3, capacity=8, ttl=10.0):
    from cilium_tpu.core.config import Config
    from cilium_tpu.ingest import synth
    from cilium_tpu.ingest.binary import (
        capture_from_bytes,
        capture_to_bytes,
    )
    from cilium_tpu.runtime.fleetserve import FleetRouter, HostReplica
    from cilium_tpu.runtime.loader import Loader

    scenario = synth.scenario_by_name("http", 12, 64)
    per_identity, scenario = synth.realize_scenario(scenario)
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    loader.regenerate(per_identity, revision=1)
    sections = capture_from_bytes(
        capture_to_bytes(scenario.flows[:16]))
    cfg.serve.pack_interval_ms = 10.0
    replicas = [HostReplica(i, loader, capacity=capacity,
                            lease_ttl_s=ttl)
                for i in range(hosts)]
    router = FleetRouter(replicas, heartbeat_interval_s=1.0,
                         suspicion_ttl_s=3.0, spill_headroom=0.0)
    return router, loader, sections


def test_lease_expiring_at_the_exact_death_tick_never_double_counts(
        tmp_path):
    """A lease whose TTL lapses at EXACTLY the tick its host is
    declared dead: the abandonment releases the slot exactly once
    (as a close — never ALSO swept as an expiry), the handoff
    re-grant on a survivor counts exactly one new grant, and the
    fleet books stay exact through the coincidence."""
    clk = VirtualClock()
    with simclock.use(clk):
        router, loader, sections = _fleet_world(tmp_path, ttl=10.0)
        host, lease = router.connect("race-0")
        dead = next(r for r in router.replicas if r.name == host)
        # advance to EXACTLY the lease expiry tick, then declare the
        # host dead without an intervening pack — the race, pinned
        clk.advance_to(lease.expires_at)
        assert lease.expired
        router.kill(host)
        st = dead.loop.status()
        assert (st["grants"], st["expiries"], st["releases"]) \
            == (1, 0, 1), "abandon must release ONCE, never also expire"
        assert st["occupancy"] == 0
        # the handoff re-granted on a survivor — exactly one grant,
        # never one on each side of the death
        assert router.conservation_violation() is None
        bal, occ = router.books()
        assert bal == occ == 1
        placed = router.placements.get("race-0")
        assert placed is not None and placed != host


def test_submit_to_host_dead_between_admit_and_submit_resumes(
        tmp_path):
    """Admit lands, the host dies, THEN the chunk arrives: the submit
    raises the TYPED HostDead (the client's resume signal, never a
    stream-fatal error), and the reconnect-with-resume replay serves
    the chunk on a survivor with the books exact."""
    from cilium_tpu.runtime.fleetserve import HostDead

    clk = VirtualClock()
    with simclock.use(clk):
        router, loader, sections = _fleet_world(tmp_path)
        host, lease = router.connect("gap-0")
        # the death slips into the admit→submit gap; the handoff is
        # fully interrupted so the stream is left UNPLACED (the
        # client-resume face of the race, not the migrated face)
        from cilium_tpu.runtime import faults as _faults

        with _faults.inject(_faults.FaultPlan(
                [_faults.FaultRule("fleet.handoff", times=1)])):
            router.kill(host)
        with pytest.raises(HostDead):
            router.submit("gap-0", lease, sections)
        # the typed error drives the replay: resume, re-submit, serve
        host2, lease2 = router.connect("gap-0", resume=True)
        assert host2 != host
        ticket = router.submit("gap-0", lease2, sections)
        router.step_all()
        assert ticket.done and ticket.error is None
        assert len(ticket.verdicts) == ticket.n
        assert router.conservation_violation() is None
        bal, occ = router.books()
        assert bal == occ == 1


def test_rejoin_racing_the_handoff_of_its_own_old_leases(tmp_path):
    """The rejoining host comes back while its OWN old leases are
    still mid-migration (the handoff was interrupted after one
    re-grant): already-migrated streams stay pinned to their
    survivor, unmigrated ones may resume onto the rejoined host's
    FRESH ring — and at no point does any stream hold leases on two
    live hosts, including the rejoined incarnation vs its survivors."""
    from cilium_tpu.runtime import faults as _faults

    clk = VirtualClock()
    with simclock.use(clk):
        router, loader, sections = _fleet_world(tmp_path)
        streams = [f"r{k}" for k in range(8)]
        for s in streams:
            router.connect(s)
        counts = {}
        for s in streams:
            h = router.placements[s]
            counts[h] = counts.get(h, 0) + 1
        victim = max(counts, key=lambda h: counts[h])
        assert counts[victim] >= 2
        # interrupt AFTER one re-grant: one stream migrated, the rest
        # of the victim's streams left unplaced
        with _faults.inject(_faults.FaultPlan(
                [_faults.FaultRule("fleet.handoff", times=1,
                                   after=1)])):
            router.kill(victim)
        assert router.partial_handoffs == 1
        assert router.handoffs == 1
        # the rejoin races the unfinished migration
        router.rejoin(victim)
        rejoined = next(r for r in router.replicas
                        if r.name == victim)
        assert rejoined.alive and not rejoined.loop.lease_ids(), \
            "the rejoined incarnation must start with a FRESH ring"
        # every stream resumes: pinned ones stay put, unplaced ones
        # may land on the rejoined host — exactly one live lease each
        pinned_before = {s: router.placements[s] for s in streams
                         if s in router.placements}
        for s in streams:
            router.connect(s, resume=True)
        for s, h in pinned_before.items():
            assert router.placements[s] == h, \
                "a pinned stream moved during the rejoin race"
        assert router.conservation_violation() is None
        bal, occ = router.books()
        assert bal == occ == len(streams)
    """Drain racing an in-flight bank compile: the compile finishes,
    its result lands in the registry (and the artifact store), and
    the drained queue refuses new work instead of buffering it."""
    import time as _time

    import pytest as _pytest

    from cilium_tpu.policy.compiler.compilequeue import QueueDraining
    from cilium_tpu.runtime.checkpoint import (
        ArtifactCache,
        BankArtifactStore,
    )

    clk = VirtualClock(start=0.0)
    with simclock.use(clk):
        from cilium_tpu.core.config import EngineConfig
        from cilium_tpu.policy.compiler.bankplan import BankRegistry
        from cilium_tpu.policy.compiler.compilequeue import CompileQueue

        cfg = EngineConfig()
        cfg.bank_size = 4
        q = CompileQueue(workers=1, deadline_s=30.0)
        store = BankArtifactStore(ArtifactCache(str(tmp_path)))
        reg = BankRegistry(queue=q, artifacts=store)
        gate = threading.Event()
        orig = reg._compile_group

        def slow(group, opts):
            gate.wait(5.0)
            return orig(group, opts)

        reg._compile_group = slow
        pats = ["/dr1/.*"]
        out = {}
        th = threading.Thread(
            target=lambda: out.update(
                res=reg.compile_field("path", pats, cfg)))
        th.start()
        _time.sleep(0.05)                    # compile is in flight
        drained = {}
        dth = threading.Thread(
            target=lambda: drained.update(ok=q.drain(timeout=60.0)))
        dth.start()
        _time.sleep(0.05)
        gate.set()                           # the compile completes
        dth.join(timeout=10.0)
        th.join(timeout=10.0)
        assert drained["ok"] is True
        _, s = out["res"]
        assert s.rebuilt and not s.quarantined, \
            "drain abandoned an in-flight compile"
        assert reg._group_count() == len(s.bank_keys)
        with _pytest.raises(QueueDraining):
            q.submit("post-drain", lambda: None)
        # ...and the artifact was published before the drain finished
        assert store.fetch(s.rebuilt[0]) is not None
        reg.close()


# ---------------------------------------------------------------------------
# ISSUE 17: fleet observability boundaries — a traced stream whose
# host is declared dead at EXACTLY its lease-expiry tick still
# stitches to ONE trace id with a bumped causal epoch, and the fleet
# event journal folds to the router's books through the coincidence.


def test_trace_stitches_when_death_lands_on_the_exact_expiry_tick(
        tmp_path):
    """Host death at EXACTLY the traced lease's expiry tick: the
    abandoned chunk resolves as lease-closed (not silently expired),
    the replay adopts the SAME trace id at a bumped causal epoch, and
    the stitched timeline orders epoch 0 strictly before epoch 1 with
    both hosts attributed — the kill → abandon → re-grant → replay
    chain is one trace even when the TTL and the death coincide."""
    from cilium_tpu.runtime.tracing import TRACER

    clk = VirtualClock()
    with simclock.use(clk):
        router, loader, sections = _fleet_world(tmp_path, ttl=10.0)
        prev_enabled, prev_rate = TRACER.enabled, TRACER.sample_rate
        TRACER.configure(enabled=True, sample_rate=1.0)
        try:
            host, lease = router.connect("tb-0")
            with TRACER.trace("stream.chunk", stream="tb-0") as ctx:
                ticket = router.submit("tb-0", lease, sections)
            tid = ctx.trace_id
            assert ticket.trace_id == tid and ticket.epoch == 0
            # advance to EXACTLY the expiry tick, then declare the
            # host dead with no intervening pack — the race, pinned
            clk.advance_to(lease.expires_at)
            assert lease.expired
            router.kill(host)
            assert ticket.done and ticket.error == "lease-closed"
            # the replay adopts the SAME id at a bumped epoch
            host2, lease2 = router.connect("tb-0", resume=True)
            assert host2 != host
            t2 = router.submit("tb-0", lease2, sections)
            assert t2.trace_id == tid
            assert t2.epoch > ticket.epoch
            router.step_all()
            assert t2.done and t2.error is None
            stitched = router.trace(tid)
            assert stitched["stitched"] is True
            assert stitched["epochs"] == [0, 1]
            assert host in stitched["hosts"]
            assert host2 in stitched["hosts"]
            names = [r["name"] for r in stitched["records"]]
            assert "fleet.handoff" in names
            # epoch ordering is strict even though the wall stamps of
            # both sides share the exact same virtual tick
            epochs = [r.get("epoch", 0) for r in stitched["records"]]
            assert epochs == sorted(epochs)
            # the journal folds to the router's books through the
            # expiry/death coincidence
            assert router.journal_consistent() is None
            assert router.conservation_violation() is None
        finally:
            TRACER.configure(enabled=prev_enabled,
                             sample_rate=prev_rate)


# ---------------------------------------------------------------------------
# ISSUE 20: tenant-fairness and canary boundaries — quantum rotation
# at the exact virtual tick, counter-walk sample selection identical
# under every PYTHONHASHSEED, and a quota lapse landing on the exact
# tick of a regeneration's admission decisions.


def test_fairness_quantum_rotates_at_the_exact_virtual_tick():
    """A tenant shed for hogging the window is forgiven at EXACTLY
    start+quantum — the rotation boundary is closed (now >= start +
    quantum). One tick before the quantum the storming tenant still
    sheds; AT the tick the window is fresh and the same tenant
    admits. The rotation also lands on the quantum grid, never on
    'whenever the next request happened to arrive'."""
    from cilium_tpu.runtime.admission import (
        CLASS_DATA,
        SHED_TENANT_QUOTA,
        AdmissionGate,
    )
    from cilium_tpu.runtime.tenant import FairShareWindow

    clk = VirtualClock(start=100.0)
    with simclock.use(clk):
        fair = FairShareWindow(quantum_s=5.0, max_share=0.3)
        gate = AdmissionGate(max_pending=8, control_reserve=2,
                             depth_fn=lambda: 6, fairness=fair)
        assert gate.admit(CLASS_DATA, tenant="b") == (True, "")
        # a storms until the window judges it over cap AND fair share
        shed = False
        for _ in range(12):
            ok, reason = gate.admit(CLASS_DATA, tenant="a")
            if not ok:
                assert reason == SHED_TENANT_QUOTA
                shed = True
                break
        assert shed, "storming tenant must shed within the window"
        # one tick BEFORE the quantum boundary: still the same window,
        # the storm is still on the books, a still sheds
        clk.advance_to(100.0 + 5.0 - 1e-6)
        ok, reason = gate.admit(CLASS_DATA, tenant="a")
        assert (ok, reason) == (False, SHED_TENANT_QUOTA)
        # AT exactly start+quantum: fresh window, a is forgiven
        clk.advance_to(105.0)
        assert gate.admit(CLASS_DATA, tenant="a") == (True, "")
        assert fair.window_start() == 105.0   # grid, not arrival time
        # an idle gap of 2.5 quanta later: the window start is still
        # on the grid (105 + 2*5), not at the arrival tick
        clk.advance_to(105.0 + 12.5)
        fair.note("b")
        assert fair.window_start() == 115.0


def test_canary_sample_selection_identical_under_hashseeds():
    """Sample selection is a pure counter walk — floor(c*f) !=
    floor((c-1)*f) — so the SAME chunks are sampled on every host and
    under every PYTHONHASHSEED. Three fresh interpreters with seeds
    0/1/2 must pick byte-identical counter sets of exactly
    floor(n*f) chunks."""
    import os
    import subprocess
    import sys

    prog = (
        "from cilium_tpu.runtime.loader import Loader\n"
        "from cilium_tpu.core.config import Config\n"
        "from cilium_tpu.runtime.canary import CanaryController\n"
        "class _L:\n"
        "    pass\n"
        "c = CanaryController(_L(), sample_fraction=0.37)\n"
        "picked = [i for i in range(1, 201) if c.should_sample(i)]\n"
        "print(len(picked), ','.join(map(str, picked)))\n"
    )
    outs = []
    for seed in ("0", "1", "2"):
        env = dict(os.environ,
                   PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", prog], env=env,
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        outs.append(out.stdout.strip())
    assert outs[0] == outs[1] == outs[2]
    count = int(outs[0].split()[0])
    assert count == int(200 * 0.37)          # exactly floor(n*f)


def test_tenant_quota_lapse_races_a_regeneration():
    """Tenant a's quota TTL expires at EXACTLY the tick its own
    regeneration lands admission decisions. The boundary is closed
    (expires_at <= now): AT the tick the conservative default share
    applies — a's data-plane burst sheds tenant-quota — while the
    regeneration's CLASS_CONTROL traffic is exempt and sails through,
    so a quota lapse can never starve the control plane that would
    refresh it. One tick earlier the live quota still holds."""
    from cilium_tpu.runtime.admission import (
        CLASS_CONTROL,
        CLASS_DATA,
        SHED_TENANT_QUOTA,
        AdmissionGate,
    )
    from cilium_tpu.runtime.metrics import METRICS, TENANT_QUOTA_READS
    from cilium_tpu.runtime.tenant import (
        FairShareWindow,
        TenantQuotas,
    )

    clk = VirtualClock(start=0.0)
    with simclock.use(clk):
        quotas = TenantQuotas(default_share=0.2, ttl_s=10.0)
        quotas.set_share("a", 0.95)          # expires_at == 10.0
        fair = FairShareWindow(quantum_s=1000.0, max_share=0.2)
        gate = AdmissionGate(max_pending=8, control_reserve=2,
                             depth_fn=lambda: 6, fairness=fair,
                             quotas=quotas)
        fair.note("b")
        lapsed0 = METRICS.get(TENANT_QUOTA_READS,
                              {"result": "lapsed"})
        # one tick BEFORE expiry: the live 0.95 quota admits the burst
        clk.advance_to(10.0 - 1e-6)
        for _ in range(6):
            assert gate.admit(CLASS_DATA, tenant="a") == (True, "")
        # AT exactly expires_at — the regeneration tick: data sheds on
        # the conservative default, control admits
        clk.advance_to(10.0)
        ok, reason = gate.admit(CLASS_DATA, tenant="a")
        assert (ok, reason) == (False, SHED_TENANT_QUOTA)
        assert gate.admit(CLASS_CONTROL, tenant="a") == (True, "")
        assert METRICS.get(TENANT_QUOTA_READS,
                           {"result": "lapsed"}) == lapsed0 + 1
        # the quota store dropped the entry — a later refresh (the
        # regeneration's control plane got through) restores service
        quotas.set_share("a", 0.95)
        assert gate.admit(CLASS_DATA, tenant="a") == (True, "")
