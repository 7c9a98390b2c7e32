"""Continuously-batched serving loop (runtime/serveloop.py +
engine/ring.py): the ring's packed dispatch must be verdict-bit-equal
to the engine's direct path across interleaved streams, memo-hit rows
must provably skip H2D (the bytes-saved counter is arithmetic, not
vibes), leases/sheds/faults must be explicit and exact, and the ring
must ride policy hot-swaps through the PR-8 delta path — including
the ISSUE-11 narrowing to family (bank-reference) granularity."""

import numpy as np
import pytest

from cilium_tpu.core.config import Config
from cilium_tpu.ingest import synth
from cilium_tpu.ingest.binary import (
    capture_from_bytes,
    capture_to_bytes,
)
from cilium_tpu.runtime import faults, simclock
from cilium_tpu.runtime.loader import Loader
from cilium_tpu.runtime.serveloop import (
    ChunkTicket,
    LeaseExpired,
    ServeLoop,
    ShedError,
)
from cilium_tpu.runtime.simclock import VirtualClock


def _world(tmp_path, name="http", n_rules=60, capacity=64,
           ttl=60.0, serve_kw=None):
    scenario = synth.scenario_by_name(name, n_rules, 1024)
    per_identity, scenario = synth.realize_scenario(scenario)
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    loader.regenerate(per_identity, revision=1)
    loop = ServeLoop(loader, capacity=capacity, lease_ttl_s=ttl,
                     pack_interval_s=0.01, **(serve_kw or {}))
    return loop, loader, scenario


def _sections(flows):
    return capture_from_bytes(capture_to_bytes(flows))


def _direct(loader, flows):
    return [int(v) for v in
            loader.engine.verdict_flows(flows)["verdict"]]


# ---------------------------------------------------------------------------
# packed dispatch: many streams, one launch, bit-equal


@pytest.mark.parametrize("name", ["http", "kafka", "fqdn", "generic"])
def test_ring_pack_is_bit_equal_across_interleaved_streams(
        tmp_path, name):
    clk = VirtualClock()
    with simclock.use(clk):
        loop, loader, scenario = _world(tmp_path, name=name)
        flows = scenario.flows[:600]
        want = _direct(loader, flows)
        leases = [loop.connect(f"s{i}") for i in range(4)]
        tickets = []
        for k, i in enumerate(range(0, 600, 75)):
            chunk = flows[i:i + 75]
            tickets.append((i, loop.submit(leases[k % 4],
                                           *_sections(chunk))))
        packs_before = loop.ring.packs
        served = loop.step()
        # one fused pack served every stream's pending chunks
        assert loop.ring.packs == packs_before + 1
        assert served == 600
        got = [None] * 600
        for i, t in tickets:
            assert t.done and t.error is None
            got[i:i + t.n] = [int(v) for v in t.verdicts]
        assert got == want


def test_memo_hit_rows_provably_skip_h2d(tmp_path):
    """The selective-copy claim as arithmetic: a chunk whose rows are
    ALL ring-resident ships only 4-byte ids — the bytes-saved counter
    grows by exactly known_rows x (row_bytes - 4) and bytes shipped
    by exactly n x 4."""
    clk = VirtualClock()
    with simclock.use(clk):
        loop, loader, scenario = _world(tmp_path)
        flows = scenario.flows[:256]
        lease = loop.connect("s0")
        loop.submit(lease, *_sections(flows))
        loop.step()
        assert loop.ring.bytes_saved > 0   # dedup within the chunk
        row_bytes = loop.ring.session.row_width * 4
        saved0 = loop.ring.bytes_saved
        shipped0 = loop.ring.bytes_shipped
        hits0 = loop.ring.session.memo.hits
        # the SAME traffic again: zero novel rows, pure memo serve
        t = loop.submit(lease, *_sections(flows))
        loop.step()
        assert t.done and t.error is None
        assert loop.ring.bytes_saved - saved0 \
            == len(flows) * (row_bytes - 4)
        assert loop.ring.bytes_shipped - shipped0 == len(flows) * 4
        assert loop.ring.session.memo.hits > hits0


def test_per_slot_pending_bound_sheds_queue_full(tmp_path):
    from cilium_tpu.runtime.admission import SHED_QUEUE_FULL

    clk = VirtualClock()
    with simclock.use(clk):
        loop, loader, scenario = _world(
            tmp_path, serve_kw={"max_slot_pending": 2})
        lease = loop.connect("s0")
        sections = _sections(scenario.flows[:8])
        loop.submit(lease, *sections)
        loop.submit(lease, *sections)
        with pytest.raises(ShedError) as exc:
            loop.submit(lease, *sections)
        assert exc.value.reason == SHED_QUEUE_FULL
        # the pack drains the backlog; the slot accepts again
        loop.step()
        loop.submit(lease, *sections)


# ---------------------------------------------------------------------------
# fault points: explicit sheds, transient pack failure retries


def test_serve_fault_points_shed_explicitly(tmp_path):
    from cilium_tpu.runtime.admission import SHED_FAULT

    clk = VirtualClock()
    with simclock.use(clk):
        loop, loader, scenario = _world(tmp_path)
        sections = _sections(scenario.flows[:8])
        with faults.inject(faults.FaultPlan([
                faults.FaultRule("serve.lease", times=1)])):
            with pytest.raises(ShedError) as exc:
                loop.connect("s0")
            assert exc.value.reason == SHED_FAULT
            lease = loop.connect("s0")   # fault exhausted: admitted
        with faults.inject(faults.FaultPlan([
                faults.FaultRule("serve.ring_slot", times=1)])):
            with pytest.raises(ShedError) as exc:
                loop.submit(lease, *sections)
            assert exc.value.reason == SHED_FAULT
            t = loop.submit(lease, *sections)   # next chunk fine
        loop.step()
        assert t.done and t.error is None


def test_transient_dispatch_fault_retries_next_cycle(tmp_path):
    """An engine.dispatch fault fails ONE pack cycle: the batch goes
    back to the slots' heads and the next cycle serves it — the
    ticket resolves with real verdicts, nothing is lost."""
    clk = VirtualClock()
    with simclock.use(clk):
        loop, loader, scenario = _world(tmp_path)
        flows = scenario.flows[:64]
        want = _direct(loader, flows)
        lease = loop.connect("s0")
        t = loop.submit(lease, *_sections(flows))
        with faults.inject(faults.FaultPlan([
                faults.FaultRule("engine.dispatch", times=1)])):
            with pytest.raises(Exception):
                loop.step()              # the faulted cycle
            assert not t.done            # batch restored, not lost
            loop.step()                  # retry succeeds
        assert t.done and t.error is None
        assert [int(v) for v in t.verdicts] == want


# ---------------------------------------------------------------------------
# hot-swap safety + family-granular (bank-reference) invalidation


def _churn_world(tmp_path):
    """A policy whose per-identity HTTP vs DNS rule families can
    churn independently — the family-granularity fixture."""
    from cilium_tpu.core.flow import (
        DNSInfo,
        Flow,
        HTTPInfo,
        L7Type,
        Protocol,
        TrafficDirection,
    )
    from cilium_tpu.core.identity import IdentityAllocator
    from cilium_tpu.core.labels import LabelSet
    from cilium_tpu.policy.api import (
        EndpointSelector,
        IngressRule,
        PortProtocol,
        PortRule,
        Rule,
    )
    from cilium_tpu.policy.api.l7 import (
        L7Rules,
        PortRuleDNS,
        PortRuleHTTP,
    )
    from cilium_tpu.policy.mapstate import PolicyResolver
    from cilium_tpu.policy.repository import Repository
    from cilium_tpu.policy.selectorcache import SelectorCache

    alloc = IdentityAllocator()
    web = alloc.allocate(LabelSet.from_dict({"app": "web"}))
    dbs = [alloc.allocate(LabelSet.from_dict({"app": f"db{i}"}))
           for i in range(3)]
    rules_of = {i: [("http", f"/svc{i}/p{j}/.*") for j in range(4)]
                + [("dns", f"api{i}.corp.io")] for i in range(3)}

    def resolve():
        repo = Repository()
        rules = []
        for i in range(3):
            http = tuple(PortRuleHTTP(path=p, method="GET")
                         for k, p in rules_of[i] if k == "http")
            dns = tuple(PortRuleDNS(match_name=p)
                        for k, p in rules_of[i] if k == "dns")
            rules.append(Rule(
                endpoint_selector=EndpointSelector.from_labels(
                    app=f"db{i}"),
                ingress=(IngressRule(
                    from_endpoints=(
                        EndpointSelector.from_labels(app="web"),),
                    to_ports=(
                        PortRule(ports=(PortProtocol(80, Protocol.TCP),),
                                 rules=L7Rules(http=http)),
                        PortRule(ports=(PortProtocol(53, Protocol.UDP),),
                                 rules=L7Rules(dns=dns)),)),),
            ))
        repo.add(rules, sanitize=False)
        resolver = PolicyResolver(repo, SelectorCache(alloc))
        return {db: resolver.resolve(alloc.lookup(db)) for db in dbs}

    def http_flow(i, path):
        return Flow(src_identity=web, dst_identity=dbs[i], dport=80,
                    protocol=Protocol.TCP,
                    direction=TrafficDirection.INGRESS,
                    l7=L7Type.HTTP,
                    http=HTTPInfo(method="GET", path=path))

    def dns_flow(i, q):
        return Flow(src_identity=web, dst_identity=dbs[i], dport=53,
                    protocol=Protocol.UDP,
                    direction=TrafficDirection.INGRESS,
                    l7=L7Type.DNS, dns=DNSInfo(query=q))

    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.engine.bank_size = 2
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    loader.regenerate(resolve(), revision=1)
    return loader, rules_of, resolve, http_flow, dns_flow


def test_ring_survives_policy_hot_swap_with_family_granular_refill(
        tmp_path):
    """A commit that changes ONLY identity 0's HTTP rules refills
    only identity 0's HTTP memo rows — its DNS rows and every other
    identity's rows keep serving from the memo (the PR-8 "remaining
    headroom", closed). Refills are counted as misses; verdicts stay
    bit-equal to the new serving engine throughout."""
    clk = VirtualClock()
    with simclock.use(clk):
        loader, rules_of, resolve, http_flow, dns_flow = \
            _churn_world(tmp_path)
        loop = ServeLoop(loader, capacity=8, lease_ttl_s=60.0,
                         pack_interval_s=0.01)
        corpus = []
        for i in range(3):
            corpus += [http_flow(i, f"/svc{i}/p{j}/x")
                       for j in range(4)]
            corpus.append(dns_flow(i, f"api{i}.corp.io"))
            corpus.append(dns_flow(i, "evil.net"))
        lease = loop.connect("s0")
        t = loop.submit(lease, *_sections(corpus * 4))
        loop.step()
        assert [int(v) for v in t.verdicts] == \
            _direct(loader, corpus * 4)
        memo = loop.ring.session.memo
        misses0, inval0 = memo.misses, memo.invalidations
        n_unique = loop.ring.session.n_rows
        # the identity whose rules churn, and its per-family unique
        # row counts, straight from the session's (ep, l7t, dport)
        # mirror
        pairs = loop.ring.session._row_eps[:n_unique]
        id0 = min(ep for ep, _, _ in pairs)  # dbs[0]: lowest identity
        id0_http = sum(1 for ep, l7t, _ in pairs
                       if ep == id0 and l7t == 1)
        id0_all = sum(1 for ep, _, _ in pairs if ep == id0)
        assert 0 < id0_http < id0_all      # both families present
        # churn ONLY identity 0's HTTP family
        rules_of[0].append(("http", "/churn/added/.*"))
        loader.regenerate(resolve(), revision=2)
        t2 = loop.submit(lease, *_sections(corpus * 4))
        loop.step()
        # still bit-equal to the NEW serving engine
        assert [int(v) for v in t2.verdicts] == \
            _direct(loader, corpus * 4)
        # family-granular: the refill re-missed EXACTLY identity 0's
        # http rows — its DNS rows (and every other identity) kept
        # serving from the memo. Identity-granular would have
        # refilled id0_all; a full drop would re-miss everything.
        refilled = memo.misses - misses0
        assert refilled == id0_http
        assert memo.invalidations == inval0 + 1
        assert loop.ring.session.n_rows == n_unique  # no new rows yet
        # the NEW rule answers on a fresh chunk (new row = new miss)
        probe = http_flow(0, "/churn/added/x")
        t3 = loop.submit(lease, *_sections([probe] * 8))
        loop.step()
        assert [int(v) for v in t3.verdicts] == \
            _direct(loader, [probe] * 8)


def test_family_delta_affects_matrix():
    """PolicyDelta.affects: the granularity ladder, exactly."""
    from cilium_tpu.engine.memo import (
        FAMILY_ALL,
        PolicyDelta,
        affected_row_ids,
    )

    full = PolicyDelta(full=True)
    assert full.affects(1, 1) and full.affects(2, 3)
    ident = PolicyDelta.banks({7}, set())
    assert ident.affects(7, 1) and ident.affects(7, 3)
    assert not ident.affects(8, 1)
    fam = PolicyDelta.banks({7, 9}, set(),
                            identity_families={(7, "http"),
                                               (9, FAMILY_ALL)})
    assert fam.affects(7, 1)           # http row of 7
    assert not fam.affects(7, 3)       # dns row of 7 survives
    assert not fam.affects(7, 0)       # l4-only row survives
    assert fam.affects(9, 3) and fam.affects(9, 0)   # structural
    eps = np.array([7, 7, 8, 9, 7])
    l7s = np.array([1, 3, 1, 0, 0])
    assert affected_row_ids(fam, eps, l7s).tolist() == [0, 3]
    # merge: families-blind x family-scoped widens to identity level
    merged = fam.merge(PolicyDelta.banks({7}, set()))
    assert merged.affects(7, 3)
    # family-scoped x family-scoped stays narrow
    merged2 = fam.merge(PolicyDelta.banks(
        {5}, set(), identity_families={(5, "dns")}))
    assert not merged2.affects(7, 3) and merged2.affects(5, 3)


def test_port_delta_affects_matrix():
    """ISSUE 13: the bank-reference (port) rung of the granularity
    ladder — exact ports narrow, PORT_ALL widens, port info only
    survives a merge when both sides carry it."""
    from cilium_tpu.engine.memo import (
        PORT_ALL,
        PolicyDelta,
        affected_row_ids,
    )

    d = PolicyDelta.banks(
        {7}, set(), identity_families={(7, "http")},
        identity_family_ports={(7, "http", 8080)})
    assert d.affects(7, 1, 8080)
    assert not d.affects(7, 1, 80)     # same identity+family, other port
    assert d.affects(7, 1)             # port-blind consumer: family level
    assert not d.affects(7, 3, 8080)   # dns row untouched
    wide = PolicyDelta.banks(
        {7}, set(), identity_families={(7, "http")},
        identity_family_ports={(7, "http", PORT_ALL)})
    assert wide.affects(7, 1, 80) and wide.affects(7, 1, 8080)
    eps = np.array([7, 7, 7, 8])
    l7s = np.array([1, 1, 3, 1])
    dps = np.array([8080, 80, 53, 8080])
    assert affected_row_ids(d, eps, l7s, dports=dps).tolist() == [0]
    assert affected_row_ids(d, eps, l7s).tolist() == [0, 1]
    # merge: ports survive only when both sides carry them
    d2 = PolicyDelta.banks(
        {9}, set(), identity_families={(9, "dns")},
        identity_family_ports={(9, "dns", 53)})
    m = d.merge(d2)
    assert not m.affects(7, 1, 80) and m.affects(9, 3, 53)
    blind = PolicyDelta.banks({5}, set(),
                              identity_families={(5, "http")})
    m2 = d.merge(blind)
    assert m2.affects(7, 1, 80), \
        "merging a ports-blind delta must widen to all ports"


# ---------------------------------------------------------------------------
# drain + the wired stream service


def test_drain_flushes_pending_and_releases_all_leases(tmp_path):
    clk = VirtualClock()
    with simclock.use(clk):
        loop, loader, scenario = _world(tmp_path)
        flows = scenario.flows[:128]
        want = _direct(loader, flows)
        leases = [loop.connect(f"s{i}") for i in range(3)]
        tickets = [loop.submit(leases[i], *_sections(flows))
                   for i in range(3)]
        flushed = loop.drain()
        assert flushed == 3 * len(flows)
        for t in tickets:
            assert [int(v) for v in t.verdicts] == want
        st = loop.status()
        assert st["occupancy"] == 0 and st["draining"]
        with pytest.raises(ShedError):
            loop.connect("late")


def test_stream_service_through_ring_is_bit_equal(tmp_path):
    """The streaming golden through the WIRED path: VerdictService
    with Config.serve.enabled routes StreamSession chunks through
    ring slot leases; verdicts are bit-equal to the engine and the
    lease releases at end-of-stream."""
    import os

    from cilium_tpu.runtime.service import VerdictService
    from cilium_tpu.runtime.stream import StreamClient

    scenario = synth.scenario_by_name("http", 60, 1024)
    per_identity, scenario = synth.realize_scenario(scenario)
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.serve.enabled = True
    cfg.serve.pack_interval_ms = 2.0
    cfg.loader.cache_dir = str(tmp_path / "cache")
    loader = Loader(cfg)
    loader.regenerate(per_identity, revision=1)
    flows = scenario.flows[:600]
    want = _direct(loader, flows)
    sock = str(tmp_path / "v.sock")
    svc = VerdictService(loader, sock)
    svc.start()
    try:
        client = StreamClient(sock)
        seqs = [client.send_flows(flows[i:i + 150])
                for i in range(0, 600, 150)]
        got = []
        for s in seqs:
            got.extend(int(v) for v in client.result(s))
        client.finish()
        client.close()
        assert got == want
        st = svc.serveloop.status()
        assert st["grants"] >= 1
        assert st["occupancy"] == 0          # lease released
        assert st["bytes_saved"] > 0         # memo bypass happened
        assert os.path.exists(sock)
    finally:
        svc.stop()


def test_ticket_wait_times_out_on_virtual_clock():
    clk = VirtualClock()
    with simclock.use(clk):
        t = ChunkTicket(4)
        import threading

        got = []

        def waiter():
            try:
                t.wait(timeout=5.0)
            except TimeoutError:
                got.append(True)

        th = threading.Thread(target=waiter)
        th.start()
        while not clk._by_seq:
            threading.Event().wait(0.002)
        clk.advance(5.1)
        th.join(timeout=5.0)
        assert got == [True]


def test_lease_expired_submit_raises_and_releases(tmp_path):
    clk = VirtualClock()
    with simclock.use(clk):
        loop, loader, scenario = _world(tmp_path, ttl=5.0)
        lease = loop.connect("s0")
        clk.advance(5.0)
        with pytest.raises(LeaseExpired):
            loop.submit(lease, *_sections(scenario.flows[:8]))
        assert loop.status()["occupancy"] == 0
        assert loop.status()["expiries"] == 1


def test_lifetime_counters_exact_under_concurrent_bumps(tmp_path):
    """The PR-18 stats-lock regression gate: the lifetime counters
    are bumped from client threads AND the pack thread, sometimes
    while ``_lock`` is held (the gate path) and sometimes not — they
    ride a dedicated leaf lock, so (a) ``_shed`` must not deadlock
    when invoked WITH the loop lock held, and (b) concurrent bumps
    must never lose an update. Deterministic under the fix (the lock
    makes every increment atomic); pre-fix this flaked on preemption
    mid ``+=``. Virtual clock, no sleeps."""
    import sys
    import threading

    clk = VirtualClock()
    with simclock.use(clk):
        loop, _loader, _scenario = _world(tmp_path)
        # (a) the gate path: _shed under the loop lock — a counter
        # guarded by _lock itself would self-deadlock right here
        before = loop.sheds
        with loop._lock:
            loop._shed("queue-full")
        assert loop.sheds == before + 1

        # (b) exactness: hammer the counter from racing threads with
        # an aggressive switch interval so a bare += would drop bumps
        n_threads, per_thread = 8, 400
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            start = threading.Barrier(n_threads)

            def bump():
                start.wait()
                for _ in range(per_thread):
                    loop._shed("queue-full")

            threads = [threading.Thread(target=bump)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            sys.setswitchinterval(old)
        assert loop.sheds == before + 1 + n_threads * per_thread


def test_pack_cycle_reads_back_once_per_chunk(tmp_path):
    """Every packed chunk is resolved by its own blocking readback:
    three chunks in one pack count three ``serve.readback`` host
    syncs, however few packs carried them."""
    from cilium_tpu.runtime.metrics import ENGINE_HOST_SYNCS, METRICS

    labels = {"site": "serve.readback"}
    clk = VirtualClock()
    with simclock.use(clk):
        loop, loader, scenario = _world(tmp_path)
        flows = scenario.flows[:300]
        lease = loop.connect("s0")
        before = METRICS.get(ENGINE_HOST_SYNCS, labels)
        packs = loop.ring.packs
        tickets = [loop.submit(lease, *_sections(flows[i:i + 100]))
                   for i in range(0, 300, 100)]
        assert loop.step() == 300
        assert all(t.done and t.error is None for t in tickets)
        assert loop.ring.packs == packs + 1
        assert METRICS.get(ENGINE_HOST_SYNCS, labels) == before + 3
