"""The socket-fronted fleet service (``runtime/service.py``
``FleetVerdictService``) over four CPU-device replicas: every chunk of
every stream is answered by the replica the router placed the stream
on, with the verdicts of the frozen reference and of a single-replica
``VerdictService``; the replicas pack on their own threads, so one
stalled replica holds up only its own streams; and ``HostReplica``
builds its loop from ``Config.serve``."""

from __future__ import annotations

import itertools
import random
import threading
import zlib

import jax
import numpy as np
import pytest

from benchmark import compare
from benchmark.program import Program
from benchmark.worlds import http
from cilium_tpu.core.config import Config
from cilium_tpu.runtime.fleetserve import FleetRouter, HostReplica
from cilium_tpu.runtime.metrics import FLEET_ROUTED_CHUNKS, METRICS
from cilium_tpu.runtime.serveloop import ServeLoop
from cilium_tpu.runtime.service import FleetVerdictService, VerdictService
from cilium_tpu.runtime.stream import StreamClient

RULES = 50
REPLICAS = 4
STREAMS = 8
CHUNK = 64
CHUNKS_PER_STREAM = 3


def _home(stream_id: str) -> str:
    """The rendezvous-first host of ``stream_id`` over the replicas
    (``FleetRouter._score``: crc32 of host and stream id)."""
    return max((f"host-{i}" for i in range(REPLICAS)),
               key=lambda h: zlib.crc32(f"{h}|{stream_id}".encode()))


def _spread_ids(n: int):
    """``n`` stream ids whose homes cover every replica (rendezvous
    does not promise that of ``n`` arbitrary ids): round-robin over
    the hosts, each the next id that hashes home there."""
    pools = {f"host-{i}": [] for i in range(REPLICAS)}
    for k in itertools.count():
        sid = f"s{k}"
        pools[_home(sid)].append(sid)
        if all(len(v) >= n // REPLICAS + 1 for v in pools.values()):
            break
    order = itertools.cycle(sorted(pools))
    return [pools[h].pop(0) for h, _ in zip(order, range(n))]


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """Four replicas' loaders (one a CPU device) over one artifact
    cache, a single-replica loader, the records and the reference."""
    cache = str(tmp_path_factory.mktemp("fleet_cache"))
    docs, endpoints = http.policy({"rules": RULES})
    progs = [Program(docs, endpoints, d, cache, serve=True)
             for d in jax.devices()[:REPLICAS]]
    single = Program(docs, endpoints, jax.devices()[REPLICAS], cache,
                     serve=True)
    rng = random.Random(2**31 + 5)
    recs = http.draw({"rules": RULES}, {"rules_by": "zipf", "zipf_s": 1.0},
                     rng, STREAMS * CHUNKS_PER_STREAM * CHUNK)
    chunks = [recs[i:i + CHUNK] for i in range(0, len(recs), CHUNK)]
    images = [progs[0].image(c) for c in chunks]
    ref = compare.Reference(docs, endpoints)
    want = [list(ref.lanes(c)["verdict"]) for c in chunks]
    return {"loaders": [p.loader for p in progs],
            "single": single.loader, "images": images,
            "widths": Program.widths(images), "want": want}


def _serve(svc, world, ids, timeout=60.0):
    """Every stream ``ids[s]`` sends chunks ``s::STREAMS``; returns
    the verdicts by chunk index."""
    svc.start()
    try:
        got = {}
        clients = [StreamClient(svc.socket_path, widths=world["widths"],
                                timeout=timeout, stream_id=sid)
                   for sid in ids]
        sent = [(c, s, clients[s].send_image(world["images"][c]))
                for s in range(len(ids))
                for c in range(s, len(world["images"]), len(ids))]
        for c, s, seq in sent:
            got[c] = [int(v) for v in clients[s].result(seq)]
        for cl in clients:
            cl.finish()
            cl.close()
        return got
    finally:
        svc.stop()


def _routed(hosts):
    return {h: METRICS.get(FLEET_ROUTED_CHUNKS, {"host": h})
            for h in hosts}


def test_fleet_answers_every_chunk_as_reference_and_single_replica(
        world, tmp_path):
    ids = _spread_ids(STREAMS)
    assert {_home(s) for s in ids} == {f"host-{i}"
                                       for i in range(REPLICAS)}
    fleet = FleetVerdictService(world["loaders"], str(tmp_path / "f.sock"))
    router = fleet.router
    hosts = [r.name for r in router.replicas]
    before = _routed(hosts)
    served0 = {r.name: r.loop.status()["served_records"]
               for r in router.replicas}
    got = _serve(fleet, world, ids)
    one = _serve(VerdictService(world["single"], str(tmp_path / "s.sock")),
                 world, [None] * STREAMS)
    assert len(got) == len(world["images"])
    assert [got[c] for c in sorted(got)] == world["want"]
    assert [one[c] for c in sorted(one)] == world["want"]
    # each stream's chunks went to its home replica, and every replica
    # answered the streams placed on it
    routed = {h: v - before[h] for h, v in _routed(hosts).items()}
    per_host = {h: sum(CHUNKS_PER_STREAM for s in ids if _home(s) == h)
                for h in hosts}
    assert routed == per_host and min(routed.values()) >= 1
    for r in router.replicas:
        served = r.loop.status()["served_records"] - served0[r.name]
        assert served == per_host[r.name] * CHUNK
    assert router.conservation_violation() is None
    assert router.journal_consistent() is None
    assert router.placements == {}          # every stream unpinned


def test_stream_without_id_is_placed_by_connection_order(world, tmp_path):
    fleet = FleetVerdictService(world["loaders"], str(tmp_path / "f.sock"))
    hosts = [r.name for r in fleet.router.replicas]
    before = _routed(hosts)
    got = _serve(fleet, world, [None] * 2)
    assert [got[c] for c in sorted(got)] == world["want"]
    routed = {h: v - before[h] for h, v in _routed(hosts).items()}
    n = len(world["images"])
    want = {h: 0 for h in hosts}
    want[_home("conn-0")] += len(range(0, n, 2))
    want[_home("conn-1")] += len(range(1, n, 2))
    assert routed == want


def _blocked_resolve(monkeypatch, host: str):
    """Hold ``host``'s loop in its resolve (the pack cycle answering a
    chunk) until ``release`` is set; ``entered`` is set once it holds.
    Returns ``(entered, release)``."""
    entered, release = threading.Event(), threading.Event()
    orig = ServeLoop._resolve_ticket

    def resolve(self, ticket, n, dev):
        if self.host_id == host:
            entered.set()
            release.wait(30.0)
        return orig(self, ticket, n, dev)

    monkeypatch.setattr(ServeLoop, "_resolve_ticket", resolve)
    return entered, release


def _serial_start(router):
    """A ``step_all``-style driver: one thread steps the replicas one
    after another (what the served path must not be)."""
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            router.step_all()
            stop.wait(0.002)

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    return stop, t


@pytest.mark.parametrize("driver", ["pack-threads", "serial"])
def test_stalled_replica_holds_up_only_its_own_streams(
        world, tmp_path, monkeypatch, driver):
    """With one replica stalled in its resolve, the streams placed on
    the other three still get their answers from their own pack
    threads; under a serial ``step_all`` loop they would not, which
    the ``serial`` case checks, so the test cannot pass vacuously."""
    ids = _spread_ids(REPLICAS)
    stalled = _home(ids[0])
    entered, release = _blocked_resolve(monkeypatch, stalled)
    fleet = FleetVerdictService(world["loaders"], str(tmp_path / "f.sock"))
    serial = None
    if driver == "serial":
        monkeypatch.setattr(fleet.router, "start", lambda: None)
        serial = _serial_start(fleet.router)
    fleet.start()
    clients = []
    try:
        clients = [StreamClient(fleet.socket_path, widths=world["widths"],
                                timeout=2.0, stream_id=sid) for sid in ids]
        # the stalled replica holds its chunk before the others send
        seqs = [clients[0].send_image(world["images"][0])]
        assert entered.wait(30.0)
        seqs += [cl.send_image(world["images"][k])
                 for k, cl in enumerate(clients) if k]
        answered = {}
        for k, (cl, seq) in enumerate(zip(clients, seqs)):
            if _home(ids[k]) == stalled:
                continue
            try:
                answered[k] = [int(v) for v in cl.result(seq)]
            except TimeoutError:
                pass
        others = [k for k in range(len(ids)) if _home(ids[k]) != stalled]
        if driver == "serial":
            assert not answered
        else:
            assert answered == {k: world["want"][k] for k in others}
        release.set()
        first = [int(v) for v in clients[0].result(seqs[0])]
        assert first == world["want"][0]
    finally:
        release.set()
        if serial is not None:
            serial[0].set()
            serial[1].join(timeout=30.0)
            assert not serial[1].is_alive()
        for cl in clients:
            cl.close()
        fleet.stop()


def test_host_replica_builds_its_loop_from_config_serve(world):
    cfg = Config()
    cfg.serve.pack_interval_ms = 7.0
    cfg.serve.max_slot_pending = 5
    cfg.serve.slot_capacity = 16
    cfg.serve.lease_ttl_s = 12.0
    loader = world["loaders"][0]
    saved = loader.config
    loader.config = cfg
    try:
        r = HostReplica(2, loader)
        sized = HostReplica(3, loader, capacity=8, lease_ttl_s=4.0)
    finally:
        loader.config = saved
    assert r.loop.pack_interval_s == pytest.approx(0.007)
    assert r.loop.max_slot_pending == 5
    assert r.loop.ring.capacity == 16 and r.loop.lease_ttl_s == 12.0
    assert r.loop.host_id == "host-2" and r.loop.explain is r.explain
    assert sized.loop.ring.capacity == 8 and sized.loop.lease_ttl_s == 4.0
    assert sized.loop.pack_interval_s == pytest.approx(0.007)
    router = FleetRouter([r, sized]).start()
    try:
        names = {t.name for t in threading.enumerate()}
        assert {"serve-pack-loop-host-2",
                "serve-pack-loop-host-3"} <= names
    finally:
        for rep in router.replicas:
            rep.loop.stop()
    assert np.all([rep.loop._thread is None for rep in router.replicas])
