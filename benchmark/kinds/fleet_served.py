"""Served fleet: the ``served`` kind's open-loop stream at fleet scale,
into one replica of the policy per chip behind one socket:
``FleetVerdictService`` → ``FleetRouter`` (rendezvous placement by
stream id, which is the connection's order) → the placed replica's
``ServeLoop``, on its own pack thread → the fused step on its own chip.

Set-up builds one ``Program`` per device over the one artifact cache
(the first compiles, the others load its banks), sends every pooled
image once on every connection (each replica compiles its chunk shape
and fills its session and memo from the streams placed on it) and
compiles every replica's ring dispatch at every pack size. The window,
the schedule (``served.schedule``), the load generator
(``benchmark/loadgen.py``) and the comparison are the ``served``
kind's: every record of every answered chunk against the reference on
the verdict lane. After the window the router's lease conservation and
journal consistency are read; a violation of either counts every chunk
of the window as failed, and the check ``fleet_violations`` (limit 0)
makes the run not correct.

Traffic keys: those of ``served``.
"""

from __future__ import annotations

import multiprocessing
import os
import random
import types
from typing import Dict, List

from cilium_tpu.runtime.metrics import FLEET_ROUTED_CHUNKS, METRICS
from cilium_tpu.runtime.service import FleetVerdictService

from benchmark import compare, loadgen
from benchmark.kinds import memory_peak, served, span, traced
from benchmark.program import Program
from benchmark.worlds import world_module

#: the traffic keys the CPU rehearsal (``tests/benchmark/``) cuts: as
#: ``served``'s, with enough connections to place streams on every
#: replica
TINY = {**served.TINY, "connections": 8}


class Fleet:
    """One ``Program`` per device, and the fleet service over their
    loaders."""

    def __init__(self, docs, endpoints, devices, cache_dir: str):
        self.programs = [Program(docs, endpoints, d, cache_dir, serve=True)
                         for d in devices]
        self.service = None

    def serve(self, sock_path: str) -> FleetVerdictService:
        self.service = FleetVerdictService(
            [p.loader for p in self.programs], sock_path)
        self.service.start()
        return self.service

    @property
    def hosts(self) -> List[str]:
        return [r.name for r in self.service.router.replicas]

    def warm_pack_buckets(self, chunk_records: int) -> int:
        """``Program.warm_pack_buckets`` on every replica's loop."""
        return sum(Program.warm_pack_buckets(
            types.SimpleNamespace(serveloop=r.loop), chunk_records)
            for r in self.service.router.replicas)

    def counters(self) -> Dict[str, float]:
        """``Program.counters`` over every replica's host series, and
        each host's routed chunks (``routed.<host>``)."""
        out = Program.counters(self.hosts)
        for h in self.hosts:
            out[f"routed.{h}"] = METRICS.get(FLEET_ROUTED_CHUNKS,
                                             {"host": h})
        return out

    def violations(self) -> List[str]:
        """The router's conservation and journal findings (empty when
        the fleet's books hold)."""
        router = self.service.router
        found = [router.conservation_violation(),
                 router.journal_consistent()]
        return [str(v) for v in found if v is not None]


def run(env) -> dict:
    cfg, traffic = env.cfg, env.traffic
    world = world_module(cfg)
    docs, endpoints = world.policy(cfg)
    with span("setup.stage"):
        fleet = Fleet(docs, endpoints, env.devices, env.cache_dir)
    size, n_img = traffic["chunk_records"], traffic["pool_images"]
    conns = traffic["connections"]
    rng = random.Random(env.seed)
    recs = world.draw(cfg, traffic, rng, size * n_img)
    pool = [recs[i * size:(i + 1) * size] for i in range(n_img)]
    prog = fleet.programs[0]
    with span("setup.images"):
        images = [prog.image(p) for p in pool]
        widths = prog.widths(images)
    svc = fleet.serve(os.path.join(env.scratch, "v.sock"))
    mp = multiprocessing.get_context("spawn")
    here, there = mp.Pipe()
    gen = mp.Process(target=loadgen.main, name="bench-loadgen",
                     args=(there, svc.socket_path, images, widths, conns,
                           traffic["pipeline_depth"], served.DRAIN_S))
    try:
        with span("setup.warm"):
            gen.start()
            served._recv(here, gen, served.SETUP_WAIT_S, "warm-up")
            fleet.warm_pack_buckets(size)
        offsets = served.schedule(traffic["rate_records_s"] / size,
                                  env.seconds, traffic["schedule_seed"],
                                  env.seed)
        irng = random.Random(env.seed ^ 0x5A5A5A5A)
        picks = [irng.randrange(n_img) for _ in offsets]
        c0 = env.meter.snapshot()
        cnt0 = fleet.counters()
        with traced(env) as tr:
            here.send((offsets, picks))
            res = served._recv(here, gen,
                               env.seconds + 2 * served.DRAIN_S, "window")
        cnt1 = fleet.counters()
        c1 = env.meter.snapshot()
        gen.join(timeout=served.DRAIN_S)
        broken = fleet.violations()
    finally:
        if gen.is_alive():
            gen.terminate()
            gen.join(timeout=10)
        here.close()
        svc.stop()
    peaks = [memory_peak(d) for d in env.devices]
    env.log(f"peak device memory per chip: {peaks}")
    hosts = fleet.hosts
    del fleet, svc, prog
    base, got, late = res["base"], res["got"], res["late"]
    t_open = base
    t_close = max(base + env.seconds,
                  max((t for _, t in got.values()), default=base))
    lat = [got[j][1] - (base + offsets[j]) for j in sorted(got)]
    n_chunks = len(offsets)
    failed = n_chunks - len(got)
    if broken:
        env.log(f"fleet books broken: {broken}")
        failed = n_chunks
    ref = env.reference(docs, endpoints)
    want = {i: ref.lanes(pool[i])["verdict"] for i in set(picks)}
    ctl = env.control(docs, endpoints)
    if ctl is not None:
        got = {j: (ctl.lanes(pool[picks[j]])["verdict"], t)
               for j, (_, t) in got.items()}
    wrong = compared = 0
    missing = (n_chunks - len(got)) * size
    for j, (verdicts, _) in got.items():
        w = want[picks[j]]
        n = min(len(verdicts), len(w))
        wrong += compare.wrong_answers({"verdict": verdicts[:n]},
                                       {"verdict": w[:n]}, ("verdict",))
        missing += len(w) - n
        compared += len(w)
    checks = compare.checks(wrong, missing, compared)
    checks["fleet_violations"] = {"value": len(broken), "limit": 0}
    e2e = {"setup_s": t_open - env.t0}
    if lat:
        e2e["served_p50_ms"] = served.percentile(lat, 50) * 1e3
        e2e["served_p99_ms"] = served.percentile(lat, 99) * 1e3
    counters = {k: cnt1[k] - cnt0[k] for k in cnt0}
    return {
        "e2e": e2e,
        "attempted": n_chunks,
        "failed": failed,
        "checks": checks,
        "memory_peak_bytes": max((p for p in peaks if p is not None),
                                 default=None),
        "ctx": {
            "window_s": t_close - t_open, "chunks": n_chunks,
            "completed": len(got), "connections": conns,
            "late_s": late, "latency_s": lat,
            "counters": counters,
            "routed": {h: counters[f"routed.{h}"] for h in hosts},
            "compiles_in_window": c1[0] - c0[0],
            "compile_s_setup": c0[2],
            "compiles_setup": c0[0], "cache_hits_setup": c0[1],
            "trace": tr or None,
        },
    }
