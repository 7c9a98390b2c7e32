"""Served stream: an open-loop Poisson stream of capture-image chunks
over ``StreamClient`` connections (``benchmark/loadgen.py``, a process
of its own) into ``VerdictService`` →
``ServeLoop`` → ``VerdictRing``, at a rate fixed in the traffic file.
The arithmetic is a copy of ``bench_service.run_stream_point``
(PR 21): each chunk is timed from its scheduled send, so a stall
counts against every chunk it delays.

Set-up sends every pooled image once on every connection (compiles
the chunk shape, fills the session and memo tables) and compiles the
ring's dispatch at every pack size. Then the window
sends chunks on the schedule for ``--seconds``; chunk ``j`` goes on
connection ``j % connections``. The inter-arrival gaps are drawn from
``schedule_seed`` and put in an order drawn from ``--seed``, so every
seed offers the same load. After the last send each connection is
finished, which waits for every answer (a minute at the most).

Traffic keys: ``chunk_records``, ``pool_images``, ``connections``,
``pipeline_depth``, ``rate_records_s``, ``schedule_seed``, and the
world's own draw keys.
"""

from __future__ import annotations

import multiprocessing
import os
import random

import numpy as np

from benchmark import compare, loadgen
from benchmark.kinds import memory_peak, span, traced
from benchmark.program import Program
from benchmark.worlds import world_module

#: how long past the window the answers are waited for
DRAIN_S = 60.0
#: how long the load generator may take to connect and warm up
SETUP_WAIT_S = 300.0


def _recv(pipe, proc, timeout: float, what: str):
    if not pipe.poll(timeout):
        raise RuntimeError(f"load generator: no {what} message in "
                           f"{timeout:.0f}s (alive={proc.is_alive()})")
    return pipe.recv()


def schedule(rate_chunks_s: float, seconds: float, schedule_seed: int,
             seed: int):
    """Send offsets (s) inside [0, seconds): gaps drawn once from
    ``schedule_seed``, ordered by ``seed``."""
    g = random.Random(schedule_seed)
    gaps, t = [], 0.0
    while True:
        d = g.expovariate(rate_chunks_s)
        if t + d >= seconds:
            break
        gaps.append(d)
        t += d
    random.Random(seed).shuffle(gaps)
    return list(np.cumsum(gaps))


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=float), q))


def run(env) -> dict:
    cfg, traffic = env.cfg, env.traffic
    world = world_module(cfg)
    docs, endpoints = world.policy(cfg)
    with span("setup.stage"):
        prog = Program(docs, endpoints, env.devices[0], env.cache_dir,
                       serve=True)
    size, n_img = traffic["chunk_records"], traffic["pool_images"]
    conns = traffic["connections"]
    rng = random.Random(env.seed)
    recs = world.draw(cfg, traffic, rng, size * n_img)
    pool = [recs[i * size:(i + 1) * size] for i in range(n_img)]
    with span("setup.images"):
        images = [prog.image(p) for p in pool]
        widths = prog.widths(images)
    svc = prog.service(os.path.join(env.scratch, "v.sock"))
    mp = multiprocessing.get_context("spawn")
    here, there = mp.Pipe()
    gen = mp.Process(target=loadgen.main, name="bench-loadgen",
                     args=(there, svc.socket_path, images, widths, conns,
                           traffic["pipeline_depth"], DRAIN_S))
    try:
        with span("setup.warm"):
            gen.start()
            _recv(here, gen, SETUP_WAIT_S, "warm-up")
            prog.warm_pack_buckets(svc, size)
        offsets = schedule(traffic["rate_records_s"] / size, env.seconds,
                           traffic["schedule_seed"], env.seed)
        irng = random.Random(env.seed ^ 0x5A5A5A5A)
        picks = [irng.randrange(n_img) for _ in offsets]
        c0 = env.meter.snapshot()
        cnt0 = prog.counters()
        with traced(env) as tr:
            here.send((offsets, picks))
            res = _recv(here, gen, env.seconds + 2 * DRAIN_S, "window")
        cnt1 = prog.counters()
        c1 = env.meter.snapshot()
        gen.join(timeout=DRAIN_S)
    finally:
        if gen.is_alive():
            gen.terminate()
            gen.join(timeout=10)
        here.close()
        svc.stop()
    peak = memory_peak(env.devices[0])
    del prog, svc
    base, got, late = res["base"], res["got"], res["late"]
    t_open = base
    t_close = max(base + env.seconds,
                  max((t for _, t in got.values()), default=base))

    lat = [got[j][1] - (base + offsets[j]) for j in sorted(got)]
    worst = sorted(((got[j][1] - base - offsets[j], offsets[j])
                    for j in got), reverse=True)[:12]
    env.log("slowest chunks (latency s @ due s): " + " ".join(
        f"{l:.3f}@{o:.2f}" for l, o in worst))
    n_chunks = len(offsets)
    failed = n_chunks - len(got)
    ref = env.reference(docs, endpoints)
    want = {i: ref.lanes(pool[i])["verdict"] for i in set(picks)}
    ctl = env.control(docs, endpoints)
    if ctl is not None:
        got = {j: (ctl.lanes(pool[picks[j]])["verdict"], t)
               for j, (_, t) in got.items()}
    wrong = compared = 0
    missing = failed * size
    for j, (verdicts, _) in got.items():
        w = want[picks[j]]
        n = min(len(verdicts), len(w))
        wrong += compare.wrong_answers({"verdict": verdicts[:n]},
                                       {"verdict": w[:n]}, ("verdict",))
        missing += len(w) - n
        compared += len(w)
    e2e = {"setup_s": t_open - env.t0}
    if lat:
        e2e["served_p50_ms"] = percentile(lat, 50) * 1e3
        e2e["served_p99_ms"] = percentile(lat, 99) * 1e3
    return {
        "e2e": e2e,
        "attempted": n_chunks,
        "failed": failed,
        "checks": compare.checks(wrong, missing, compared),
        "memory_peak_bytes": peak,
        "ctx": {
            "window_s": t_close - t_open, "chunks": n_chunks,
            "completed": len(got), "connections": conns,
            "late_s": late, "latency_s": lat,
            "counters": {k: cnt1[k] - cnt0[k] for k in cnt0},
            "compiles_in_window": c1[0] - c0[0],
            "compile_s_setup": c0[2],
            "compiles_setup": c0[0], "cache_hits_setup": c0[1],
            "trace": tr or None,
        },
    }
