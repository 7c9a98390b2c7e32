"""Capture replay: a pool of capture segments is written at set-up and
cycled through the window, each replay a fresh ``CaptureReplay``
session (string-table scan, whole-file featurize, dedup, one verdict
chunk). The window opens after every pool segment has been replayed
once and closes at the first segment completion after ``--seconds``.

``replay_verdicts_per_s`` = records whose verdicts reached the host ÷
the whole window, staging included.

Traffic keys: ``segment_records``, ``pool_segments``,
``compare_per_segment`` (records of each replayed segment kept for the
comparison, drawn from the seed), ``compare_max`` (of those, how many
the reference checks once the window has closed, drawn from the seed
over the whole window), and the world's own draw keys.
"""

from __future__ import annotations

import os
import random
import sys
import time
import traceback

import numpy as np

from benchmark import compare
from benchmark.kinds import memory_peak, span, traced
from benchmark.program import Program
from benchmark.worlds import world_module


def _pick(lane, idx) -> np.ndarray:
    """The sampled records' answers; -1 where the lane has none (an
    answer that never came)."""
    lane = np.asarray(lane)
    idx = np.asarray(idx)
    got = np.full(len(idx), -1, dtype=np.int64)
    have = idx < len(lane)
    got[have] = lane[idx[have]]
    return got


def run(env) -> dict:
    cfg, traffic = env.cfg, env.traffic
    world = world_module(cfg)
    docs, endpoints = world.policy(cfg)
    with span("setup.stage"):
        prog = Program(docs, endpoints, env.devices[0], env.cache_dir)
    n, n_pool = traffic["segment_records"], traffic["pool_segments"]
    rng = random.Random(env.seed)
    pool = []
    with span("setup.write"):
        for k in range(n_pool):
            recs = world.draw(cfg, traffic, rng, n, first_id=k * n)
            path = os.path.join(env.scratch, f"segment{k}.cap")
            prog.write_segment(path, recs)
            pool.append((path, recs))
    with span("setup.warm"):
        for path, _ in pool:
            prog.replay_segment(path)
    per_seg = traffic["compare_per_segment"]
    srng = random.Random(env.seed ^ 0x5A5A5A5A)
    done, facts, failed = [], [], 0
    c0 = env.meter.snapshot()
    cnt0 = prog.counters()
    with traced(env) as tr:
        t_open = time.perf_counter()
        while True:
            k = len(done) + failed // n
            path, recs = pool[k % n_pool]
            try:
                with span("replay.segment"):
                    out, f = prog.replay_segment(path)
            except Exception:  # noqa: BLE001 — a failed segment is counted
                traceback.print_exc(file=sys.stderr)
                failed += n
            else:
                idx = sorted(srng.sample(range(n), per_seg))
                done.append((k % n_pool, idx,
                             {l: _pick(out[l], idx)
                              for l in compare.LANES}))
                out_bytes = sum(int(np.asarray(v).nbytes)
                                for v in out.values())
                facts.append({**f, "out_bytes": out_bytes})
            if time.perf_counter() - t_open >= env.seconds:
                break
        t_close = time.perf_counter()
    cnt1 = prog.counters()
    c1 = env.meter.snapshot()
    peak = memory_peak(env.devices[0])
    policy_bytes = prog.policy_array_bytes()
    del prog

    window_s = t_close - t_open
    records = sum(f["records"] for f in facts)
    # the sample: up to compare_max of the kept records, drawn from
    # the seed over every segment the window replayed
    kept = [(seg, i, j) for seg, idx, _ in done for j, i in enumerate(idx)]
    seg_of = [k for k, (_, idx, _) in enumerate(done) for _ in idx]
    pick = sorted(srng.sample(range(len(kept)),
                              min(len(kept), traffic["compare_max"])))
    recs = [pool[kept[p][0]][1][kept[p][1]] for p in pick]
    got = {l: np.array([done[seg_of[p]][2][l][kept[p][2]] for p in pick],
                       dtype=np.int64) for l in compare.LANES}
    ref = env.reference(docs, endpoints)
    ctl = env.control(docs, endpoints)
    if ctl is not None:
        got = ctl.lanes(recs)
    came = got["verdict"] >= 0
    want = ref.lanes(recs) if recs else got
    wrong = compare.wrong_answers({l: v[came] for l, v in got.items()},
                                  {l: v[came] for l, v in want.items()},
                                  compare.LANES)
    missing = failed + int((~came).sum())
    compared = len(recs)
    return {
        "e2e": {"replay_verdicts_per_s": records / window_s,
                "setup_s": t_open - env.t0},
        "attempted": records + failed,
        "failed": failed,
        "checks": compare.checks(wrong, missing, compared),
        "memory_peak_bytes": peak,
        "ctx": {
            "window_s": window_s, "records": records,
            "segments": facts, "policy_array_bytes": policy_bytes,
            "counters": {k: cnt1[k] - cnt0[k] for k in cnt0},
            "compiles_in_window": c1[0] - c0[0],
            "compile_s_setup": c0[2],
            "compiles_setup": c0[0], "cache_hits_setup": c0[1],
            "trace": tr or None,
        },
    }
