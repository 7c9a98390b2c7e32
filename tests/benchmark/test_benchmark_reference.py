"""The benchmark's reference against the program's CPU oracle, and
each generator's traffic against its parameters."""

from __future__ import annotations

import collections
import json
import math
import os
import random
import subprocess
import sys

import numpy as np
import pytest

from benchmark import compare
from benchmark.worlds import resolve, to_flow, world_module
from benchmark.worlds import http as http_world

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", kind, f"{name}.json")) as f:
        return json.load(f)


def _program_lanes(docs, endpoints, recs):
    from cilium_tpu.policy.oracle import OracleVerdictEngine

    from benchmark.program import _modules

    mods = _modules()
    per_identity, ids = resolve(mods, docs, endpoints)
    flows = [to_flow(mods.flow, r, ids) for r in recs]
    return OracleVerdictEngine(per_identity).verdict_flows(flows), ids


@pytest.mark.parametrize("config,traffic,n", [
    ("http-1k-regex", "http-replay-fresh", 150),
    ("http-1k-regex", "http-served-zipf", 150),
])
def test_benchmark_reference_equals_program_oracle(config, traffic, n):
    cfg, tr = _load("configs", config), _load("traffic", traffic)
    world = world_module(cfg)
    docs, endpoints = world.policy(cfg)
    recs = world.draw(cfg, tr, random.Random(2**31 + 3), n)
    ref = compare.Reference(docs, endpoints)
    want, ids = _program_lanes(docs, endpoints, recs)
    assert ref.ids == ids
    got = ref.lanes(recs)
    for k in compare.LANES:
        assert np.array_equal(got[k], np.asarray(want[k]).astype(np.int64)), k
    # neither side is degenerate: allows and drops both occur
    assert len(set(got["verdict"].tolist())) >= 2


def test_benchmark_control_breaks_the_l7_guarantee():
    cfg = _load("configs", "http-1k-regex")
    docs, endpoints = http_world.policy(cfg)
    recs = http_world.draw(cfg, {"rules_by": "uniform"},
                           random.Random(5), 400)
    want = compare.Reference(docs, endpoints).lanes(recs)
    ctl = compare.Reference(docs, endpoints, control=True).lanes(recs)
    wrong = compare.wrong_answers(ctl, want, compare.LANES)
    assert wrong == int((want["verdict"] == 2).sum()) > 100


def test_benchmark_reference_imports_nothing_of_the_program():
    code = ("import sys, benchmark.compare, benchmark.worlds.http, "
            "benchmark.reference.oracle, "
            "benchmark.reference.cnp, benchmark.reference.mapstate\n"
            "bad = [m for m in sys.modules if m.startswith('cilium_tpu')]\n"
            "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_benchmark_fresh_paths_are_all_unique_and_fit_the_bucket():
    cfg, tr = _load("configs", "http-1k-regex"), _load(
        "traffic", "http-replay-fresh")
    rng = random.Random(2**31 + 9)
    n = 4096
    recs = []
    for k in range(tr["pool_segments"]):
        recs += http_world.draw(cfg, tr, rng, n, first_id=k * n)
    paths = [r[6][1] for r in recs]
    assert len(set(paths)) == len(paths)  # 100% unique
    assert max(len(p.encode()) for p in paths) <= \
        cfg["field_widths"]["path_max_bytes"]


def test_benchmark_zipf_traffic_matches_its_parameters():
    cfg, tr = _load("configs", "http-1k-regex"), _load(
        "traffic", "http-served-zipf")
    recs = http_world.draw(cfg, tr, random.Random(2**31 + 11), 200_000)
    # every record is one of the 1,000 rules' hit/miss templates
    pool = {http_world.request(i, h) for i in range(cfg["rules"])
            for h in (True, False)}
    assert set(recs) <= pool
    rule_of = {http_world.request(i, h): i for i in range(cfg["rules"])
               for h in (True, False)}
    counts = collections.Counter(rule_of[r] for r in recs)
    freq = sorted(counts.values(), reverse=True)
    # slope of log(frequency) over log(rank), ranks 1..100
    x = np.log(np.arange(1, 101))
    y = np.log(np.asarray(freq[:100], dtype=float))
    slope = np.polyfit(x, y, 1)[0]
    assert abs(-slope - tr["zipf_s"]) < 0.1, slope
    hits = sum(1 for r in recs if r == http_world.request(rule_of[r], True))
    assert abs(hits / len(recs) - 0.5) < 0.01


def test_benchmark_zipf_cdf_is_the_zipf_law():
    cdf = http_world.zipf_cdf(1000, 1.0)
    assert math.isclose(cdf[-1], sum(1 / r for r in range(1, 1001)))


@pytest.mark.parametrize("kind", range(5))
def test_benchmark_fresh_ids_keep_each_template_hit_or_miss(kind):
    """A per-record id leaves a template's verdict as it was, except
    kind 4, whose exact path takes it in the query and always misses."""
    cfg = dict(_load("configs", "http-1k-regex"), rules=50)
    docs, endpoints = http_world.policy(cfg)
    ref = compare.Reference(docs, endpoints)
    recs = [http_world.request(i, hit, uid)
            for i in range(kind, 50, 5) for hit in (True, False)
            for uid in (None, 0xBEEF, 2**40 + 7)]
    v = ref.lanes(recs)["verdict"].reshape(-1, 2, 3)
    hit, miss = v[:, 0], v[:, 1]
    assert (miss == 2).all()
    assert (hit[:, 0] == 5).all()
    assert (hit[:, 1:] == (2 if kind == 4 else 5)).all()
