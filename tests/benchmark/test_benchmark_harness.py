"""The harness on the CPU at a tiny size: each cell runs end to end
and is correct, its last line has the contract's keys, it exits
non-zero without a TPU, and a new cell and metric are found by name."""

from __future__ import annotations

import json
import os

import pytest

from benchmark import run
from benchmark.kinds import served

CELLS = [w["name"] for w in run.benchmark_spec()["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("cell", CELLS)
def test_benchmark_cell_runs_and_is_correct(run_tiny, cell):
    out, lines = run_tiny(cell)
    assert list(out) == KEYS + ["checks"]
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["wrong_answers"]["value"] == 0
    assert out["checks"]["compared_records"]["value"] > 0
    assert "compilations in the window: 0" in lines
    spec = run.benchmark_spec()
    want = {m["name"] for m in run.e2e_for(spec, cell)}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    json.dumps(out)


@pytest.mark.parametrize("cell", CELLS)
def test_benchmark_traced_run_reports_per_layer(run_tiny, cell):
    out, _ = run_tiny(cell, trace=True)
    assert list(out)[:len(KEYS)] == KEYS and list(out)[-1] == "checks"
    assert set(out) <= set(KEYS) | {"breakdown", "checks"}
    spec = run.benchmark_spec()
    allowed = {m["name"] for m in run.per_layer_for(
        spec, cell, {e["name"] for e in run.e2e_for(spec, cell)})}
    # the CPU has no device plane: trace-read metrics stay silent
    assert set(out["metrics"]) <= allowed
    assert "setup.compile_s" in out["metrics"]
    assert out["correct"] is True


def test_benchmark_exits_nonzero_without_tpu(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_every_metric_moves_a_metric_its_cells_report():
    spec = run.benchmark_spec()
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in e2e[m["moves"]].get("workloads", CELLS), m
        assert callable(run.metric_reader(m["name"]))


def test_benchmark_finds_new_traffic_and_metric_by_name(run_tiny, tiny_root):
    """A later PR adds a cell and a metric with files and entries only."""
    bench = os.path.join(tiny_root, "BENCHMARK.json")
    with open(bench) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "http-replay-small",
                              "config": "http-1k-regex",
                              "traffic": "http-replay-small", "chips": 1,
                              "why": "throwaway"})
    spec["per_layer"].append({"name": "replay.segments", "unit": "n",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "throwaway",
                              "moves": "replay_verdicts_per_s",
                              "workloads": ["http-replay-small"]})
    spec["end_to_end"][0]["workloads"].append("http-replay-small")
    with open(bench, "w") as f:
        json.dump(spec, f)
    with open(os.path.join(tiny_root, "benchmark", "traffic",
                           "http-replay-small.json"), "w") as f:
        json.dump({"kind": "replay", "rules_by": "uniform",
                   "segment_records": 256, "pool_segments": 1,
                   "compare_per_segment": 8, "compare_max": 64}, f)
    with open(os.path.join(tiny_root, "benchmark", "metrics",
                           "replay.segments.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx['segments'])\n")
    out, _ = run_tiny("http-replay-small", trace=True)
    assert out["correct"] is True
    assert out["metrics"]["replay.segments"]["value"] >= 1


def test_benchmark_schedule_offers_every_seed_the_same_load():
    a = served.schedule(500.0, 4.0, 1, 2**31 + 1)
    b = served.schedule(500.0, 4.0, 1, 7)
    assert a != b
    gaps = lambda xs: sorted(round(y - x, 12) for x, y in
                             zip([0.0] + xs[:-1], xs))
    assert gaps(a) == gaps(b)
    assert abs(a[-1] - b[-1]) < 1e-9 and a[-1] < 4.0
    assert 1800 < len(a) < 2200


def test_benchmark_percentile_is_of_all_values():
    vals = list(range(1, 101))
    assert served.percentile(vals, 50) == 50.5
    assert served.percentile(vals, 99) == pytest.approx(99.01)
