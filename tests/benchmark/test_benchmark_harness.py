"""The harness on the CPU at a tiny size: each cell runs end to end
and is correct, its last line has the contract's keys, it exits
non-zero without a TPU, and a new cell and metric, or a four-chip cell
of a new traffic kind, are found by name."""

from __future__ import annotations

import importlib
import json
import os
import sys
import types

import pytest

from benchmark import run, sweep
from benchmark.kinds import served

REPO = run.ROOT

CELLS = [w["name"] for w in run.benchmark_spec()["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]
#: the per-layer metric of set-up compile seconds, and its splits
COMPILE = "setup.compile_s"
KINDS = sorted({run.load_json(os.path.join(REPO, "benchmark", "traffic",
                                           f))["kind"]
                for f in os.listdir(os.path.join(REPO, "benchmark",
                                                 "traffic"))})


def is_compile_metric(name: str) -> bool:
    return name == COMPILE or name.startswith(COMPILE + ".")


def check_contract(spec: dict, root: str = run.ROOT) -> None:
    """Every per-layer metric moves an end-to-end metric that each of
    its cells reports, and has a reader; every cell is listed by
    exactly one compile-seconds entry; at most half the cells (one
    always) ask for four chips."""
    cells = [w["name"] for w in spec["workloads"]]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", cells), m
        assert callable(run.metric_reader(m["name"], root))
    compile_lists = [m["workloads"] for m in spec["per_layer"]
                     if is_compile_metric(m["name"])]
    for cell in cells:
        assert sum(cell in w for w in compile_lists) == 1, cell
    chips = [w["chips"] for w in spec["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)


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
    assert len([n for n in out["metrics"] if is_compile_metric(n)]) == 1
    assert out["correct"] is True


def test_benchmark_exits_nonzero_without_tpu(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_every_metric_moves_a_metric_its_cells_report():
    check_contract(run.benchmark_spec())


@pytest.mark.parametrize("kind", KINDS)
def test_every_kind_declares_its_tiny_sizes(kind):
    """Each traffic kind names the keys the CPU rehearsal cuts, and
    each is a key of every traffic file of that kind."""
    tiny = importlib.import_module(f"benchmark.kinds.{kind}").TINY
    assert isinstance(tiny, dict) and tiny
    traffic = os.path.join(REPO, "benchmark", "traffic")
    for f in os.listdir(traffic):
        t = run.load_json(os.path.join(traffic, f))
        if t["kind"] == kind:
            assert set(tiny) <= set(t), (f, sorted(set(tiny) - set(t)))


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


FOUR = "http-served-four"


def _four_chip_kind() -> types.ModuleType:
    """A traffic kind that exists only for the test: it asserts four
    devices and otherwise drives ``served.run``."""
    mod = types.ModuleType("benchmark.kinds.rehearsal_four")
    mod.TINY = dict(served.TINY)

    def go(env):
        assert len(env.devices) == 4, env.devices
        res = served.run(env)
        res["ctx"]["devices"] = len(env.devices)
        return res

    mod.run = go
    return mod


def test_benchmark_adds_a_four_chip_cell_of_a_new_kind(run_tiny, tiny_root,
                                                       monkeypatch):
    """A later PR adds a four-chip cell of a new traffic kind with new
    files and additions to ``BENCHMARK.json`` only."""
    kind = _four_chip_kind()
    monkeypatch.setitem(sys.modules, "benchmark.kinds.rehearsal_four", kind)
    bench = os.path.join(tiny_root, "BENCHMARK.json")
    spec = run.load_json(bench)
    spec["workloads"].append({"name": FOUR, "config": "http-1k-regex",
                              "traffic": FOUR, "chips": 4,
                              "why": "rehearsal"})
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    e2e["served_p50_ms"]["workloads"].append(FOUR)
    spec["per_layer"] += [
        {"name": COMPILE + ".four", "unit": "s", "better": "lower",
         "source": "program_span", "layer": "loader and compile cache",
         "moves": "setup_s", "workloads": [FOUR]},
        {"name": "rehearsal.chips", "unit": "chips", "better": "higher",
         "source": "program_counter", "layer": "rehearsal",
         "moves": "served_p50_ms", "workloads": [FOUR]}]
    with open(bench, "w") as f:
        json.dump(spec, f)
    served_traffic = run.load_json(os.path.join(
        REPO, "benchmark", "traffic", "http-served-zipf.json"))
    with open(os.path.join(tiny_root, "benchmark", "traffic",
                           f"{FOUR}.json"), "w") as f:
        json.dump({**served_traffic, "kind": "rehearsal_four",
                   **kind.TINY}, f)
    with open(os.path.join(tiny_root, "benchmark", "metrics",
                           "rehearsal.chips.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.get('devices')\n")
    check_contract(spec, tiny_root)

    out, _ = run_tiny(FOUR)
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) == {"served_p50_ms", "setup_s"}
    out, _ = run_tiny(FOUR, trace=True)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {COMPILE + ".four", "rehearsal.chips"}
    assert out["metrics"]["rehearsal.chips"]["value"] == 4


def test_program_counters_sum_the_named_hosts():
    """``Program.counters(hosts)`` adds each named host's series to the
    unlabelled one; with no host named it reads the unlabelled alone."""
    from cilium_tpu.runtime.metrics import (
        METRICS,
        SERVE_PACK_RECORDS,
        SERVE_PACK_STREAMS,
    )

    from benchmark.program import Program

    hosts = ("bench-test-a", "bench-test-b")
    a0, h0 = Program.counters(), Program.counters(hosts)
    for labels, streams in ((None, 1), ({"host": hosts[0]}, 2),
                            ({"host": hosts[1]}, 3),
                            ({"host": "bench-test-c"}, 5)):
        METRICS.observe(SERVE_PACK_RECORDS, 64.0, labels=labels)
        METRICS.observe(SERVE_PACK_STREAMS, float(streams), labels=labels)
    a1, h1 = Program.counters(), Program.counters(hosts)
    assert a1["packs"] - a0["packs"] == 1
    assert a1["pack_streams"] - a0["pack_streams"] == 1
    assert h1["packs"] - h0["packs"] == 3
    assert h1["pack_streams"] - h0["pack_streams"] == 6
    assert set(a1) == set(h1)


SWEEP_FIELDS = ["offered_records_s", "completed_records_s", "chunks",
                "failed", "correct", "p50_ms", "p99_ms", "late_p99_ms",
                "backlog", "compiles_in_window"]


def test_sweep_point_of_the_served_kind(tiny_root, tmp_path, monkeypatch):
    import jax

    from benchmark import program
    from benchmark.meter import CompileMeter

    monkeypatch.setattr(program, "enable_compile_cache", lambda path: None)
    cell, cfg, traffic = run.cell_parts(run.benchmark_spec(tiny_root),
                                        "http-served-zipf", tiny_root)
    meter = CompileMeter()
    try:
        line = sweep.point(served, cell, cfg, traffic, 2000.0, 2**31 + 3,
                           1.0, jax.devices()[:1], meter,
                           cache_dir=str(tmp_path / "cache"))
    finally:
        meter.close()
    assert list(line) == SWEEP_FIELDS
    assert line["offered_records_s"] == 2000.0
    assert line["correct"] is True and line["failed"] == 0


def test_sweep_takes_the_kind_from_the_traffic_and_refuses_a_bare_ctx(
        tiny_root, monkeypatch, capsys):
    """``benchmark.sweep`` runs the kind its traffic file names, and
    exits non-zero with a message where that kind's ctx lacks what a
    rate's line needs."""
    import jax

    from benchmark import program

    ran = []
    bare = types.ModuleType("benchmark.kinds.rehearsal_bare")
    bare.run = lambda env: ran.append(env.traffic) or {
        "e2e": {}, "failed": 0, "checks": {}, "ctx": {"window_s": 1.0}}
    monkeypatch.setitem(sys.modules, "benchmark.kinds.rehearsal_bare", bare)
    cell, cfg, traffic = run.cell_parts(run.benchmark_spec(tiny_root),
                                        "http-served-zipf", tiny_root)
    parts = (cell, cfg, {**traffic, "kind": "rehearsal_bare"})
    monkeypatch.setattr(run, "cell_parts", lambda spec, name: parts)
    monkeypatch.setattr(run, "device_gate",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(program, "enable_compile_cache", lambda path: None)
    rc = sweep.main(["--workload", "http-served-zipf", "--rates", "100"])
    assert rc != 0
    assert [t["rate_records_s"] for t in ran] == [100.0]
    err = capsys.readouterr()
    assert err.out == ""
    assert "latency_s" in err.err and "rehearsal_bare" in err.err
