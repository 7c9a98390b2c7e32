"""The program spans as the benchmark reads them (``benchmark/spans.py``)
against a small chip trace recorded on a v5e
(``python3 -m benchmark.record_spans_trace``: program spans on two
threads, ``.wait`` spans and a harness span between device steps), the
five per-layer readers, and the tiny cells run traced on the CPU."""

from __future__ import annotations

import os
import shutil
from collections import Counter

import pytest

from benchmark import run, spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "small_tpu.xplane.pb")
SPANS = os.path.join(HERE, "small_tpu_spans.xplane.pb")
P = spans.PREFIX

#: each new metric → its computation over a span list and a window
METRICS = {
    "stream.host_p50_ms": lambda s, w: spans.host_p50_ms(s),
    "serve.ring_wait_p50_ms": lambda s, w: spans.ring_wait_p50_ms(s),
    "serve.cycle_gap_p99_ms": lambda s, w: spans.cycle_gap_p99_ms(s),
    "serve.host_syncs_per_chunk":
        lambda s, w: spans.host_syncs_per_chunk(s),
    "replay.device_wait_pct": spans.device_wait_pct,
}


@pytest.fixture(scope="module")
def st():
    return spans.SpanTrace(SPANS)


def _named(st, name):
    return sorted((s for s in st.program if s.name == P + name),
                  key=lambda s: s.start)


def test_spans_collected_by_host_line(st):
    names = Counter(s.name for s in st.program)
    assert names[P + "capture.verdict"] == 6
    assert names[P + "capture.readback.wait"] == 6
    assert names[P + "stream.chunk"] == 4
    assert names[P + "serve.ticket.wait"] == 6
    assert names[P + "serve.cycle"] >= 2
    assert names[P + "serve.readback.wait"] == names[P + "serve.cycle"] - 1
    # the pack-shaped thread and the main thread write different lines
    main = {s.line for s in _named(st, "capture.verdict")}
    pack = {s.line for s in _named(st, "serve.sleep.wait")}
    assert len(main) == 1 and pack and not main & pack
    # a readback wait nests in its verdict span, on the same line
    for w in _named(st, "capture.readback.wait"):
        assert any(v.line == w.line and v.start <= w.start < w.end <= v.end
                   for v in _named(st, "capture.verdict"))
    # harness spans stay harness spans
    assert {n for n, _, _ in st.spans} == {trace.WINDOW,
                                          trace.PREFIX + "host"}


@pytest.mark.parametrize("path", [SMALL, SPANS])
def test_span_trace_keeps_the_reduction(path):
    want = trace.Trace(path).reduce()
    got = spans.SpanTrace(path).reduce()
    for key in ("busy_s", "window_s", "module_s", "device_ops"):
        assert got[key] == want[key], key


@pytest.mark.parametrize("path", [SMALL, SPANS])
def test_traced_window_reduction_names_gaps_by_program_spans(tmp_path, path):
    """What a traced window reduces its trace directory with
    (``spans.reduce_dir``): the harness reduction's numbers, identical,
    with the same idle gaps named by the program's spans."""
    shutil.copy(path, tmp_path / "t.xplane.pb")
    want = trace.Trace(path).reduce()
    got = spans.reduce_dir(str(tmp_path))
    for key in ("busy_s", "window_s", "module_s", "device_ops"):
        assert got[key] == want[key], key
    assert [g[1] for g in got["idle_gaps"]] == [g[1] for g in
                                               want["idle_gaps"]]
    names = [g[0] for g in got["idle_gaps"]]
    if path == SPANS:
        assert any(n.startswith(P) for n in names), names
    else:
        assert names[:3] == [trace.PREFIX + "host"] * 3


def test_idle_gaps_named_by_tier(st):
    """Between successive device steps: nothing (idle), the harness
    span alone, a lone ``.wait``, a working span on another thread
    over a ``.wait`` and a harness span, then the chunks."""
    verdicts = _named(st, "capture.verdict")
    between = [(a.end, b.start) for a, b in zip(verdicts, verdicts[1:])]
    assert [st.host_activity(lo, hi) for lo, hi in between] == [
        spans.IDLE, trace.PREFIX + "host", P + "serve.ticket.wait",
        P + "serve.cycle", P + "stream.chunk"]
    gaps = st.reduce()["idle_gaps"]
    assert {g[0] for g in gaps[:5]} == {
        spans.IDLE, trace.PREFIX + "host", P + "serve.ticket.wait",
        P + "serve.cycle", P + "stream.chunk"}


def test_idle_gap_within_a_tier_most_cover_then_shortest():
    S = spans.Span
    tier = [S("a", 0, 100, 1), S("b", 40, 60, 2), S("c", 30, 70, 3)]
    assert spans._best(tier, 0, 100) == "a"     # most cover
    assert spans._best(tier, 45, 55) == "b"     # tie: the shortest
    assert spans._best(tier, 100, 200) is None  # no cover
    # an edge that covers under half names nothing; one name's spans
    # on two lines cover as their union
    edge = [S("step", 0, 10, 1), S("step", 95, 100, 1)]
    assert spans._best(edge, 5, 100) is None
    split = [S("chunk", 0, 30, 1), S("chunk", 25, 60, 2),
             S("cycle", 0, 45, 3)]
    assert spans._best(split, 0, 100) == "chunk"


def test_span_metrics_on_the_chip_trace(st):
    m = st.metrics()
    # 5 ms of host work in each chunk, around a 10 ms ticket wait
    assert 4.9 < m["stream.host_p50_ms"] < 7
    assert 9.9 < m["serve.ring_wait_p50_ms"] < 15
    # the 60 ms cycle on the pack-shaped line is the slowest gap
    assert m["serve.cycle_gap_p99_ms"] > 50
    cycles = len(_named(st, "serve.cycle"))
    assert m["serve.host_syncs_per_chunk"] == (cycles - 1) / 4
    win = st.window()
    waits = sum(s.end - s.start for s in st.program
                if s.name.startswith(P + "capture.")
                and s.name.endswith(".wait"))
    assert m["replay.device_wait_pct"] == pytest.approx(
        100 * waits / (win[1] - win[0]))


def test_span_coverage_by_line(st):
    win = st.window()
    cov = spans.coverage(st.program, *win)
    main = _named(st, "capture.verdict")[0].line
    assert 0.6 < cov[main] <= 1.0
    assert all(0 < v <= 1.0 for v in cov.values())


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_reads_the_program_spans(monkeypatch, st, name):
    win = st.window()
    window_s = (win[1] - win[0]) / 1e9
    monkeypatch.setattr(spans, "program_spans", lambda: st.program)
    got = run.metric_reader(name)({"window_s": window_s})
    assert got is not None
    assert got == METRICS[name](st.program, window_s)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_is_silent_without_the_program_log(monkeypatch, name):
    """A program that keeps no span log (the parent of this metric)
    reads None, and does not raise."""
    from cilium_tpu.runtime import tracing

    monkeypatch.delattr(tracing, "profiled_spans")
    assert spans.program_spans() is None
    assert run.metric_reader(name)({"window_s": 1.0}) is None


@pytest.mark.parametrize("cell,names", [
    ("http-served-zipf", ["stream.host_p50_ms", "serve.ring_wait_p50_ms",
                          "serve.cycle_gap_p99_ms",
                          "serve.host_syncs_per_chunk"]),
    ("http-replay-fresh", ["replay.device_wait_pct"]),
])
def test_traced_tiny_run_reports_the_span_metrics(run_tiny, cell, names):
    from cilium_tpu.runtime.tracing import clear_profiled_spans

    clear_profiled_spans()
    try:
        out, _ = run_tiny(cell, trace=True)
    finally:
        spans_seen = spans.program_spans()
        clear_profiled_spans()
    assert out["correct"] is True
    for name in names:
        assert out["metrics"][name]["value"] > 0, name
    if "serve.host_syncs_per_chunk" in names:
        # one blocking readback a chunk, on the pack thread
        assert out["metrics"]["serve.host_syncs_per_chunk"]["value"] == 1.0
    threads = {s.line for s in spans_seen}
    assert len(threads) >= 2 if cell == "http-served-zipf" else threads
