"""The trace reduction against a small trace recorded on a v5e in
PR 22 (``python3 -m benchmark.record_trace``: three jitted steps, each
followed by 10 ms of host sleep, inside the window span), and the
peaks table."""

from __future__ import annotations

import os

import pytest

from benchmark import peaks, spans, trace

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "small_tpu.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace.Trace(SMALL).reduce()


def test_benchmark_trace_window_and_busy(reduced):
    assert 0.03 < reduced["window_s"] < 0.05
    assert 0 < reduced["busy_s"] < 0.001
    assert reduced["busy_s"] < reduced["window_s"]


def test_benchmark_trace_modules_and_ops(reduced):
    assert set(reduced["module_s"]) == {"jit__lambda"}
    assert reduced["module_s"]["jit__lambda"] == pytest.approx(
        reduced["busy_s"], rel=0.01)
    ops = reduced["device_ops"]
    assert 1 <= len(ops) <= 10
    assert ops == sorted(ops, key=lambda o: o[1], reverse=True)
    assert sum(s for _, s in ops) == pytest.approx(reduced["busy_s"],
                                                   rel=0.01)


def test_benchmark_trace_idle_gaps_named_by_host_span(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) <= 10
    # the three 10 ms host sleeps are the longest gaps
    assert [g[0] for g in gaps[:3]] == ["bench.host"] * 3
    assert all(0.009 < g[1] < 0.02 for g in gaps[:3])


def test_benchmark_trace_union_and_clip():
    assert trace._union([(0, 5), (3, 8), (10, 12)]) == [(0, 8), (10, 12)]
    assert trace._clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]
    assert trace.module_name("jit_verdict_step_capture(42)") == \
        "jit_verdict_step_capture"


def test_benchmark_trace_without_window_reads_nothing(tmp_path):
    assert spans.reduce_dir(str(tmp_path)) is None


def test_benchmark_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
