"""The fleet cell's own checks on the CPU: broken fleet books make the
run not correct, and its two per-layer readers compute what their
docstrings say."""

from __future__ import annotations

import pytest

from benchmark import run, spans

FLEET = "http-fleet4-served"


@pytest.mark.parametrize("finding", ["conservation", "journal"])
def test_fleet_violation_fails_every_chunk(run_tiny, monkeypatch, finding):
    from cilium_tpu.runtime.fleetserve import FleetRouter

    if finding == "conservation":
        monkeypatch.setattr(FleetRouter, "conservation_violation",
                            lambda self: ("conn-0", "host-0", "host-1"))
    else:
        monkeypatch.setattr(FleetRouter, "journal_consistent",
                            lambda self: "host host-0: journal diverged")
    out, lines = run_tiny(FLEET)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] > 0
    assert out["checks"]["fleet_violations"]["value"] == 1
    assert out["checks"]["wrong_answers"]["value"] == 0
    assert any("fleet books broken" in line for line in lines)


@pytest.mark.parametrize("routed,want", [
    ({"host-0": 7, "host-1": 9, "host-2": 8, "host-3": 8}, 1.125),
    ({"host-0": 5, "host-1": 5}, 1.0),
    ({"host-0": 12, "host-1": 0, "host-2": 0, "host-3": 0}, 4.0),
    ({"host-0": 0, "host-1": 0}, None),
    ({}, None),
])
def test_route_skew_is_the_largest_host_over_the_mean(routed, want):
    read = run.metric_reader("fleet.route_skew")
    assert read({"routed": routed}) == want


def _cycle(start, end, line):
    return spans.Span(spans.CYCLE, start, end, line)


@pytest.mark.parametrize("cycles,want", [
    # one thread, or threads that take turns: no overlap
    ([_cycle(0, 10, 1), _cycle(20, 30, 1)], 1.0),
    ([_cycle(0, 10, 1), _cycle(10, 20, 2), _cycle(20, 30, 3)], 1.0),
    # four threads' cycles over the same interval
    ([_cycle(0, 10, k) for k in range(4)], 4.0),
    # two at once half the time: 30 ns of cycles over a 20 ns union
    ([_cycle(0, 20, 1), _cycle(5, 15, 2)], 1.5),
    ([], None),
])
def test_pack_overlap_is_cycle_time_over_its_union(monkeypatch, cycles,
                                                   want):
    other = spans.Span(spans.PREFIX + "serve.sleep.wait", 0, 100, 9)
    monkeypatch.setattr(spans, "program_spans",
                        lambda: (cycles + [other]) or None)
    read = run.metric_reader("fleet.pack_overlap")
    assert read({}) == want
