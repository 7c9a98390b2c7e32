"""The comparison catches a broken timed path: each cell's run, with
the chip gate skipped and a fault planted in the program under the
window, comes out not correct; and the control (the reference with L7
unenforced, in the program's place) comes out not correct in every
cell. The chip runs of the control are in PERF.md; this keeps it as a
test at a size the CPU holds."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import run

CELLS = [w["name"] for w in run.benchmark_spec()["workloads"]]
REPLAY = [c for c in CELLS if "replay" in c]
SERVED = [c for c in CELLS if "served" in c]


def _altered(v: np.ndarray) -> np.ndarray:
    v = np.array(v, copy=True)
    v[::16] = np.where(v[::16] == 5, 2, 5)  # REDIRECTED <-> DROPPED
    return v


@pytest.fixture
def replay_fault(monkeypatch):
    """``replay_fault(kind)``: CaptureReplay.verdict_chunk answers
    wrongly (``"altered"``: every 16th verdict flipped; ``"half"``:
    answers for the first half of the chunk only)."""
    from cilium_tpu.engine.verdict import CaptureReplay

    orig = CaptureReplay.verdict_chunk

    def plant(kind):
        def broken(self, rec, l7, *a, **k):
            out = dict(orig(self, rec, l7, *a, **k))
            if kind == "altered":
                out["verdict"] = _altered(out["verdict"])
            else:
                out = {l: np.asarray(v)[:len(rec) // 2]
                       for l, v in out.items()}
            return out

        monkeypatch.setattr(CaptureReplay, "verdict_chunk", broken)

    return plant


@pytest.fixture
def served_fault(monkeypatch):
    """``served_fault(kind)``: the serve loop resolves each chunk's
    ticket with wrong verdicts (as ``replay_fault``)."""
    from cilium_tpu.runtime.serveloop import ChunkTicket

    orig = ChunkTicket.resolve

    def plant(kind):
        def broken(self, verdicts, *a, **k):
            if verdicts is not None:
                verdicts = (_altered(verdicts) if kind == "altered"
                            else np.asarray(verdicts)[:len(verdicts) // 2])
            return orig(self, verdicts, *a, **k)

        monkeypatch.setattr(ChunkTicket, "resolve", broken)

    return plant


@pytest.mark.parametrize("kind", ["altered", "half"])
@pytest.mark.parametrize("cell", REPLAY)
def test_benchmark_replay_fault_is_not_correct(run_tiny, replay_fault,
                                               cell, kind):
    replay_fault(kind)
    out, _ = run_tiny(cell)
    assert out["correct"] is False
    number = "wrong_answers" if kind == "altered" else "missing_answers"
    assert out["checks"][number]["value"] > 0


@pytest.mark.parametrize("kind", ["altered", "half"])
@pytest.mark.parametrize("cell", SERVED)
def test_benchmark_served_fault_is_not_correct(run_tiny, served_fault,
                                               cell, kind):
    served_fault(kind)
    out, _ = run_tiny(cell)
    assert out["correct"] is False
    number = "wrong_answers" if kind == "altered" else "missing_answers"
    assert out["checks"][number]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_benchmark_control_is_not_correct(run_tiny, cell):
    out, _ = run_tiny(cell, control=True)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0
