"""The comparison that decides ``correct``: the program's answers
against the plain reference (``benchmark/reference/``), lane by lane.

The reference resolves the same CNP documents over the same endpoints
with its own copy of the policy code, and verdicts each record with its
own copy of the CPU oracle. It runs after the window has closed, over
the records the window verdicted (every answer of the served cell, a
seeded sample of each replayed segment). Records repeat, so it
memoizes by record: the reference is a pure function of one.

The numbers compared, each with its limit (an exact comparison):

- ``wrong_answers``: records on which some compared lane differs — 0;
- ``missing_answers``: records due in the window whose answer never
  came — 0.

The control (``control=True``) is the reference with one guarantee of
the configuration broken: a redirect entry forwards every request, its
L7 allow-list unenforced. It must read as not correct.
"""

from __future__ import annotations

import types
from typing import Dict, List, Sequence

import numpy as np

from benchmark.worlds import resolve, to_flow

#: the lanes the reference computes
LANES = ("verdict", "auth_required", "l7_log")


def _modules():
    from benchmark.reference import (
        cnp,
        flow,
        identity,
        labels,
        mapstate,
        repository,
        selectorcache,
    )

    return types.SimpleNamespace(
        flow=flow, identity=identity, labels=labels, cnp=cnp,
        selectorcache=selectorcache, repository=repository,
        mapstate=mapstate)


class Reference:
    def __init__(self, docs: List[dict], endpoints: Dict[str, dict],
                 control: bool = False):
        from benchmark.reference.oracle import OracleVerdictEngine

        mods = _modules()
        self.flowmod = mods.flow
        self.per_identity, self.ids = resolve(mods, docs, endpoints)
        self.oracle = OracleVerdictEngine(self.per_identity,
                                          l7_enforced=not control)
        self._memo: Dict[tuple, tuple] = {}

    def lanes(self, recs: Sequence[tuple]) -> Dict[str, np.ndarray]:
        todo = [r for r in dict.fromkeys(recs) if r not in self._memo]
        if todo:
            out = self.oracle.verdict_flows(
                [to_flow(self.flowmod, r, self.ids) for r in todo])
            for j, r in enumerate(todo):
                self._memo[r] = tuple(int(out[k][j]) for k in LANES)
        rows = np.array([self._memo[r] for r in recs],
                        dtype=np.int64).reshape(-1, len(LANES))
        return {k: rows[:, i] for i, k in enumerate(LANES)}


def wrong_answers(got: Dict[str, np.ndarray],
                  want: Dict[str, np.ndarray], lanes) -> int:
    """Records on which any of ``lanes`` differs."""
    bad = np.zeros(len(want[lanes[0]]), dtype=bool)
    for k in lanes:
        bad |= (np.asarray(got[k]).astype(np.int64)
                != np.asarray(want[k]).astype(np.int64))
    return int(bad.sum())


def checks(wrong: int, missing: int, compared: int) -> Dict[str, dict]:
    """The compared numbers with their limits, in print order."""
    return {
        "wrong_answers": {"value": wrong, "limit": 0},
        "missing_answers": {"value": missing, "limit": 0},
        "compared_records": {"value": compared, "limit": 1,
                             "at_least": True},
    }


def is_correct(chk: Dict[str, dict]) -> bool:
    return all((c["value"] >= c["limit"]) if c.get("at_least")
               else (c["value"] <= c["limit"]) for c in chk.values())
