"""Deterministic, seeded fault injection.

The round-5 sweeps met real transient failures — a connection drop
during a forced recompile, a stream stall escaping as a raw traceback,
a shim serving a stale table (ADVICE.md) — but none were reproducible
on demand. This module makes failure a test
input: named **injection points** sit at the seams where production
failures actually happen (device dispatch, frame delivery, revision
swap, kvstore sessions, the DNS proxy), and a :class:`FaultPlan`
decides, deterministically, which hits of which point raise what.

Design constraints, in order:

* **Zero cost when idle.** ``maybe_fail`` is a module-global ``None``
  check when no plan is installed — the seams stay in production code
  paths, so the disarmed probe must be free.
* **Replayable.** Every decision is drawn from a per-point RNG seeded
  by ``(plan seed, point name)`` and consumed in per-point hit order,
  so the decision sequence at a point is a pure function of the plan —
  independent of thread interleaving ACROSS points. The recorded
  :meth:`FaultPlan.trace` of two runs with the same plan and the same
  per-point hit counts is identical; chaos tests assert exactly that.
* **Plans choose the exception.** A stream-drop plan raises
  ``ConnectionError`` so the reconnect path (not a generic handler)
  absorbs it; a device fault raises :class:`FaultInjected`.

Usage::

    plan = FaultPlan(seed=7, rules=[
        FaultRule("engine.dispatch", times=3),          # first 3 hits
        FaultRule("stream.frame.client", prob=0.1,
                  exc=ConnectionError),                  # 10% of frames
    ])
    with inject(plan):
        ... run the workload ...
    plan.trace()   # {"engine.dispatch": [(0, True), (1, True), ...]}

Known injection points (registered by the modules owning the seam):

=========================  ==================================================
``engine.dispatch``        device dispatch in ``engine/verdict.py``
                           (``verdict_batch_arrays`` / blob step)
``loader.swap``            between stage and commit in ``runtime/loader.py``
``loader.bank_compile``    per-bank DFA compile in
                           ``policy/compiler/bankplan.BankRegistry`` (a
                           fired fault quarantines ONLY that bank; the
                           regeneration proceeds on the old cover)
``kvstore.churn_storm``    per identity-churn event delivery in
                           ``identity_kvstore.ClusterIdentityAllocator``
                           (a fired fault loses that delivery —
                           modelling burst add/delete churn)
``stream.frame.server``    per-chunk dispatch in ``StreamSession``
``stream.frame.client``    per-frame receive in ``StreamClient``
``stream.credit``          credit-grant send in ``StreamSession`` (a
                           fired fault LOSES the grant)
``service.admit``          admission decision in ``runtime/admission.py``
                           (a fired fault forces an explicit shed)
``serve.lease``            slot-lease decision in
                           ``runtime/serveloop.ServeLoop.connect`` (a
                           fired fault is an explicit shed)
``serve.ring_slot``        chunk submit into a ring slot in
                           ``ServeLoop.submit`` (a fired fault fails
                           only that chunk)
``service.drain``          between stop-admitting and the pending
                           flush in ``VerdictService.drain``
``kvstore.watch``          per-watch event delivery in ``kvstore.py``
``clustermesh.session``    remote-cluster event ingest in ``clustermesh.py``
``clustermesh.heartbeat``  local-state publisher heartbeat
``dnsproxy.query``         banked-DFA batch path in ``fqdn/dnsproxy.py``
``fleet.heartbeat``        per-host heartbeat in ``runtime/fleetserve.py``
                           (a fired fault LOSES the beat — enough lost
                           beats push the host through suspicion into
                           fail-closed death)
``fleet.handoff``          per-stream lease migration in the fleet
                           router's host-death handoff (a fired fault
                           interrupts the transfer mid-batch; the
                           unmigrated remainder re-grants through the
                           client resume path, never on two live hosts)
``artifact.fetch``         compiled-bank artifact fetch in
                           ``runtime/checkpoint.BankArtifactStore``
``canary.dispatch``        shadow (N+1) verdict dispatch in
                           ``runtime/canary.CanaryController`` (a fired
                           fault ABORTS the canary safely — staged
                           generation dropped, serving generation N
                           untouched)
``tenant.quota``           per-tenant quota-store read in
                           ``runtime/tenant.TenantQuotas`` (a fired
                           fault falls back to the conservative
                           configured default share)
=========================  ==================================================
"""

from __future__ import annotations

import contextlib
import os
import random
import threading
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

from cilium_tpu.runtime.metrics import FAULTS_INJECTED, METRICS


class FaultInjected(Exception):
    """Default exception raised at an armed injection point."""


class FaultRule:
    """One point's failure policy.

    ``prob``  — per-hit fire probability (1.0 = every eligible hit).
    ``times`` — max fires (None = unbounded); after that the point is
                permanently healthy, which is how chaos tests model
                "the outage ends".
    ``after`` — skip the first N hits (fault appears mid-run).
    ``exc``   — exception *class* to raise (``FaultInjected`` default);
                instantiated with ``message`` per fire so tracebacks
                carry the point name.
    """

    def __init__(self, point: str, prob: float = 1.0,
                 times: Optional[int] = None, after: int = 0,
                 exc: type = FaultInjected,
                 message: Optional[str] = None):
        if not 0.0 <= prob <= 1.0:
            raise ValueError(f"prob must be in [0, 1], got {prob}")
        self.point = point
        self.prob = prob
        self.times = times
        self.after = after
        self.exc = exc
        self.message = message or f"injected fault at {point}"


class _PointState:
    """Per-point mutable state: its own RNG, counters, and trace."""

    __slots__ = ("rule", "rng", "hits", "fires", "trace", "lock")

    def __init__(self, rule: FaultRule, seed: int):
        self.rule = rule
        # crc32 folds the point name into the seed so two points under
        # one plan draw independent, order-free decision streams
        self.rng = random.Random(
            (seed << 32) ^ zlib.crc32(rule.point.encode()))
        self.hits = 0
        self.fires = 0
        self.trace: List[Tuple[int, bool]] = []
        self.lock = threading.Lock()

    def decide(self) -> Optional[Exception]:
        with self.lock:
            idx = self.hits
            self.hits += 1
            # the RNG is consumed on EVERY hit (fired or not) so the
            # decision at hit k never depends on times/after gating
            draw = self.rng.random()
            fire = (idx >= self.rule.after
                    and (self.rule.times is None
                         or self.fires < self.rule.times)
                    and draw < self.rule.prob)
            if fire:
                self.fires += 1
            self.trace.append((idx, fire))
        if not fire:
            return None
        return self.rule.exc(f"{self.rule.message} (hit {idx})")


class FaultPlan:
    """A seeded set of :class:`FaultRule`\\ s plus the recorded trace."""

    def __init__(self, rules: Sequence[FaultRule] = (), seed: int = 0):
        self.seed = seed
        self._points: Dict[str, _PointState] = {}
        for r in rules:
            if r.point in self._points:
                raise ValueError(f"duplicate rule for point {r.point!r}")
            self._points[r.point] = _PointState(r, seed)

    def check(self, point: str) -> Optional[Exception]:
        st = self._points.get(point)
        return st.decide() if st is not None else None

    def trace(self) -> Dict[str, List[Tuple[int, bool]]]:
        """point → [(hit index, fired)] — the replayable event trace."""
        return {p: list(st.trace) for p, st in self._points.items()}

    def counts(self, point: str) -> Tuple[int, int]:
        """(hits, fires) for one point (0, 0 if never hit/ruled)."""
        st = self._points.get(point)
        return (st.hits, st.fires) if st is not None else (0, 0)


#: the armed plan; ``None`` (the default, and the production state)
#: makes every ``maybe_fail`` a single global read
_PLAN: Optional[FaultPlan] = None
_PLAN_LOCK = threading.Lock()

#: advisory registry of seams that call ``maybe_fail`` (introspection /
#: docs; unknown points still work — the registry is not a gate)
_POINTS: Dict[str, str] = {}


def register_point(name: str, doc: str = "") -> str:
    """Declare an injection point (module import time). Returns the
    name so seams can do ``POINT = register_point(...)``."""
    # ctlint: disable=unbounded-registry  # import-time registration, bounded by module count
    _POINTS.setdefault(name, doc)
    return name


def registered_points() -> Dict[str, str]:
    return dict(_POINTS)


def install(plan: FaultPlan) -> None:
    global _PLAN
    with _PLAN_LOCK:
        if _PLAN is not None:
            raise RuntimeError("a FaultPlan is already installed")
        _PLAN = plan


def clear() -> None:
    global _PLAN
    with _PLAN_LOCK:
        _PLAN = None


def active() -> Optional[FaultPlan]:
    return _PLAN


@contextlib.contextmanager
def inject(plan: FaultPlan):
    """``with inject(plan): ...`` — install for the block, always
    cleared on exit (a leaked plan would fail unrelated tests)."""
    install(plan)
    try:
        yield plan
    finally:
        clear()


#: DST mutation testing (runtime/dst.py): ``CILIUM_TPU_DST_MUTATION``
#: names a known FIXED bug to re-introduce, so the schedule search can
#: prove it would have caught the bug. Off (empty) in production; the
#: env var is read per call so tests toggle it with monkeypatch.
MUTATION_ENV = "CILIUM_TPU_DST_MUTATION"

#: mutation name → where the planted bug lives (introspection/docs)
MUTATIONS: Dict[str, str] = {
    "rollback-artifact-key":
        "Loader.regenerate rollback keeps _last_artifact_key at the "
        "aborted revision (the PR-7 warm-snapshot staleness bug)",
    "positional-banks":
        "bankplan.partition_patterns groups positionally — one delete "
        "shifts every later bank (the pre-PR-8 O(policy) compile bug)",
}


def mutation_active(name: str) -> bool:
    """True when the named planted bug is armed. The seams guard their
    buggy variant with this, so shipped behavior is untouched unless
    the DST validation lane arms the mutation explicitly."""
    return os.environ.get(MUTATION_ENV, "") == name


def maybe_fail(point: str) -> None:
    """The seam probe. Raises the plan's exception when the armed plan
    says this hit of ``point`` fails; otherwise (or with no plan) does
    nothing. Seams call this unconditionally — disarmed cost is one
    global read."""
    plan = _PLAN
    if plan is None:
        return
    exc = plan.check(point)
    if exc is not None:
        METRICS.inc(FAULTS_INJECTED, labels={"point": point})
        # a fired fault under an active flight-recorder trace becomes
        # a span event — the trace shows WHICH request the fault hit
        # (import here: the disarmed path must stay one global read)
        from cilium_tpu.runtime.tracing import TRACER

        TRACER.event("fault.injected", point=point,
                     exc=type(exc).__name__)
        raise exc
