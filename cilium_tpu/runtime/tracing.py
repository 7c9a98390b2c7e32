"""Request-scoped tracing: a sampled, always-on flight recorder.

The verdict path spans many seams — stream frame → MicroBatcher queue
→ ResilientVerdictor → engine dispatch (or oracle fallback) → ack —
and the degraded modes from the fault-injection layer (breaker trips,
reconnect-with-resume, loader rollback) were visible only as aggregate
counters. When a tail-latency regression appears, counters cannot say
*which phase* of *which request* paid. This module can: every ingress
(service op, stream chunk, CLI replay, DNS batch) draws a trace id,
the context rides a contextvar through the layers, and each layer
records **phase-attributed spans** into a bounded ring buffer:

=================  =====================================================
``queue-wait``     enqueue → drain pickup (MicroBatcher, stream queues)
``host-prep``      featurize/encode/pack on the host
``device-dispatch``  device transfer + jitted step + readback
``oracle-fallback``  the CPU oracle lane (breaker open, or gate off)
=================  =====================================================

Phase spans are LEAF and non-overlapping by construction, so a single
request's phase durations sum to (within scheduler noise) its measured
end-to-end latency — the property the round-5 regression hunt lacked.

Three export faces (one id joins all three):

* ``GET /v1/trace`` on the REST API (``runtime/api.py``);
* ``cilium-tpu trace dump`` / ``replay --trace-out`` emitting Chrome
  trace-event JSON (Perfetto-loadable, the same family as the
  ``jax.profiler`` device traces);
* the trace id is stamped on Hubble flow records
  (``hubble/observer.py``) and on JSONL log lines
  (``runtime/logging.py``), so metrics, flows, and logs correlate.

Every span has a second sink: the JAX profiler. ``TRACER.span(...)``
and the context-free :func:`span` (code that serves no one request:
the pack thread, a replay's verdict chunk) open
``cilium_tpu.<layer>.<what>`` on the calling thread's host line
whenever a profiler session is on, sampled or not, so a chip trace
shows which layer the host was in while the device idled. The
profiler names every Python thread's line ``python``, so each span
name says whose work it is (``docs/OBSERVABILITY.md``, span catalog),
and a span that blocks (a lock, a readback, a sleep) ends in
``.wait``. While a session is on, the spans are also kept, on the
host's ``perf_counter`` clock, for readers in the same process
(:func:`profiled_spans`). :func:`host_sync` spans one blocking
device→host read and counts it on
``cilium_tpu_engine_host_syncs_total{site}``.

Design constraints, in order:

* **Near-zero cost disarmed.** With no sampled context and no
  profiler session, a span is one contextvar get, one object and one
  ``TraceMe.is_enabled()`` call (well under 1 µs). Nothing here runs
  per flow; instrumentation is per request/batch/chunk. The module
  never imports jax itself: with jax not loaded, no session is on.
* **Bounded.** Completed spans land in a ``deque(maxlen=capacity)``;
  a long-running agent's recorder memory is a constant.
* **Batch-safe.** A MicroBatcher flush serves many requests at once;
  :meth:`Tracer.group` fans one measured span out to every sampled
  member context so each trace stays self-contained.

Wire propagation: the stream protocol (``runtime/stream.py``) gained
an optional TRACED frame kind whose payload prefixes the 16-hex-char
trace id; servers advertise ``"trace": true`` in the stream_start ack
and clients only send traced frames to peers that do — old peers on
either side are unaffected.
"""

from __future__ import annotations

import contextvars
import sys
import threading
import time
import uuid
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

from cilium_tpu.runtime import simclock
from cilium_tpu.runtime.metrics import (
    ENGINE_HOST_SYNCS,
    METRICS,
    TRACE_SPANS,
)

#: canonical phase names (ISSUE 2); free-form phases are allowed but
#: these four are what the attribution tooling groups by
PHASE_QUEUE = "queue-wait"
PHASE_HOST = "host-prep"
PHASE_DEVICE = "device-dispatch"
PHASE_FALLBACK = "oracle-fallback"
#: the request never reached the engine: shed at admission, or reaped
#: from the queue after its caller abandoned / its deadline expired
PHASE_SHED = "shed"
PHASES = (PHASE_QUEUE, PHASE_HOST, PHASE_DEVICE, PHASE_FALLBACK,
          PHASE_SHED)

#: trace ids on the wire are exactly this many ascii hex chars
TRACE_ID_CHARS = 16

_CURRENT: "contextvars.ContextVar[Optional[TraceContext]]" = \
    contextvars.ContextVar("cilium_tpu_trace", default=None)


def new_trace_id() -> str:
    return uuid.uuid4().hex[:TRACE_ID_CHARS]


class TraceContext:
    """One sampled request's identity: the trace id plus a span-id
    counter. The context object itself is what rides the contextvar
    (and thread handoffs, explicitly) — spans land in the tracer's
    ring, not here, so contexts are cheap to drop."""

    __slots__ = ("trace_id", "name", "t0", "attrs", "_next_span",
                 "epoch")

    def __init__(self, trace_id: str, name: str,
                 attrs: Optional[Dict] = None, epoch: int = 0):
        self.trace_id = trace_id
        self.name = name
        self.t0 = simclock.wall()
        self.attrs = attrs or {}
        #: causal epoch for cross-host stitching (ISSUE 17): 0 on the
        #: original host; each lease handoff bumps it, so a stitched
        #: timeline orders by (epoch, ts) even when the survivor's
        #: clock reads earlier than the dead host's last span
        self.epoch = int(epoch)
        self._next_span = [0]  # list: shared mutable counter, no lock
        # (span ids only need uniqueness per trace; a rare duplicate
        # under a race costs nothing — ids are for display grouping)

    def next_span_id(self) -> int:
        sid = self._next_span[0]
        self._next_span[0] = sid + 1
        return sid

    def members(self) -> Tuple["TraceContext", ...]:
        return (self,)


class GroupContext:
    """A batch's worth of contexts: one measured span fans out to
    every member (a MicroBatcher flush serves many requests; each
    request's trace must still show the batch's device phase)."""

    __slots__ = ("_members",)

    def __init__(self, members: Sequence[TraceContext]):
        self._members = tuple(members)

    @property
    def trace_id(self) -> str:
        # ambiguous on purpose: a group is not ONE trace. Log lines
        # and flow stamps use the first member so they stay joinable.
        return self._members[0].trace_id if self._members else ""

    def members(self) -> Tuple[TraceContext, ...]:
        return self._members

    def next_span_id(self) -> int:  # pragma: no cover - via members
        return 0


#: profiler span names are ``SPAN_PREFIX + <layer>.<what>``
SPAN_PREFIX = "cilium_tpu."
#: the suffix of a span that blocks (lock, readback, sleep)
WAIT_SUFFIX = ".wait"
#: bound on the profiled-span log (an entry holds ~150 bytes)
PROFILED_CAPACITY = 1 << 19

#: ``(name, start_ns, end_ns, thread ident)`` of every span closed
#: while a profiler session was on, on the ``perf_counter_ns`` clock;
#: process-global, like the profiler session it mirrors
_PROFILED: deque = deque(maxlen=PROFILED_CAPACITY)
#: ``jax.profiler.TraceAnnotation`` once jax is loaded
_ANNOTATION = None


def _annotation():
    """The profiler's annotation class while a session is on, else
    None. Looked up in ``sys.modules``: a process that never loaded
    jax has no session to write to."""
    global _ANNOTATION
    cls = _ANNOTATION
    if cls is None:
        prof = sys.modules.get("jax.profiler")
        if prof is None:
            return None
        cls = _ANNOTATION = prof.TraceAnnotation
    return cls if cls.is_enabled() else None


class _ProfileSpan:
    """``cilium_tpu.<name>`` on the profiler's host line of the calling
    thread, and in the profiled-span log; nothing with no session on.
    The context-free span (:func:`span`), and the profiler half of
    every recorder span."""

    __slots__ = ("name", "_tm", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        cls = _annotation()
        self._tm = None if cls is None else cls(SPAN_PREFIX + self.name)
        if self._tm is not None:
            self._tm.__enter__()
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        tm = self._tm
        if tm is not None:
            t1 = time.perf_counter_ns()
            tm.__exit__(*exc)
            _PROFILED.append((SPAN_PREFIX + self.name, self._t0, t1,
                              threading.get_ident()))
        return False


class _SpanCM(_ProfileSpan):
    """A recorder span under a sampled context: the profiler span plus
    one ring record per member trace."""

    __slots__ = ("tracer", "ctx", "phase", "attrs", "t0")

    def __init__(self, tracer, ctx, name, phase, attrs):
        super().__init__(name)
        self.tracer = tracer
        self.ctx = ctx
        self.phase = phase
        self.attrs = attrs

    def __enter__(self):
        super().__enter__()
        self.t0 = simclock.wall()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = simclock.wall() - self.t0
        if exc is not None:
            self.attrs = dict(self.attrs,
                              error=f"{exc_type.__name__}: {exc}")
        self.tracer._record(self.ctx, self.name, self.phase,
                            self.t0, dur, self.attrs)
        return super().__exit__(exc_type, exc, tb)


def span(name: str) -> _ProfileSpan:
    """A program span with no request behind it: the profiler and the
    profiled-span log only, never the recorder ring. ``name`` is
    ``<layer>.<what>``; a blocking wait ends in ``.wait``."""
    return _ProfileSpan(name)


def host_sync(site: str) -> _ProfileSpan:
    """Span ``<site>.wait`` around one blocking device→host read, and
    count it on ``cilium_tpu_engine_host_syncs_total{site}``."""
    METRICS.inc(ENGINE_HOST_SYNCS, labels={"site": site})
    return _ProfileSpan(site + WAIT_SUFFIX)


def profiled_spans() -> List[Tuple[str, int, int, int]]:
    """``(name, start_ns, end_ns, thread ident)`` of the spans closed
    while a profiler session was on, oldest first (``perf_counter_ns``
    clock; at most :data:`PROFILED_CAPACITY`, the newest kept)."""
    return list(_PROFILED)


def clear_profiled_spans() -> None:
    _PROFILED.clear()


class Tracer:
    """The flight recorder. One process-global instance (:data:`TRACER`)
    mirrors the metrics registry discipline; tests build their own."""

    def __init__(self, capacity: int = 4096, sample_rate: float = 1.0,
                 enabled: bool = True):
        self.enabled = bool(enabled)
        self.sample_rate = float(sample_rate)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(capacity)))
        #: monotone sampling counter: rate r admits every ceil(1/r)-th
        #: ingress — deterministic (tests, chaos replays) and fair
        #: under bursts, unlike a per-ingress coin flip
        self._ingress = 0
        self.dropped = 0  # records evicted by the ring bound
        #: span ids for by-id (contextvar-less) remote records
        self._remote_span = 1 << 20

    # -- configuration ----------------------------------------------------
    def configure(self, enabled: Optional[bool] = None,
                  sample_rate: Optional[float] = None,
                  capacity: Optional[int] = None) -> None:
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if sample_rate is not None:
                self.sample_rate = min(1.0, max(0.0, float(sample_rate)))
            if capacity is not None and \
                    int(capacity) != self._ring.maxlen:
                self._ring = deque(self._ring,
                                   maxlen=max(1, int(capacity)))

    # -- trace lifecycle --------------------------------------------------
    def start(self, name: str, trace_id: Optional[str] = None,
              **attrs) -> Optional[TraceContext]:
        """Sampling decision + context creation. ``trace_id`` adopts a
        propagated id (stream server side: the CLIENT already sampled,
        so adoption bypasses the local sampler). Returns ``None`` when
        not sampled — every downstream call no-ops on None."""
        if not self.enabled:
            return None
        if trace_id is None:
            rate = self.sample_rate
            if rate <= 0.0:
                return None
            if rate < 1.0:
                with self._lock:
                    n = self._ingress
                    self._ingress = n + 1
                if (n % max(1, round(1.0 / rate))) != 0:
                    return None
            trace_id = new_trace_id()
        return TraceContext(trace_id, name, attrs or None)

    def activate(self, ctx) -> "_Activation":
        """``with TRACER.activate(ctx): ...`` — contextvar scope (no-op
        for None, so callers never branch)."""
        return _Activation(ctx)

    def trace(self, name: str, trace_id: Optional[str] = None,
              **attrs) -> "_RootTrace":
        """start + activate + a root span recorded on exit — the one
        ingress-side call: ``with TRACER.trace("service.check") as ctx``."""
        return _RootTrace(self, name, trace_id, attrs)

    def finish(self, ctx) -> None:
        """Record the root (end-to-end) span for a started context."""
        if ctx is None:
            return
        for m in ctx.members():
            self._record(m, m.name, "", m.t0, simclock.wall() - m.t0,
                         dict(m.attrs, root=True))

    @staticmethod
    def current() -> Optional[TraceContext]:
        return _CURRENT.get()

    @staticmethod
    def current_trace_id() -> str:
        ctx = _CURRENT.get()
        return ctx.trace_id if ctx is not None else ""

    def group(self, ctxs: Sequence[Optional[TraceContext]]):
        """Collapse a batch's member contexts: None when none are
        sampled, the single member, or a :class:`GroupContext`."""
        live = [c for c in ctxs if c is not None]
        if not live:
            return None
        if len(live) == 1:
            return live[0]
        return GroupContext(live)

    # -- recording --------------------------------------------------------
    def span(self, name: str, phase: str = "", ctx=None, **attrs):
        """Measured span context manager: the profiler span always
        (:func:`span`), a ring record per member only when a sampled
        trace is active."""
        ctx = ctx if ctx is not None else _CURRENT.get()
        if ctx is None or not self.enabled:
            return _ProfileSpan(name)
        return _SpanCM(self, ctx, name, phase, attrs)

    def add_span(self, ctx, name: str, phase: str,
                 t0: float, dur: float, **attrs) -> None:
        """Record a span with explicit timing — for durations measured
        elsewhere (queue-wait from enqueue stamps, writer-thread
        readbacks)."""
        if ctx is None or not self.enabled:
            return
        self._record(ctx, name, phase, t0, dur, attrs)

    def event(self, name: str, ctx=None, **attrs) -> None:
        """Point-in-time annotation (breaker trip, injected fault,
        loader rollback) attached to the active trace."""
        ctx = ctx if ctx is not None else _CURRENT.get()
        if ctx is None or not self.enabled:
            return
        now = simclock.wall()
        recs = [{"trace_id": m.trace_id, "span_id": m.next_span_id(),
                 "name": name, "event": True, "ts": round(now, 6),
                 "attrs": attrs} for m in ctx.members()]
        with self._lock:
            self._note_evictions(len(recs))
            self._ring.extend(recs)

    def _record(self, ctx, name, phase, t0, dur, attrs) -> None:
        recs = [{"trace_id": m.trace_id, "span_id": m.next_span_id(),
                 "name": name, "phase": phase, "ts": round(t0, 6),
                 "dur": round(max(0.0, dur), 9),
                 **({"epoch": m.epoch}
                    if getattr(m, "epoch", 0) else {}),
                 **({"attrs": attrs} if attrs else {})}
                for m in ctx.members()]
        with self._lock:
            self._note_evictions(len(recs))
            self._ring.extend(recs)
        METRICS.inc(TRACE_SPANS, len(recs),
                    labels={"phase": phase or "root"})

    # -- cross-host stitching (ISSUE 17) ----------------------------------
    def _append_remote(self, rec: Dict, phase: str) -> None:
        with self._lock:
            rec["span_id"] = self._remote_span
            self._remote_span += 1
            self._note_evictions(1)
            self._ring.append(rec)
        METRICS.inc(TRACE_SPANS, labels={"phase": phase or "root"})

    def record_remote(self, trace_id: str, name: str, phase: str = "",
                      t0: Optional[float] = None, dur: float = 0.0,
                      host: str = "", epoch: int = 0,
                      parent: Optional[int] = None, **attrs) -> None:
        """Append a span to a trace BY ID — for code that holds no
        contextvar for the trace: the pack thread resolving another
        stream's ticket, or the router minting handoff spans for a
        dead host's streams. ``host``/``epoch``/``parent`` land as
        record keys only when set, so pre-fleet record shapes are
        unchanged."""
        if not self.enabled or not trace_id:
            return
        ts = simclock.wall() if t0 is None else t0
        rec: Dict = {"trace_id": trace_id, "name": name,
                     "phase": phase, "ts": round(ts, 6),
                     "dur": round(max(0.0, dur), 9)}
        if host:
            rec["host"] = host
        if epoch:
            rec["epoch"] = int(epoch)
        if parent is not None:
            rec["parent"] = int(parent)
        if attrs:
            rec["attrs"] = attrs
        self._append_remote(rec, phase)

    def event_remote(self, trace_id: str, name: str, host: str = "",
                     epoch: int = 0, **attrs) -> None:
        """Point-in-time annotation appended BY trace id (the
        handoff/abandon markers that stitch a failover timeline)."""
        if not self.enabled or not trace_id:
            return
        rec: Dict = {"trace_id": trace_id, "name": name,
                     "event": True, "ts": round(simclock.wall(), 6)}
        if host:
            rec["host"] = host
        if epoch:
            rec["epoch"] = int(epoch)
        if attrs:
            rec["attrs"] = attrs
        self._append_remote(rec, "")

    def stitch(self, trace_id: str) -> Dict:
        """One stream's causally-ordered cross-host timeline: every
        record for the trace, ordered by (causal epoch, timestamp) —
        NOT timestamp alone, because a survivor's span can carry an
        earlier wall reading than the dead host's last span — plus
        the distinct hosts that contributed and whether the timeline
        actually crossed a handoff (``stitched``)."""
        recs = self.dump(trace_id=trace_id)
        recs.sort(key=lambda r: (r.get("epoch", 0), r["ts"]))
        hosts: List[str] = []
        for r in recs:
            h = r.get("host") or (r.get("attrs") or {}).get("host")
            if h and h not in hosts:
                hosts.append(h)
        epochs = sorted({int(r.get("epoch", 0)) for r in recs})
        handoff = any(r.get("event") and r["name"] == "fleet.handoff"
                      for r in recs)
        return {
            "trace_id": trace_id,
            "records": recs,
            "hosts": hosts,
            "epochs": epochs,
            "stitched": bool(handoff or len(hosts) > 1
                             or any(epochs[1:])
                             or (epochs and epochs[0] > 0)),
        }

    def _note_evictions(self, incoming: int) -> None:
        room = self._ring.maxlen - len(self._ring)
        if incoming > room:
            self.dropped += incoming - room

    # -- export -----------------------------------------------------------
    def dump(self, trace_id: Optional[str] = None,
             limit: Optional[int] = None) -> List[Dict]:
        """Snapshot of recorded spans/events (oldest first), optionally
        filtered to one trace and/or bounded to the newest ``limit``."""
        with self._lock:
            recs = list(self._ring)
        if trace_id is not None:
            recs = [r for r in recs if r["trace_id"] == trace_id]
        if limit is not None and limit > 0:
            recs = recs[-limit:]
        return recs

    def trace_ids(self) -> List[str]:
        """Distinct trace ids currently in the ring, oldest first."""
        seen: Dict[str, None] = {}
        for r in self.dump():
            seen.setdefault(r["trace_id"], None)
        return list(seen)

    def chrome_trace(self, trace_id: Optional[str] = None) -> Dict:
        """Chrome trace-event JSON (the ``chrome://tracing`` /
        Perfetto format; same family as the ``jax.profiler`` dumps).
        Each trace renders as its own thread track; phase spans are
        complete ('X') events, trace events are instants ('i')."""
        events = []
        tids: Dict[str, int] = {}
        for r in self.dump(trace_id=trace_id):
            tid = tids.setdefault(r["trace_id"], len(tids) + 1)
            base = {
                "pid": 1,
                "tid": tid,
                "ts": round(r["ts"] * 1e6, 3),
                "name": r["name"],
                "args": dict(r.get("attrs") or {},
                             trace_id=r["trace_id"]),
            }
            if r.get("event"):
                events.append(dict(base, ph="i", s="t"))
            else:
                events.append(dict(base, ph="X",
                                   cat=r.get("phase") or "span",
                                   dur=round(r["dur"] * 1e6, 3)))
        meta = [{"ph": "M", "pid": 1, "tid": tid, "name": "thread_name",
                 "args": {"name": f"trace {tr}"}}
                for tr, tid in tids.items()]
        return {"traceEvents": meta + events,
                "displayTimeUnit": "ms"}

    def phase_totals(self, trace_id: str) -> Dict[str, float]:
        """Per-phase summed duration for one trace (attribution math:
        phases are leaf + non-overlapping, so their sum approximates
        the root span's end-to-end duration)."""
        totals: Dict[str, float] = {}
        for r in self.dump(trace_id=trace_id):
            if not r.get("event") and r.get("phase"):
                totals[r["phase"]] = \
                    totals.get(r["phase"], 0.0) + r["dur"]
        return totals

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0
            self._ingress = 0


class _Activation:
    __slots__ = ("ctx", "_token")

    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        self._token = (_CURRENT.set(self.ctx)
                       if self.ctx is not None else None)
        return self.ctx

    def __exit__(self, *exc):
        if self._token is not None:
            _CURRENT.reset(self._token)
        return False


class _RootTrace:
    __slots__ = ("tracer", "name", "trace_id", "attrs", "ctx", "_token")

    def __init__(self, tracer, name, trace_id, attrs):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.attrs = attrs

    def __enter__(self) -> Optional[TraceContext]:
        self.ctx = self.tracer.start(self.name, trace_id=self.trace_id,
                                     **self.attrs)
        self._token = (_CURRENT.set(self.ctx)
                       if self.ctx is not None else None)
        return self.ctx

    def __exit__(self, exc_type, exc, tb):
        if self._token is not None:
            _CURRENT.reset(self._token)
        if self.ctx is not None:
            if exc is not None:
                self.ctx.attrs = dict(self.ctx.attrs,
                                      error=f"{exc_type.__name__}: {exc}")
            self.tracer.finish(self.ctx)
        return False


#: process-global flight recorder (like the metrics registry)
TRACER = Tracer()
