"""Metrics + spanstat.

Counter/gauge/histogram registry with Prometheus text exposition, plus
``SpanStat`` duration spans (reference: ``pkg/metrics``,
``pkg/spanstat`` — SURVEY.md §5.5). Key series mirror the reference's:
``policy_regeneration_time_stats_seconds`` → compile spans;
``drop_count_total`` / ``policy_l7_total`` → verdict counters.

Histograms are FIXED-BUCKET (cumulative ``_bucket{le=...}`` series +
``_count``/``_sum``), not sample lists: a long-running agent must hold
constant memory per series. A small bounded reservoir of the most
recent observations is retained per series so :meth:`Metrics.quantile`
(benches, tests) still answers over the recent window. Exposition is
valid Prometheus text format (``# HELP``/``# TYPE`` per family, label
values escaped) — :func:`lint_exposition` is the scrape-lint the
``make obs`` lane runs against the live registry.
"""

from __future__ import annotations

import bisect
import re
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

# -- degraded-operation series (runtime/faults.py + the TPU→oracle
# circuit breaker in runtime/service.py). Named here so the emitting
# seams, the chaos suite, and dashboards agree on one spelling.
#: breaker CLOSED→OPEN transitions (N consecutive device failures)
BREAKER_TRIPS = "cilium_tpu_breaker_trips_total"
#: breaker HALF_OPEN→CLOSED transitions (a probe succeeded)
BREAKER_RECOVERIES = "cilium_tpu_breaker_recoveries_total"
#: verdicts served by the CPU oracle because the device lane was
#: tripped or the dispatch failed (correct-but-slower path)
BREAKER_FALLBACK_VERDICTS = "cilium_tpu_breaker_fallback_verdicts_total"
#: gauge: 0 = CLOSED (device serving), 1 = OPEN (oracle serving),
#: 2 = HALF_OPEN (probe in flight)
BREAKER_STATE = "cilium_tpu_breaker_state"
#: faults fired by an armed FaultPlan, labelled by injection point
FAULTS_INJECTED = "cilium_tpu_faults_injected_total"
#: regenerations rolled back mid-swap (previous table kept serving)
LOADER_ROLLBACKS = "cilium_tpu_loader_swap_rollbacks_total"
#: stream-client reconnect attempts that re-established the session
STREAM_RECONNECTS = "cilium_tpu_stream_reconnects_total"
#: watch callbacks that raised and were isolated (kvstore.py)
KVSTORE_WATCH_ERRORS = "cilium_tpu_kvstore_watch_errors_total"
#: banked-DFA DNS batch failures degraded to the CPU regex path
DNSPROXY_FALLBACKS = "cilium_tpu_dnsproxy_fallback_total"
#: spans recorded by the flight recorder (runtime/tracing.py),
#: labelled by phase — the aggregate face of per-request attribution
TRACE_SPANS = "cilium_tpu_trace_spans_total"

# -- overload-resilience series (runtime/admission.py + the drain /
# warm-restart sequence in runtime/service.py + runtime/loader.py).
#: requests admitted past the gate, by surface (service/api) + class
ADMISSION_ADMITTED = "cilium_tpu_admission_admitted_total"
#: requests shed at (or behind) the gate, by surface/class/reason
ADMISSION_SHED = "cilium_tpu_admission_shed_total"
#: queued entries dropped before dispatch: caller abandoned (timed
#: out) or deadline expired while queued
ADMISSION_REAPED = "cilium_tpu_admission_reaped_total"
#: gauge: verdict-queue occupancy sampled at each admission decision
ADMISSION_QUEUE_DEPTH = "cilium_tpu_admission_queue_depth"
#: graceful drains completed (admission stopped, pending flushed)
DRAINS = "cilium_tpu_drains_total"
#: loader restorations from a warm-restart snapshot (no recompile)
WARM_RESTORES = "cilium_tpu_warm_restores_total"
#: corrupt artifact-cache entries deleted on read (recompile follows)
ARTIFACT_CACHE_CORRUPT = "cilium_tpu_artifact_cache_corrupt_total"
#: stream-client sends that blocked at zero credit (backpressure)
STREAM_CREDIT_WAITS = "cilium_tpu_stream_credit_waits_total"
#: credit grants sent by stream servers (one per answered chunk)
STREAM_CREDITS_GRANTED = "cilium_tpu_stream_credits_granted_total"

# -- perf-ledger series (device-time attribution + collective
# accounting: engine/phases.py, engine/verdict.py capture staging,
# parallel/collectives.py). Named here so the probes, the benches,
# and the obs-doc-parity lint agree on one spelling.
#: per-phase seconds from the engine phase probe (mapstate / dfa-scan
#: / resolve / gather / h2d / featurize / compile / execute)
ENGINE_PHASE_SECONDS = "cilium_tpu_engine_phase_seconds"
#: intentional host↔device sync points executed, by site — the phase
#: probes' completion-forcing readbacks. Every OTHER sync on the hot
#: path is a ctlint `implicit-sync` finding (docs/ANALYSIS.md v4);
#: this family makes the allowlisted remainder observable at runtime.
ENGINE_HOST_SYNCS = "cilium_tpu_engine_host_syncs_total"
#: capture-replay session staging, split by phase (tables / featurize
#: / dedup / table-h2d) — the 12.5s ``stage_ms`` decomposed
CAPTURE_STAGE_SECONDS = "cilium_tpu_capture_stage_seconds"
#: string-table row pieces dispatched to the device scan, by field
#: (engine/verdict.stage_capture_tables: one per piece of each table)
CAPTURE_TABLE_PIECES = "cilium_tpu_capture_table_pieces_total"
#: capture-replay dedup decisions, by what decided (strings: one
#: string-row column tells every row apart; rows: the built table) and
#: outcome (dropped / kept) — CaptureReplay.stage_unique
CAPTURE_DEDUP_DECISIONS = "cilium_tpu_capture_dedup_decisions_total"
#: collective ops recorded by the trace-time ledger, by site/op/axis
#: (counts are per compiled block execution — see parallel/collectives)
COLLECTIVE_OPS = "cilium_tpu_collective_ops_total"
#: bytes moved by those collectives (as-traced payload shapes)
COLLECTIVE_BYTES = "cilium_tpu_collective_bytes_total"

# -- verdict-memo series (engine/memo.py: the device-resident verdict
# memo behind capture/stream replay — hits are chunk rows served by
# the on-device gather, misses are unique rows verdicted and
# inserted, invalidations are memo drops with a reason label
# (policy-swap / auth-change / session-reset, plus the bank-scoped
# partial drops: bank-swap)).
VERDICT_MEMO_HITS = "cilium_tpu_verdict_memo_hits_total"
VERDICT_MEMO_MISSES = "cilium_tpu_verdict_memo_misses_total"
VERDICT_MEMO_INVALIDATIONS = "cilium_tpu_verdict_memo_invalidations_total"

# -- churn-proof policy plane (policy/compiler/bankplan.py +
# runtime/loader.py): content-addressed automaton banks, per-bank
# quarantine, and the O(Δ) incremental-compile ledger.
#: bank groups compiled (a cache miss in the content-addressed
#: registry), by field — O(Δ) under churn is THE property
BANK_REBUILDS = "cilium_tpu_bank_rebuilds_total"
#: bank groups quarantined after a compile failure (old cover keeps
#: serving; TTL-retried), by field
BANK_QUARANTINED = "cilium_tpu_bank_quarantined_total"
#: bank groups hot-swapped into the serving plan by a committed
#: revision (new content-addressed key), by field
BANK_HOTSWAPS = "cilium_tpu_bank_hotswaps_total"

# -- fleet-scale compile plane (policy/compiler/compilequeue.py +
# runtime/checkpoint.py bank artifacts + the sharded registry/
# fingerprint stores): the parallel bank-compile work queue's
# lifecycle ledger, the artifact-distribution fetch results, and the
# byte-bound eviction counters.
#: compile tasks submitted to the work queue, by priority class
#: (serving = delta compiles blocking a regeneration; background =
#: proactive quarantine-TTL rebuilds)
COMPILE_QUEUE_SUBMITTED = "cilium_tpu_compile_queue_submitted_total"
#: submits coalesced onto an in-flight task with the same work key
#: (content-addressed dedup: N racing compilers, one compile)
COMPILE_QUEUE_DEDUP = "cilium_tpu_compile_queue_dedup_total"
#: tasks completed (success or permanent failure)
COMPILE_QUEUE_COMPLETED = "cilium_tpu_compile_queue_completed_total"
#: in-queue retries (worker death re-queues with backoff)
COMPILE_QUEUE_RETRIES = "cilium_tpu_compile_queue_retries_total"
#: serving-blocking waits that hit the per-bank compile deadline
#: (the bank serves its cover; the compile finishes in background)
COMPILE_DEADLINE_LAPSES = "cilium_tpu_compile_deadline_lapses_total"
#: worker threads killed by the ``compile.worker`` fault point (or a
#: crash in the pool machinery); the pool respawns
COMPILE_WORKER_DEATHS = "cilium_tpu_compile_worker_deaths_total"
#: gauge: pending + running compile tasks (bounded by
#: ``[compile] max_pending``)
COMPILE_QUEUE_DEPTH = "cilium_tpu_compile_queue_depth"
#: compile results that landed AFTER their serving-blocking waiter
#: lapsed (stored for the next regeneration — work never wasted)
COMPILE_LATE_RESULTS = "cilium_tpu_compile_late_results_total"
#: banks served from their last-good cover while their compile was
#: still PENDING in the queue (deadline lapse, not quarantine)
BANK_PENDING_SERVES = "cilium_tpu_bank_pending_serves_total"
#: compiled-bank artifact fetches, by result (hit / miss / corrupt —
#: a corrupt or faulted fetch degrades to recompile, never a crash)
BANK_ARTIFACT_FETCHES = "cilium_tpu_bank_artifact_fetches_total"
#: bank groups evicted from the byte-bounded registry shards
REGISTRY_SHARD_EVICTIONS = "cilium_tpu_registry_shard_evictions_total"
#: identity-fingerprint bundles evicted from the sharded store
#: (recomputed on next regeneration — cost, never correctness)
FP_CACHE_EVICTIONS = "cilium_tpu_fp_cache_evictions_total"
#: on-disk artifact-cache entries evicted by the byte-bound LRU
#: (the serving artifact and warm snapshot are protected)
ARTIFACT_CACHE_EVICTIONS = "cilium_tpu_artifact_cache_evictions_total"

# -- continuously-batched serving loop (runtime/serveloop.py +
# engine/ring.py): persistent verdict ring, stream slot leases, and
# the memo-bypass selective-copy accounting.
#: gauge: stream slots currently leased in the verdict ring
SERVE_RING_OCCUPANCY = "cilium_tpu_serve_ring_occupancy"
#: slot leases granted (one per admitted stream; a reconnect-with-
#: resume that finds its lease alive does NOT grant again)
SERVE_LEASE_GRANTS = "cilium_tpu_serve_lease_grants_total"
#: leases expired by TTL (no activity renewed them in time)
SERVE_LEASE_EXPIRIES = "cilium_tpu_serve_lease_expiries_total"
#: leases released cleanly (stream end / drain)
SERVE_LEASE_RELEASES = "cilium_tpu_serve_lease_releases_total"
#: H2D bytes that never crossed because the row was already ring-
#: resident (memo/dedup hit): featurized row bytes avoided minus the
#: 4-byte id actually shipped — the Libra selective-copy claim, as a
#: counter
SERVE_MEMO_BYPASS_BYTES = "cilium_tpu_serve_memo_bypass_bytes_total"
#: records per pack-cycle fused dispatch
SERVE_PACK_RECORDS = "cilium_tpu_serve_pack_records"
#: distinct streams contributing to one pack-cycle dispatch
SERVE_PACK_STREAMS = "cilium_tpu_serve_pack_streams"
#: submit→verdict latency through the serving loop (seconds, on the
#: installed clock — virtual under the DST load model)
SERVE_LATENCY = "cilium_tpu_serve_latency_seconds"
#: wall seconds one pack cycle spent in the fused dispatch (encode
#: excluded — submit-side host work is attributed to the submitter)
SERVE_PACK_DISPATCH_SECONDS = "cilium_tpu_serve_pack_dispatch_seconds"

# -- verdict provenance & SLO telemetry (engine/attribution.py,
# runtime/explain.py, runtime/slo.py)
#: gauge: error-budget burn rate per declared SLO and trailing
#: window ({slo="serve-p99"|"serve-shed", window="300s"|...}); 1.0 =
#: spending budget exactly as declared
SLO_BURN_RATE = "cilium_tpu_slo_burn_rate"
#: verdicts that passed through provenance recording, by result
#: (explained / unexplained) — the explanation-coverage numerator and
#: denominator the serve-soak gate holds ≥0.999
PROVENANCE_RECORDS = "cilium_tpu_provenance_records_total"
#: explain-plane queries (/v1/explain, the explain op/CLI), by result
#: (hit / miss)
EXPLAIN_QUERIES = "cilium_tpu_explain_queries_total"

# -- serving fleet (runtime/fleetserve.py): stream-affinity routing,
# host-death failover, and the fleet-coherent shedding ledger.
#: stream leases migrated off a dead/partitioned host and re-granted
#: on a survivor (one per stream that moved)
FLEET_HANDOFFS = "cilium_tpu_fleet_handoffs_total"
#: hosts declared dead by the suspicion state machine (missed
#: heartbeats past the TTL) or killed outright
FLEET_HOST_DEATHS = "cilium_tpu_fleet_host_deaths_total"
#: dead hosts warm-restored back into the placement ring
FLEET_REJOINS = "cilium_tpu_fleet_rejoins_total"
#: new streams the router placed AWAY from their rendezvous owner
#: because the owner was past its spill headroom
FLEET_SPILLED_STREAMS = "cilium_tpu_fleet_spilled_streams_total"
#: gauge: leased-slot occupancy per host, by host — the occupancy
#: digest the fleet-coherent shed/spill decision reads
FLEET_HOST_OCCUPANCY = "cilium_tpu_fleet_host_occupancy"
#: chunks the router handed to a replica's loop, by host
#: (``FleetRouter.submit``)
FLEET_ROUTED_CHUNKS = "cilium_tpu_fleet_routed_chunks_total"

# -- fleet observability plane (runtime/fleetserve.py + hubble/
# flowagg.py): cross-host trace stitching, continuous flow export,
# fleet SLO roll-ups, and the fleet event journal.
#: gauge: fleet-wide burn-rate roll-up over the per-replica SLO
#: trackers, by slo, trailing window, and view (``worst`` = the worst
#: single host; ``weighted`` = fleet-weighted by request volume)
FLEET_SLO_BURN_RATE = "cilium_tpu_fleet_slo_burn_rate"
#: failover latency per handoff, by stage: ``death-to-regrant``
#: (death declared → lease re-granted on a survivor),
#: ``regrant-to-verdict`` (re-grant → first verdict after replay),
#: ``death-to-verdict`` (the end-to-end client-visible gap)
FLEET_FAILOVER_SECONDS = "cilium_tpu_fleet_failover_seconds"
#: fleet event-journal entries appended, by kind (the journal's
#: catalog is machine-checked against OBSERVABILITY.md)
FLEET_JOURNAL_EVENTS = "cilium_tpu_fleet_journal_events_total"
#: handoff-replayed chunks that resolved with a STITCHED trace — one
#: trace id spanning spans from both the dead host and the survivor
FLEET_TRACE_STITCHES = "cilium_tpu_fleet_trace_stitches_total"
#: provenance-stamped flow records fed into the per-host Hubble
#: FlowAggregator off the serve resolve path, by host
HUBBLE_FLOW_RECORDS = "cilium_tpu_hubble_flow_records_total"
#: flow aggregation keys dropped at the aggregator's bound (the
#: overflow counter that keeps the export honest about sampling)
HUBBLE_FLOW_OVERFLOW = "cilium_tpu_hubble_flow_overflow_total"

# -- multi-tenant control plane & policy canary (runtime/tenant.py,
# runtime/canary.py): per-tenant fairness attribution and the
# shadow-rollout verdict-diff gate.
#: per-tenant quota-store reads, by result (``live`` = an unexpired
#: declared share, ``lapsed`` = TTL expiry fell back to the
#: conservative default, ``fault-default`` = the ``tenant.quota``
#: read was lost and the conservative default applied)
TENANT_QUOTA_READS = "cilium_tpu_tenant_quota_reads_total"
#: canary double-dispatch samples, by result (``match`` / ``diff``)
CANARY_SAMPLES = "cilium_tpu_canary_samples_total"
#: canary commit attempts, by result (``committed`` / ``refused`` /
#: ``aborted``)
CANARY_COMMITS = "cilium_tpu_canary_commits_total"
#: gauge: observed verdict-diff fraction of the active canary
CANARY_DIFF_FRACTION = "cilium_tpu_canary_diff_fraction"

#: latency-shaped default boundaries (seconds; the Prometheus client
#: defaults) — covers every ``*_seconds`` series we emit
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
#: count-shaped boundaries (batch sizes, record counts): pow2, matching
#: the engine's pow2 padding buckets
SIZE_BUCKETS: Tuple[float, ...] = tuple(
    float(1 << i) for i in range(15))  # 1 .. 16384
#: most recent observations retained per series for quantile()
RESERVOIR = 1024


class _Histogram:
    """One series: cumulative fixed buckets + count/sum + a bounded
    reservoir of recent samples (quantile's window)."""

    __slots__ = ("buckets", "counts", "count", "sum", "reservoir")

    def __init__(self, buckets: Tuple[float, ...]):
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)  # +1: the +Inf bucket
        self.count = 0
        self.sum = 0.0
        self.reservoir: deque = deque(maxlen=RESERVOIR)

    def observe(self, value: float) -> None:
        self.counts[bisect.bisect_left(self.buckets, value)] += 1
        self.count += 1
        self.sum += value
        self.reservoir.append(value)


class Metrics:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[Tuple[str, Tuple], float] = {}
        self._gauges: Dict[Tuple[str, Tuple], float] = {}
        self._histos: Dict[Tuple[str, Tuple], _Histogram] = {}
        #: name → explicit bucket boundaries (else shape heuristic)
        self._buckets: Dict[str, Tuple[float, ...]] = {}
        self._help: Dict[str, str] = {}

    @staticmethod
    def _key(name: str, labels: Optional[Dict[str, str]]):
        return (name, tuple(sorted((labels or {}).items())))

    def describe(self, name: str, help_text: str,
                 buckets: Optional[Tuple[float, ...]] = None) -> None:
        """Register HELP text (and, for histograms, explicit bucket
        boundaries) for a metric family."""
        with self._lock:
            # ctlint: disable=unbounded-registry  # bounded by declared metric families (metric-registry enforces the catalog)
            self._help[name] = help_text
            if buckets is not None:
                # ctlint: disable=unbounded-registry  # one entry per declared histogram family
                self._buckets[name] = tuple(sorted(float(b)
                                                   for b in buckets))

    def _buckets_for(self, name: str) -> Tuple[float, ...]:
        explicit = self._buckets.get(name)
        if explicit is not None:
            return explicit
        # shape heuristic: count-valued series get pow2 boundaries,
        # everything else is latency-shaped seconds
        if name.endswith(("_size", "_records", "_bytes")):
            return SIZE_BUCKETS
        return DEFAULT_BUCKETS

    def inc(self, name: str, value: float = 1.0,
            labels: Optional[Dict[str, str]] = None) -> None:
        k = self._key(name, labels)
        with self._lock:
            # ctlint: disable=unbounded-registry  # keyed by declared family x finite label enums
            self._counters[k] = self._counters.get(k, 0.0) + value

    def set_gauge(self, name: str, value: float,
                  labels: Optional[Dict[str, str]] = None) -> None:
        with self._lock:
            # ctlint: disable=unbounded-registry  # keyed by declared family x finite label enums
            self._gauges[self._key(name, labels)] = value

    def observe(self, name: str, value: float,
                labels: Optional[Dict[str, str]] = None) -> None:
        k = self._key(name, labels)
        with self._lock:
            h = self._histos.get(k)
            if h is None:
                h = self._histos[k] = _Histogram(self._buckets_for(name))
            h.observe(value)

    def histo_sum(self, name: str,
                  labels: Optional[Dict[str, str]] = None) -> float:
        """Locked cumulative sum of a histogram series (phase-attribution
        deltas and similar read-side consumers)."""
        with self._lock:
            h = self._histos.get(self._key(name, labels))
            return float(h.sum) if h is not None else 0.0

    def histo_count(self, name: str,
                    labels: Optional[Dict[str, str]] = None) -> int:
        """Cumulative observation count — monotone, so callers can use
        it as a mark for :meth:`samples_since`."""
        with self._lock:
            h = self._histos.get(self._key(name, labels))
            return int(h.count) if h is not None else 0

    def samples_since(self, name: str, mark: int,
                      labels: Optional[Dict[str, str]] = None
                      ) -> List[float]:
        """Observations recorded after ``mark`` (a prior
        :meth:`histo_count`), served from the bounded reservoir —
        truncated to the newest :data:`RESERVOIR` if more arrived."""
        with self._lock:
            h = self._histos.get(self._key(name, labels))
            if h is None:
                return []
            newer = h.count - mark
            if newer <= 0:
                return []
            return list(h.reservoir)[-min(newer, len(h.reservoir)):]

    def get(self, name: str, labels: Optional[Dict[str, str]] = None) -> float:
        with self._lock:
            k = self._key(name, labels)
            if k in self._counters:
                return self._counters[k]
            return self._gauges.get(k, 0.0)

    def quantile(self, name: str, q: float,
                 labels: Optional[Dict[str, str]] = None) -> float:
        """Quantile over the series' recent-sample reservoir (the
        bench/test face; dashboards use the bucket series)."""
        with self._lock:
            h = self._histos.get(self._key(name, labels))
            vals = sorted(h.reservoir) if h is not None else []
        if not vals:
            return 0.0
        idx = min(len(vals) - 1, int(q * len(vals)))
        return vals[idx]

    def expose(self) -> str:
        """Valid Prometheus text format: one ``# HELP``/``# TYPE`` pair
        per family, escaped label values, cumulative ``_bucket{le=...}``
        series (ending ``+Inf``) plus ``_count``/``_sum`` per
        histogram series."""
        out: List[str] = []
        with self._lock:
            counters = sorted(self._counters.items())
            gauges = sorted(self._gauges.items())
            histos = sorted(self._histos.items(),
                            key=lambda kv: kv[0])
            help_texts = dict(self._help)

        def _family(name: str, typ: str) -> None:
            help_text = help_texts.get(
                name, f"cilium_tpu {typ} {name}")
            out.append(f"# HELP {name} {_escape_help(help_text)}")
            out.append(f"# TYPE {name} {typ}")

        last = None
        for (name, labels), v in counters:
            if name != last:
                _family(name, "counter")
                last = name
            out.append(f"{_fmt(name, labels)} {_num(v)}")
        last = None
        for (name, labels), v in gauges:
            if name != last:
                _family(name, "gauge")
                last = name
            out.append(f"{_fmt(name, labels)} {_num(v)}")
        last = None
        for (name, labels), h in histos:
            if name != last:
                _family(name, "histogram")
                last = name
            cum = 0
            for bound, n in zip(h.buckets, h.counts):
                cum += n
                out.append(_fmt(name + "_bucket",
                                labels + (("le", _num(bound)),))
                           + f" {cum}")
            out.append(_fmt(name + "_bucket", labels + (("le", "+Inf"),))
                       + f" {h.count}")
            out.append(f"{_fmt(name + '_count', labels)} {h.count}")
            out.append(f"{_fmt(name + '_sum', labels)} {_num(h.sum)}")
        return "\n".join(out) + "\n"


def _num(v: float) -> str:
    """Canonical number rendering: integers without a trailing .0 (the
    Prometheus text convention for counts/bounds)."""
    f = float(v)
    return str(int(f)) if f.is_integer() and abs(f) < 1e15 else repr(f)


def _escape_label(v: str) -> str:
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _escape_help(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


def _fmt(name: str, labels: Tuple) -> str:
    if not labels:
        return name
    inner = ",".join(f'{k}="{_escape_label(v)}"' for k, v in labels)
    return f"{name}{{{inner}}}"


# -- scrape lint ------------------------------------------------------------

_NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*")
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?P<labels>\{[^{}]*\})?"
    r" (?P<value>[+-]?(?:\d+\.?\d*(?:[eE][+-]?\d+)?|Inf|NaN))"
    r"(?: (?P<ts>[+-]?\d+))?$")
_LABEL_RE = re.compile(
    r'(?P<key>[a-zA-Z_][a-zA-Z0-9_]*)='
    r'"(?P<val>(?:[^"\\\n]|\\["\\n])*)"')


def lint_exposition(text: str) -> List[str]:
    """Parse Prometheus text exposition line-by-line; return a list of
    error strings (empty = clean). Checks: comment shape, sample-line
    grammar, label quoting/escaping, TYPE declared before a family's
    samples, histogram buckets cumulative and +Inf-terminated with
    ``_count`` equal to the +Inf bucket."""
    errors: List[str] = []
    typed: Dict[str, str] = {}
    buckets_seen: Dict[Tuple[str, Tuple], List[Tuple[str, int]]] = {}
    counts_seen: Dict[Tuple[str, Tuple], int] = {}
    for i, line in enumerate(text.splitlines(), 1):
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(" ", 3)
            if len(parts) < 4 or parts[1] not in ("HELP", "TYPE") \
                    or not _NAME_RE.fullmatch(parts[2]):
                errors.append(f"line {i}: malformed comment: {line!r}")
            elif parts[1] == "TYPE":
                if parts[3] not in ("counter", "gauge", "histogram",
                                    "summary", "untyped"):
                    errors.append(f"line {i}: unknown type {parts[3]!r}")
                typed[parts[2]] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            errors.append(f"line {i}: malformed sample: {line!r}")
            continue
        name = m.group("name")
        raw_labels = m.group("labels")
        labels: List[Tuple[str, str]] = []
        if raw_labels:
            body = raw_labels[1:-1]
            consumed = 0
            for lm in _LABEL_RE.finditer(body):
                labels.append((lm.group("key"), lm.group("val")))
                consumed = lm.end()
                if consumed < len(body) and body[consumed] == ",":
                    consumed += 1
            if consumed != len(body):
                errors.append(
                    f"line {i}: malformed labels: {raw_labels!r}")
        family = name
        for suffix in ("_bucket", "_count", "_sum"):
            base = name[:-len(suffix)] if name.endswith(suffix) else None
            if base and typed.get(base) == "histogram":
                family = base
                break
        if family not in typed:
            errors.append(f"line {i}: sample {name!r} has no # TYPE")
            continue
        if typed[family] == "histogram" and name == family + "_bucket":
            le = dict(labels).get("le")
            if le is None:
                errors.append(f"line {i}: bucket without le label")
                continue
            key = (family, tuple(sorted(
                (k, v) for k, v in labels if k != "le")))
            buckets_seen.setdefault(key, []).append(
                (le, int(float(m.group("value")))))
        if typed.get(family) == "histogram" and name == family + "_count":
            counts_seen[(family, tuple(sorted(labels)))] = \
                int(float(m.group("value")))
    for (family, labels), series in buckets_seen.items():
        values = [v for _, v in series]
        if values != sorted(values):
            errors.append(
                f"{family}{dict(labels)}: buckets not cumulative")
        if series[-1][0] != "+Inf":
            errors.append(f"{family}{dict(labels)}: missing +Inf bucket")
        else:
            total = counts_seen.get((family, labels))
            if total is not None and total != series[-1][1]:
                errors.append(
                    f"{family}{dict(labels)}: _count {total} != "
                    f"+Inf bucket {series[-1][1]}")
    return errors


#: process-global registry (like the reference's default registry)
METRICS = Metrics()
METRICS.describe("cilium_tpu_microbatch_size",
                 "records per MicroBatcher flush", buckets=SIZE_BUCKETS)
METRICS.describe("cilium_tpu_microbatch_seconds",
                 "MicroBatcher flush wall seconds")
METRICS.describe("cilium_tpu_span_seconds",
                 "SpanStat duration spans, labelled by span")
METRICS.describe(BREAKER_STATE,
                 "0=closed (device), 1=open (oracle), 2=half-open")
METRICS.describe(TRACE_SPANS,
                 "flight-recorder spans recorded, by phase")

# -- family catalog ---------------------------------------------------------
# ctlint (analysis/registry.py, rule metric-registry) requires every
# family written anywhere in the package to be declared here exactly
# once: the declaration is what turns a typo'd producer name into a
# lint error instead of a silently-dead series, and it gives every
# exposed family real # HELP text.
METRICS.describe(BREAKER_TRIPS,
                 "breaker CLOSED->OPEN transitions")
METRICS.describe(BREAKER_RECOVERIES,
                 "breaker HALF_OPEN->CLOSED transitions")
METRICS.describe(BREAKER_FALLBACK_VERDICTS,
                 "verdicts served by the CPU oracle while degraded")
METRICS.describe(FAULTS_INJECTED,
                 "faults fired by an armed FaultPlan, by point")
METRICS.describe(LOADER_ROLLBACKS,
                 "regenerations rolled back mid-swap")
METRICS.describe(STREAM_RECONNECTS,
                 "stream-client reconnects that resumed the session")
METRICS.describe(KVSTORE_WATCH_ERRORS,
                 "kvstore watch callbacks that raised and were isolated")
METRICS.describe(DNSPROXY_FALLBACKS,
                 "banked-DFA DNS batches degraded to the regex path")
METRICS.describe("cilium_tpu_accesslog_decode_errors_total",
                 "undecodable access-log records")
METRICS.describe("cilium_tpu_accesslog_records_total",
                 "access-log records ingested, by proto")
METRICS.describe("cilium_tpu_api_requests_total",
                 "REST API requests served")
METRICS.describe("cilium_tpu_auth_pairs",
                 "mutual-auth pairs currently authenticated")
METRICS.describe("cilium_tpu_clustermesh_decode_errors_total",
                 "undecodable remote-cluster kvstore events")
METRICS.describe("cilium_tpu_clustermesh_ready",
                 "1 when the remote cluster's session is live")
METRICS.describe("cilium_tpu_compile_seconds",
                 "policy snapshot compile wall seconds",
                 buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                          30.0, 60.0, 120.0))
METRICS.describe("cilium_tpu_controller_runs_total",
                 "controller loop runs, by name and status")
METRICS.describe("cilium_tpu_endpoint_regenerations_total",
                 "per-endpoint regeneration completions, by status")
METRICS.describe("cilium_tpu_identity_regen_coalesced_total",
                 "identity-churn events absorbed by an already-armed "
                 "regeneration debounce window (storm size minus the "
                 "one regeneration that covered it)")
METRICS.describe("cilium_tpu_endpoints",
                 "endpoints currently managed")
METRICS.describe("cilium_tpu_endpoints_restored_total",
                 "endpoints restored from the state dir at startup")
METRICS.describe("cilium_tpu_frontend_rules",
                 "protocol-frontend rules in the serving compiled "
                 "policy, by proto (policy/compiler/frontends)")
METRICS.describe("cilium_tpu_fqdn_handler_errors_total",
                 "DNS proxy handler threads that raised")
METRICS.describe("cilium_tpu_fqdn_malformed_queries_total",
                 "DNS queries that failed wire parsing")
METRICS.describe("cilium_tpu_fqdn_queries_total",
                 "DNS proxy queries, by verdict")
METRICS.describe("cilium_tpu_fqdn_unknown_client_total",
                 "DNS queries from unmapped client addresses")
METRICS.describe("cilium_tpu_fqdn_upstream_timeouts_total",
                 "upstream DNS resolutions that timed out")
METRICS.describe("cilium_tpu_health_probe_seconds",
                 "node-to-node health probe latency, by peer")
METRICS.describe("cilium_tpu_health_reachable",
                 "1 when the peer's last health probe succeeded")
METRICS.describe("cilium_tpu_identities_cluster",
                 "identities known to the cluster-scope cache")
METRICS.describe("cilium_tpu_ipam_endpoints_outside_cidr",
                 "restored endpoints whose IP is outside the node CIDR")
METRICS.describe("cilium_tpu_ipam_ips_allocated",
                 "IPs currently allocated from the node CIDR")
METRICS.describe("cilium_tpu_ipam_node_cidrs",
                 "node CIDRs carved from the cluster pool")
METRICS.describe("cilium_tpu_k8s_cnp_parse_errors_total",
                 "CNP/CCNP objects that failed rule parsing")
METRICS.describe("cilium_tpu_lb_services",
                 "load-balancer services installed")
METRICS.describe("cilium_tpu_leader",
                 "1 while this process holds the named leader lock")
METRICS.describe("cilium_tpu_monitor_events_total",
                 "monitor socket events fanned out, by type")
METRICS.describe("cilium_tpu_npds_pulls_total",
                 "NPDS mapstate pulls served to shims")
METRICS.describe("cilium_tpu_operator_cidrs_quarantined_total",
                 "pod CIDRs quarantined pending release confirmation")
METRICS.describe("cilium_tpu_operator_cidrs_reclaimed_total",
                 "pod CIDRs reclaimed from departed nodes")
METRICS.describe("cilium_tpu_operator_identities_gc_total",
                 "kvstore identities garbage-collected")
METRICS.describe("cilium_tpu_operator_pool_exhausted_total",
                 "node CIDR requests refused: cluster pool exhausted")
METRICS.describe("cilium_tpu_policy_l7_total",
                 "L7 proxy policy checks, by proto and verdict")
METRICS.describe("cilium_tpu_policy_watch_ops_total",
                 "policy-directory watch operations applied")
METRICS.describe("cilium_tpu_policy_watch_parse_errors_total",
                 "policy-directory files that failed YAML parsing")
METRICS.describe("cilium_tpu_proxy_redirects",
                 "proxy redirects currently installed")
METRICS.describe("cilium_tpu_proxy_redirects_created_total",
                 "proxy redirects created")
METRICS.describe("cilium_tpu_proxy_redirects_released_total",
                 "proxy redirects released")
METRICS.describe("cilium_tpu_regenerations_total",
                 "policy snapshot regenerations committed, by backend")
METRICS.describe("cilium_tpu_service_verdicts_total",
                 "flows verdicted via the bulk service op")
METRICS.describe("cilium_tpu_stream_unknown_frames_total",
                 "stream frames dropped for an unknown kind")
METRICS.describe("cilium_tpu_stream_verdicts_total",
                 "verdicts returned over the chunked binary stream")
METRICS.describe(ADMISSION_ADMITTED,
                 "requests admitted past the gate, by surface/class")
METRICS.describe(ADMISSION_SHED,
                 "requests shed, by surface/class/reason")
METRICS.describe(ADMISSION_REAPED,
                 "queued entries dropped before dispatch (abandoned "
                 "caller or expired deadline)")
METRICS.describe(ADMISSION_QUEUE_DEPTH,
                 "verdict-queue occupancy at the admission decision")
METRICS.describe(DRAINS,
                 "graceful drains completed")
METRICS.describe(WARM_RESTORES,
                 "loader restorations from a warm-restart snapshot")
METRICS.describe(ARTIFACT_CACHE_CORRUPT,
                 "corrupt artifact-cache entries deleted on read")
METRICS.describe(STREAM_CREDIT_WAITS,
                 "stream-client sends that blocked at zero credit")
METRICS.describe(STREAM_CREDITS_GRANTED,
                 "credit grants sent by stream servers")
METRICS.describe(ENGINE_PHASE_SECONDS,
                 "engine phase probe seconds, by phase",
                 buckets=(1e-5, 5e-5, 1e-4, 5e-4, 0.001, 0.0025,
                          0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                          1.0, 2.5))
METRICS.describe(ENGINE_HOST_SYNCS,
                 "blocking device-to-host reads executed, by site "
                 "(served and replay readbacks, phase-probe "
                 "completion forcing)")
METRICS.describe(CAPTURE_STAGE_SECONDS,
                 "capture-replay session staging seconds, by phase",
                 buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                          2.5, 5.0, 10.0, 30.0, 60.0, 120.0))
METRICS.describe(CAPTURE_TABLE_PIECES,
                 "capture string-table row pieces dispatched to the "
                 "device scan, by field")
METRICS.describe(CAPTURE_DEDUP_DECISIONS,
                 "capture-replay dedup decisions, by what decided "
                 "(a string-row column or the built table) and the "
                 "outcome")
METRICS.describe(COLLECTIVE_OPS,
                 "collective ops recorded at trace time, by "
                 "site/op/axis (count per compiled block)")
METRICS.describe(COLLECTIVE_BYTES,
                 "collective payload bytes (as-traced shapes), by "
                 "site/op/axis")
METRICS.describe(VERDICT_MEMO_HITS,
                 "replay rows served from the device verdict memo")
METRICS.describe(VERDICT_MEMO_MISSES,
                 "unique rows verdicted and inserted into the memo")
METRICS.describe(VERDICT_MEMO_INVALIDATIONS,
                 "verdict-memo drops, by reason (policy-swap / "
                 "auth-change / session-reset / bank-swap)")
METRICS.describe(BANK_REBUILDS,
                 "automaton bank groups compiled, by field")
METRICS.describe(BANK_QUARANTINED,
                 "bank groups quarantined after compile failure, "
                 "by field")
METRICS.describe(BANK_HOTSWAPS,
                 "bank groups hot-swapped by a committed revision, "
                 "by field")
METRICS.describe(COMPILE_QUEUE_SUBMITTED,
                 "bank-compile tasks submitted, by priority class "
                 "(serving / background)")
METRICS.describe(COMPILE_QUEUE_DEDUP,
                 "compile submits coalesced onto an in-flight task "
                 "with the same work key")
METRICS.describe(COMPILE_QUEUE_COMPLETED,
                 "compile tasks completed (success or permanent "
                 "failure)")
METRICS.describe(COMPILE_QUEUE_RETRIES,
                 "in-queue compile retries (worker-death backoff "
                 "re-queues)")
METRICS.describe(COMPILE_DEADLINE_LAPSES,
                 "serving-blocking compile waits that hit the "
                 "per-bank deadline (bank rides its cover)")
METRICS.describe(COMPILE_WORKER_DEATHS,
                 "compile worker threads that died mid-task (pool "
                 "respawns)")
METRICS.describe(COMPILE_QUEUE_DEPTH,
                 "pending + running compile tasks in the work queue")
METRICS.describe(COMPILE_LATE_RESULTS,
                 "compile results stored after their waiter's "
                 "deadline lapsed")
METRICS.describe(BANK_PENDING_SERVES,
                 "banks served from their last-good cover while "
                 "their compile was still pending")
METRICS.describe(BANK_ARTIFACT_FETCHES,
                 "compiled-bank artifact fetches, by result "
                 "(hit / miss / corrupt)")
METRICS.describe(REGISTRY_SHARD_EVICTIONS,
                 "bank groups evicted from the byte-bounded registry "
                 "shards")
METRICS.describe(FP_CACHE_EVICTIONS,
                 "identity-fingerprint bundles evicted from the "
                 "sharded store")
METRICS.describe(ARTIFACT_CACHE_EVICTIONS,
                 "artifact-cache entries evicted by the byte-bound "
                 "LRU (serving + warm keys protected)")
METRICS.describe(SERVE_RING_OCCUPANCY,
                 "stream slots currently leased in the verdict ring")
METRICS.describe(SERVE_LEASE_GRANTS,
                 "verdict-ring slot leases granted")
METRICS.describe(SERVE_LEASE_EXPIRIES,
                 "slot leases expired by TTL without renewal")
METRICS.describe(SERVE_LEASE_RELEASES,
                 "slot leases released cleanly (stream end / drain)")
METRICS.describe(SERVE_MEMO_BYPASS_BYTES,
                 "H2D bytes saved by ring-resident rows (memo/dedup "
                 "hits ship a 4-byte id, not the featurized row)")
METRICS.describe(SERVE_PACK_RECORDS,
                 "records per pack-cycle fused dispatch",
                 buckets=SIZE_BUCKETS)
METRICS.describe(SERVE_PACK_STREAMS,
                 "distinct streams contributing to one pack-cycle "
                 "dispatch", buckets=SIZE_BUCKETS)
METRICS.describe(SERVE_LATENCY,
                 "submit-to-verdict latency through the serving loop "
                 "(installed-clock seconds)")
METRICS.describe(SERVE_PACK_DISPATCH_SECONDS,
                 "wall seconds per pack-cycle fused dispatch")
METRICS.describe(SLO_BURN_RATE,
                 "error-budget burn rate, by slo and trailing window "
                 "(1.0 = spending budget exactly as declared)")
METRICS.describe(PROVENANCE_RECORDS,
                 "verdicts through provenance recording, by result "
                 "(explained / unexplained)")
METRICS.describe(EXPLAIN_QUERIES,
                 "explain-plane queries, by result (hit / miss)")
METRICS.describe(FLEET_HANDOFFS,
                 "stream leases migrated off a dead host and "
                 "re-granted on a survivor")
METRICS.describe(FLEET_HOST_DEATHS,
                 "hosts declared dead (missed heartbeats past the "
                 "suspicion TTL, or killed)")
METRICS.describe(FLEET_REJOINS,
                 "dead hosts warm-restored back into rotation")
METRICS.describe(FLEET_SPILLED_STREAMS,
                 "new streams placed away from their rendezvous "
                 "owner for headroom")
METRICS.describe(FLEET_HOST_OCCUPANCY,
                 "leased-slot occupancy per fleet host, by host")
METRICS.describe(FLEET_ROUTED_CHUNKS,
                 "chunks the fleet router handed to a replica, by host")
METRICS.describe(FLEET_SLO_BURN_RATE,
                 "fleet burn-rate roll-up, by slo, window, and view "
                 "(worst single host / fleet-weighted)")
METRICS.describe(FLEET_FAILOVER_SECONDS,
                 "failover latency per handoff, by stage (death-to-"
                 "regrant / regrant-to-verdict / death-to-verdict)")
METRICS.describe(FLEET_JOURNAL_EVENTS,
                 "fleet event-journal entries appended, by kind")
METRICS.describe(FLEET_TRACE_STITCHES,
                 "handoff-replayed chunks resolved under a stitched "
                 "cross-host trace")
METRICS.describe(HUBBLE_FLOW_RECORDS,
                 "flow records fed into the per-host Hubble flow "
                 "aggregator, by host")
METRICS.describe(HUBBLE_FLOW_OVERFLOW,
                 "flow aggregation keys dropped at the aggregator's "
                 "key bound")
METRICS.describe(TENANT_QUOTA_READS,
                 "per-tenant quota-store reads, by result (live / "
                 "lapsed / fault-default)")
METRICS.describe(CANARY_SAMPLES,
                 "canary double-dispatch samples, by result "
                 "(match / diff)")
METRICS.describe(CANARY_COMMITS,
                 "canary commit attempts, by result (committed / "
                 "refused / aborted)")
METRICS.describe(CANARY_DIFF_FRACTION,
                 "observed verdict-diff fraction of the active "
                 "canary")


class SpanStat:
    """Duration span: ``with SpanStat("compile"): ...`` records seconds
    into the global histogram ``cilium_tpu_span_seconds{span=...}``."""

    def __init__(self, span: str, metrics: Metrics = METRICS):
        self.span = span
        self.metrics = metrics
        self.seconds = 0.0

    def __enter__(self) -> "SpanStat":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self.metrics.observe("cilium_tpu_span_seconds", self.seconds,
                             {"span": self.span})
