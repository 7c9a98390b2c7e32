"""End-to-end verdict tracing (ISSUE 2): the flight recorder
(runtime/tracing.py), phase attribution across the MicroBatcher /
ResilientVerdictor / stream transport, trace-context survival across
reconnect-with-resume, the trace_id joins (JSONL logs, Hubble flows,
/v1/trace), and the Prometheus exposition validity of
runtime/metrics.py."""

import io
import json
import logging as pylogging
import threading
import time

import numpy as np
import pytest

from cilium_tpu.core.config import Config
from cilium_tpu.core.flow import Flow, Protocol, TrafficDirection, Verdict
from cilium_tpu.runtime import faults
from cilium_tpu.runtime.faults import FaultPlan, FaultRule
from cilium_tpu.runtime.loader import Loader
from cilium_tpu.runtime.metrics import (
    METRICS,
    Metrics,
    lint_exposition,
)
from cilium_tpu.runtime.service import VerdictService
from cilium_tpu.runtime.tracing import (
    PHASE_DEVICE,
    PHASE_FALLBACK,
    PHASE_HOST,
    PHASE_QUEUE,
    TRACE_ID_CHARS,
    TRACER,
    Tracer,
)


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """Each test sees an empty ring with default knobs; leaked state
    (a prior test's spans, a disabled recorder) must not bleed."""
    TRACER.configure(enabled=True, sample_rate=1.0, capacity=4096)
    TRACER.clear()
    yield
    TRACER.configure(enabled=True, sample_rate=1.0)
    TRACER.clear()
    faults.clear()


def _tiny_policy(port):
    from cilium_tpu.core.identity import IdentityAllocator
    from cilium_tpu.core.labels import LabelSet
    from cilium_tpu.policy.api import (
        EndpointSelector,
        IngressRule,
        PortProtocol,
        PortRule,
        Rule,
    )
    from cilium_tpu.policy.mapstate import PolicyResolver
    from cilium_tpu.policy.repository import Repository
    from cilium_tpu.policy.selectorcache import SelectorCache

    rules = [Rule(
        endpoint_selector=EndpointSelector.from_labels(app="db"),
        ingress=(IngressRule(
            from_endpoints=(EndpointSelector.from_labels(app="web"),),
            to_ports=(PortRule(ports=(
                PortProtocol(port, Protocol.TCP),)),)),),
    )]
    alloc = IdentityAllocator()
    db = alloc.allocate(LabelSet.from_dict({"app": "db"}))
    web = alloc.allocate(LabelSet.from_dict({"app": "web"}))
    cache = SelectorCache(alloc)
    repo = Repository()
    repo.add(rules, sanitize=False)
    per_identity = {db: PolicyResolver(repo, cache).resolve(
        alloc.lookup(db))}
    return per_identity, db, web


def _flow(web, db, port):
    return Flow(src_identity=web, dst_identity=db, dport=port,
                protocol=Protocol.TCP,
                direction=TrafficDirection.INGRESS)


def _service(tmp_path, per_identity, offload=True):
    cfg = Config()
    cfg.enable_tpu_offload = offload
    cfg.loader.enable_cache = False
    loader = Loader(cfg)
    loader.regenerate(per_identity, revision=1)
    svc = VerdictService(loader, str(tmp_path / "svc.sock"))
    svc.start()
    return svc


# ---------------------------------------------------------------------------
# Tracer unit behavior


def test_span_recording_and_ring_bound():
    tr = Tracer(capacity=8)
    for i in range(20):
        with tr.trace("req", i=i):
            with tr.span("work", phase=PHASE_HOST):
                pass
    recs = tr.dump()
    assert len(recs) == 8  # bounded
    assert tr.dropped == 2 * 20 - 8
    # newest survive
    assert recs[-1]["name"] == "req"


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.trace("req") as ctx:
        assert ctx is None
        with tr.span("work", phase=PHASE_HOST):
            pass
        tr.event("boom")
    assert tr.dump() == []


def test_sample_rate_admits_every_nth_ingress():
    tr = Tracer(sample_rate=0.25)
    sampled = [tr.start("req") is not None for _ in range(16)]
    assert sum(sampled) == 4
    assert sampled[0]  # deterministic: first ingress always admitted
    # adoption (a propagated wire id) bypasses the sampler entirely
    assert tr.start("req", trace_id="a" * TRACE_ID_CHARS) is not None


def test_group_context_fans_span_to_all_members():
    tr = Tracer()
    a, b = tr.start("a"), tr.start("b")
    with tr.activate(tr.group([a, None, b])):
        with tr.span("batch", phase=PHASE_DEVICE):
            pass
    ids = {r["trace_id"] for r in tr.dump()}
    assert ids == {a.trace_id, b.trace_id}


def test_chrome_trace_export_shape():
    tr = Tracer()
    with tr.trace("req") as ctx:
        with tr.span("work", phase=PHASE_HOST):
            pass
        tr.event("mark", detail="x")
    doc = tr.chrome_trace()
    assert "traceEvents" in doc
    phs = sorted(e["ph"] for e in doc["traceEvents"])
    assert phs == ["M", "X", "X", "i"]  # meta + 2 spans + 1 instant
    complete = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    for e in complete:
        assert e["args"]["trace_id"] == ctx.trace_id
        assert e["dur"] >= 0 and e["ts"] > 0
    assert any(e.get("cat") == PHASE_HOST for e in complete)


# ---------------------------------------------------------------------------
# Phase attribution through the service


def test_check_op_phases_sum_to_e2e(tmp_path):
    """A single MicroBatcher 'check': queue-wait + fallback (oracle
    engine) spans exist, carry one trace id, and their sum is a sane
    share of the measured end-to-end latency."""
    from cilium_tpu.runtime.service import VerdictClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, offload=False)
    try:
        client = VerdictClient(svc.socket_path)
        t0 = time.time()
        resp = client.call({"op": "check", "flow": {
            "source": {"identity": int(web)},
            "destination": {"identity": int(db)},
            "l4": {"TCP": {"destination_port": 5432}},
            "traffic_direction": "INGRESS"}})
        e2e = time.time() - t0
        assert resp["verdict"] == 1
        tid = resp["trace_id"]
        spans = TRACER.dump(trace_id=tid)
        phases = TRACER.phase_totals(tid)
        assert PHASE_QUEUE in phases and PHASE_FALLBACK in phases
        root = [s for s in spans
                if s.get("attrs", {}).get("root")][0]
        assert root["name"] == "service.check"
        # phases are leaf + non-overlapping: they can never exceed the
        # measured wall (modulo clock rounding), and the queue-wait
        # (deadline window) should make them the dominant share of the
        # server-side root span
        total = sum(phases.values())
        assert total <= e2e * 1.05
        assert total >= 0.25 * root["dur"]
        client.close()
    finally:
        svc.stop()


def test_verdict_op_device_phases_and_flow_stamp(tmp_path):
    """Bulk 'verdict' op on the TPU-gated engine: host-prep +
    device-dispatch spans recorded under the request's trace."""
    from cilium_tpu.runtime.service import VerdictClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, offload=True)
    try:
        client = VerdictClient(svc.socket_path)
        resp = client.call({"op": "verdict", "flows": [
            {"source": {"identity": int(web)},
             "destination": {"identity": int(db)},
             "l4": {"TCP": {"destination_port": 5432}},
             "traffic_direction": "INGRESS"}]})
        assert resp["verdicts"] == [1]
        phases = TRACER.phase_totals(resp["trace_id"])
        assert PHASE_HOST in phases and PHASE_DEVICE in phases
        assert PHASE_FALLBACK not in phases
        client.close()
    finally:
        svc.stop()


def test_breaker_fallback_shows_in_trace(tmp_path):
    """Device faults: the trace records the injected-fault event, the
    device failure, and the oracle-fallback phase — the per-request
    face of the breaker counters."""
    from cilium_tpu.runtime.service import VerdictClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, offload=True)
    try:
        client = VerdictClient(svc.socket_path)
        with faults.inject(FaultPlan(
                [FaultRule("engine.dispatch", times=1)], seed=0)):
            resp = client.call({"op": "verdict", "flows": [
                {"source": {"identity": int(web)},
                 "destination": {"identity": int(db)},
                 "l4": {"TCP": {"destination_port": 5432}},
                 "traffic_direction": "INGRESS"}]})
        assert resp["verdicts"] == [1]  # oracle answered
        spans = TRACER.dump(trace_id=resp["trace_id"])
        names = [s["name"] for s in spans]
        assert "fault.injected" in names
        assert "device.failure" in names
        assert PHASE_FALLBACK in TRACER.phase_totals(resp["trace_id"])
        client.close()
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# Stream transport propagation


def test_stream_trace_context_propagates_to_server(tmp_path):
    from cilium_tpu.runtime.stream import StreamClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, offload=False)
    try:
        client = StreamClient(svc.socket_path, timeout=30.0)
        assert client._trace_peer  # server advertised trace support
        flows = [_flow(web, db, 5432 if i % 2 == 0 else 5433)
                 for i in range(16)]
        with TRACER.trace("client.request") as ctx:
            seq = client.send_flows(flows)
        client.finish()
        assert list(client.result(seq)) == [1, 2] * 8
        # the SERVER recorded this chunk under the client's trace id
        spans = TRACER.dump(trace_id=ctx.trace_id)
        names = {s["name"] for s in spans}
        assert "stream.chunk" in names  # server root span
        phases = TRACER.phase_totals(ctx.trace_id)
        assert PHASE_QUEUE in phases
        assert PHASE_FALLBACK in phases  # oracle engine served it
        client.close()
    finally:
        svc.stop()


def test_stream_trace_survives_reconnect_with_resume(tmp_path):
    """A mid-stream connection drop: the re-sent chunk keeps its trace
    id across the resume, and the injected fault appears as a span
    event in SOME trace (the drop hits whichever frame was in
    flight)."""
    from cilium_tpu.runtime.stream import StreamClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, offload=False)
    try:
        client = StreamClient(svc.socket_path, timeout=60.0,
                              reconnect=True, backoff_base=0.01)
        flows = [_flow(web, db, 5432 if i % 2 == 0 else 5433)
                 for i in range(8)]
        ctxs = []
        with faults.inject(FaultPlan([FaultRule(
                "stream.frame.client", after=1, times=1,
                exc=ConnectionError)], seed=3)):
            seqs = []
            for _ in range(5):
                with TRACER.trace("client.request") as ctx:
                    seqs.append(client.send_flows(flows))
                ctxs.append(ctx)
            client.finish()
            for seq in seqs:
                assert list(client.result(seq)) == [1, 2] * 4
        # every chunk's trace shows a server-side dispatch — including
        # the one(s) re-sent after the drop. The re-sent chunk is
        # dispatched TWICE server-side (at-least-once resume), so its
        # trace has >= 1 stream.chunk roots; all have the same id.
        for ctx in ctxs:
            names = [s["name"] for s in
                     TRACER.dump(trace_id=ctx.trace_id)]
            assert names.count("stream.chunk") >= 1, ctx.trace_id
        client.close()
    finally:
        svc.stop()


def test_untraced_stream_frames_still_work(tmp_path):
    """Tracing disabled client-side → plain KIND_CHUNK frames; the
    server answers normally and records nothing for them (old-peer
    compatibility of the optional wire field)."""
    from cilium_tpu.runtime.stream import StreamClient

    per, db, web = _tiny_policy(5432)
    svc = _service(tmp_path, per, offload=False)
    TRACER.configure(enabled=False)
    TRACER.clear()  # drop the loader.regenerate trace from setup
    try:
        client = StreamClient(svc.socket_path, timeout=30.0)
        flows = [_flow(web, db, 5432)] * 4
        seq = client.send_flows(flows)
        client.finish()
        assert list(client.result(seq)) == [1] * 4
        assert TRACER.dump() == []
        client.close()
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# The trace_id joins: JSONL logs + Hubble flows


def test_log_records_carry_trace_id():
    from cilium_tpu.runtime import logging as ct_logging

    buf = io.StringIO()
    ct_logging.setup(level="info", stream=buf)
    try:
        log = ct_logging.get_logger("test")
        with TRACER.trace("req") as ctx:
            log.info("inside", extra={"fields": {"k": 1}})
        log.info("outside")
        lines = [json.loads(x) for x in
                 buf.getvalue().strip().splitlines()]
        assert lines[0]["trace_id"] == ctx.trace_id
        assert lines[0]["k"] == 1
        assert "trace_id" not in lines[1]
    finally:
        pylogging.getLogger(ct_logging.ROOT).handlers.clear()


def test_annotate_flows_stamps_trace_id_and_serde_roundtrip():
    from cilium_tpu.hubble.observer import annotate_flows
    from cilium_tpu.ingest.hubble import flow_from_dict, flow_to_dict

    flows = [_flow(1, 2, 80)]
    with TRACER.trace("req") as ctx:
        annotate_flows(flows, {"verdict": np.array([1])})
    assert flows[0].trace_id == ctx.trace_id
    d = flow_to_dict(flows[0])
    assert d["trace_id"] == ctx.trace_id
    assert flow_from_dict(d).trace_id == ctx.trace_id


def test_service_verdict_op_stamps_hubble_flow(tmp_path):
    """The full join on one id: the service verdict op's response
    trace_id appears on the Hubble-observed flow AND in the recorded
    spans."""
    from cilium_tpu.agent import Agent
    from cilium_tpu.runtime.service import VerdictClient

    agent = Agent(Config())
    try:
        agent.endpoint_add(1, {"app": "db"}, ipv4="10.0.0.9")
        dst = agent.endpoint_manager.get(1).identity
        svc = VerdictService(agent.loader,
                             str(tmp_path / "svc.sock"), agent=agent)
        svc.start()
        try:
            client = VerdictClient(svc.socket_path)
            resp = client.call({"op": "verdict", "flows": [
                {"source": {"identity": 2},
                 "destination": {"identity": int(dst)},
                 "l4": {"TCP": {"destination_port": 80}},
                 "traffic_direction": "INGRESS"}]})
            tid = resp["trace_id"]
            ring_flows = list(agent.observer.get_flows())
            assert ring_flows and ring_flows[-1].trace_id == tid
            assert TRACER.dump(trace_id=tid)
            client.close()
        finally:
            svc.stop()
    finally:
        agent.stop()


# ---------------------------------------------------------------------------
# /v1/trace REST exposure


def test_rest_trace_endpoint(tmp_path):
    from cilium_tpu.agent import Agent
    from cilium_tpu.runtime.api import APIClient, APIServer

    agent = Agent(Config())
    api = APIServer(agent, str(tmp_path / "api.sock")).start()
    try:
        with TRACER.trace("req") as ctx:
            with TRACER.span("work", phase=PHASE_HOST):
                pass
        c = APIClient(str(tmp_path / "api.sock"))
        body = c.traces()
        assert body["enabled"] is True
        assert ctx.trace_id in body["trace_ids"]
        one = c.traces(trace_id=ctx.trace_id)
        assert all(s["trace_id"] == ctx.trace_id for s in one["spans"])
        chrome = c.traces(trace_id=ctx.trace_id, chrome=True)
        assert any(e["ph"] == "X" for e in chrome["traceEvents"])
    finally:
        api.stop()
        agent.stop()


# ---------------------------------------------------------------------------
# Metrics: exposition validity + bounded histograms


def test_exposition_is_valid_prometheus_text():
    m = Metrics()
    m.inc("cilium_tpu_x_total", 3, labels={"op": "check"})
    m.set_gauge("cilium_tpu_g", 2.5)
    for v in (0.001, 0.02, 0.3, 7.0, 99.0):
        m.observe("cilium_tpu_lat_seconds", v, labels={"op": "a"})
    text = m.expose()
    assert lint_exposition(text) == []
    lines = text.splitlines()
    assert "# TYPE cilium_tpu_x_total counter" in lines
    assert "# TYPE cilium_tpu_g gauge" in lines
    assert "# TYPE cilium_tpu_lat_seconds histogram" in lines
    # cumulative buckets, +Inf terminated, _count matches
    buckets = [ln for ln in lines if "_bucket" in ln]
    assert buckets[-1].startswith(
        'cilium_tpu_lat_seconds_bucket{le="+Inf",op="a"} 5') or \
        'le="+Inf"' in buckets[-1]
    vals = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert vals == sorted(vals)
    assert 'cilium_tpu_lat_seconds_count{op="a"} 5' in lines
    # the 99.0 observation lands only in +Inf
    assert vals[-1] == 5 and vals[-2] == 4


def test_label_escaping_round_trips_the_linter():
    m = Metrics()
    m.inc("cilium_tpu_esc_total",
          labels={"path": 'a"b\\c\nd', "ok": "plain"})
    text = m.expose()
    assert lint_exposition(text) == []
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    # the raw newline must NOT appear inside the sample line
    sample = [ln for ln in text.splitlines()
              if ln.startswith("cilium_tpu_esc_total")]
    assert len(sample) == 1


def test_rest_metrics_endpoint_passes_scrape_lint(tmp_path):
    from cilium_tpu.agent import Agent
    from cilium_tpu.runtime.api import APIClient, APIServer

    agent = Agent(Config())
    api = APIServer(agent, str(tmp_path / "api.sock")).start()
    try:
        agent.endpoint_add(1, {"app": "db"}, ipv4="10.0.0.9")
        text = APIClient(str(tmp_path / "api.sock")).metrics()
        assert text.strip()
        errs = lint_exposition(text)
        assert errs == [], errs
    finally:
        api.stop()
        agent.stop()


def test_histogram_memory_is_bounded_and_quantile_works():
    from cilium_tpu.runtime.metrics import RESERVOIR

    m = Metrics()
    n = RESERVOIR * 4
    for i in range(n):
        m.observe("cilium_tpu_big_seconds", i / n)
    k = m._key("cilium_tpu_big_seconds", None)
    h = m._histos[k]
    assert h.count == n
    assert len(h.reservoir) == RESERVOIR  # bounded, not n
    assert abs(m.histo_sum("cilium_tpu_big_seconds")
               - sum(i / n for i in range(n))) < 1e-6
    # quantile answers over the recent window (the newest quarter)
    q50 = m.quantile("cilium_tpu_big_seconds", 0.5)
    assert 0.75 <= q50 <= 1.0
    # samples_since serves the tail and reports cumulative counts
    mark = m.histo_count("cilium_tpu_big_seconds")
    m.observe("cilium_tpu_big_seconds", 42.0)
    assert m.samples_since("cilium_tpu_big_seconds", mark) == [42.0]


def test_global_registry_exposition_is_clean():
    """The LIVE process registry (whatever earlier tests populated)
    must expose lint-clean — the scrape-lint lane's in-test face."""
    METRICS.inc("cilium_tpu_selftest_total")
    errs = lint_exposition(METRICS.expose())
    assert errs == [], errs


# ---------------------------------------------------------------------------
# cross-host stitching primitives (ISSUE 17): by-id remote records
# and the (epoch, ts)-ordered stitched timeline


def test_record_remote_and_event_remote_append_by_id():
    tr = Tracer()
    tid = "t" * TRACE_ID_CHARS
    tr.record_remote(tid, "serve.chunk", phase=PHASE_DEVICE, t0=1.0,
                     dur=0.5, host="hostA", epoch=1, records=8)
    tr.event_remote(tid, "fleet.handoff", host="hostA", epoch=2,
                    stream="vs0")
    span, ev = tr.dump(trace_id=tid)
    assert span["host"] == "hostA" and span["epoch"] == 1
    assert span["dur"] == 0.5 and span["attrs"]["records"] == 8
    assert ev["event"] is True and ev["epoch"] == 2
    assert span["span_id"] != ev["span_id"]
    # disabled recorder / empty trace id: both are no-ops
    tr.configure(enabled=False)
    tr.record_remote("x" * TRACE_ID_CHARS, "n")
    tr.configure(enabled=True)
    tr.record_remote("", "n")
    tr.event_remote("", "n")
    assert len(tr.dump()) == 2


def test_remote_records_keep_pre_fleet_shape_when_unset():
    """host/epoch/parent/attrs land as record keys only when set —
    pre-fleet consumers of the span shape see no new fields."""
    tr = Tracer()
    tr.record_remote("a" * TRACE_ID_CHARS, "serve.chunk", t0=0.0)
    (rec,) = tr.dump()
    for absent in ("host", "epoch", "parent", "attrs"):
        assert absent not in rec


def test_stitch_orders_by_epoch_then_ts_and_attributes_hosts():
    """The survivor's span can carry an EARLIER wall reading than the
    dead host's last span — causal epoch must win the sort."""
    tr = Tracer()
    tid = "s" * TRACE_ID_CHARS
    tr.record_remote(tid, "serve.chunk", t0=5.0, dur=0.1, host="hA")
    tr.event_remote(tid, "fleet.handoff", host="hA", epoch=1)
    tr.record_remote(tid, "serve.chunk", t0=1.0, dur=0.1, host="hB",
                     epoch=1)
    out = tr.stitch(tid)
    assert out["hosts"] == ["hA", "hB"]
    assert out["epochs"] == [0, 1]
    assert out["stitched"] is True
    epochs = [r.get("epoch", 0) for r in out["records"]]
    assert epochs == sorted(epochs)
    # the epoch-0 span leads despite its LATER timestamp
    assert out["records"][0]["ts"] == 5.0
    assert out["records"][0]["host"] == "hA"


def test_stitch_single_host_single_epoch_is_not_stitched():
    tr = Tracer()
    tid = "u" * TRACE_ID_CHARS
    tr.record_remote(tid, "serve.chunk", t0=0.0, host="hA")
    out = tr.stitch(tid)
    assert out["stitched"] is False
    assert out["hosts"] == ["hA"]
    assert out["epochs"] == [0]


# ---------------------------------------------------------------------------
# The profiler sink: program spans on the host plane


def _host_events(trace_dir):
    """``{name: [(line index, start_ns, end_ns), ...]}`` of the
    ``cilium_tpu.`` events on the host plane of a saved trace."""
    import glob
    import os

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("cilium_tpu."):
                    out.setdefault(e.name, []).append(
                        (k, int(e.start_ns),
                         int(e.start_ns + e.duration_ns)))
    return out


def test_program_spans_nest_on_the_profiler_host_plane(tmp_path):
    """An unsampled recorder span and a context-free ``.wait`` span
    inside it reach a CPU profiler trace as nested events on one host
    line, another thread's span on a line of its own, and the
    profiled-span log holds the same spans; with the profiler off,
    nothing is logged."""
    import jax

    from cilium_tpu.runtime import tracing

    def other():
        with tracing.span("test.other"):
            time.sleep(0.002)

    tracing.clear_profiled_spans()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TRACER.span("test.outer", phase=PHASE_HOST):
            with tracing.span("test.inner.wait"):
                time.sleep(0.002)
            t = threading.Thread(target=other)
            t.start()
            t.join()
    finally:
        jax.profiler.stop_trace()
    with tracing.span("test.after"):
        pass
    ev = _host_events(str(tmp_path))
    (lo, o0, o1), = ev["cilium_tpu.test.outer"]
    (li, i0, i1), = ev["cilium_tpu.test.inner.wait"]
    (lt, _, _), = ev["cilium_tpu.test.other"]
    assert li == lo and o0 <= i0 < i1 <= o1 and lt != lo
    logged = tracing.profiled_spans()
    assert [s[0] for s in logged] == ["cilium_tpu.test.inner.wait",
                                      "cilium_tpu.test.other",
                                      "cilium_tpu.test.outer"]
    inner, other_, outer = logged
    assert outer[1] <= inner[1] < inner[2] <= outer[2]
    assert inner[3] == outer[3] != other_[3]
    assert TRACER.dump() == []  # nothing was sampled
    tracing.clear_profiled_spans()


@pytest.mark.parametrize("sampled", [True, False])
def test_recorder_ring_fills_only_under_a_sampled_context(sampled):
    from cilium_tpu.runtime import tracing

    tr = Tracer(sample_rate=1.0 if sampled else 0.0)
    with tr.span("work", phase=PHASE_HOST):
        pass  # no context at all
    assert tr.dump() == []
    with tr.trace("req") as ctx:
        assert (ctx is not None) == sampled
        with tr.span("work", phase=PHASE_HOST):
            pass
        with tracing.span("structure.only"):
            pass  # context-free: profiler only, never the ring
    names = [r["name"] for r in tr.dump()]
    assert names == (["work", "req"] if sampled else [])


def test_host_sync_counts_and_spans_a_wait():
    from cilium_tpu.runtime.metrics import ENGINE_HOST_SYNCS
    from cilium_tpu.runtime.tracing import host_sync

    labels = {"site": "test.read"}
    before = METRICS.get(ENGINE_HOST_SYNCS, labels)
    with host_sync("test.read") as s:
        assert s.name == "test.read.wait"
    assert METRICS.get(ENGINE_HOST_SYNCS, labels) == before + 1
