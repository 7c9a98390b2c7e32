#!/usr/bin/env python
"""Chip smoke: the verdict hot path, once, on a TPU, through the
entry points a user calls.

One process holds the chip and runs, in order:

1. **device gate** — exit non-zero unless JAX's first device is a TPU;
2. **capture** — BASELINE configs[1] (1k HTTP path/header regex rules
   × 10k flows, shaped as ``bench.py`` builds them) through
   ``Loader`` → ``VerdictEngine`` → ``CaptureReplay`` and the fused
   megakernel, every output lane checked against the CPU oracle;
3. **families** — the ``examples/policies/`` corpus plus one
   ``l7proto`` frontend rule over 20k flows (DNS, Kafka and l7g arms),
   then the same flows on the bitset-NFA plan (``megakernel.nfa_plan``:
   the compiled Pallas NFA kernel);
4. **served** — ``VerdictService`` with ``serve.enabled``: 8 streams
   × 4 chunks through ``ServeLoop`` + ``VerdictRing``, plus ``check``
   ops through the ``MicroBatcher``;
5. **no hidden fallback** — no breaker transition, no fallback
   verdict, no failed or retried pack.

``--chips 4`` runs only the multi-chip path and what it is compared
with: four one-chip ``ServeLoop`` replicas behind ``FleetRouter``
(one ``Loader`` per device) and the DP lane over a 4-device mesh,
both against the one-chip engine.

Earlier lines carry per-phase seconds (compile apart from the rest)
and the compilations inside the replay window; the last line is the
JSON verdict ``{"ok": true, "device": {...}}``. Any failure exits
non-zero without printing it. There is no CPU mode: the CPU rehearsal
is ``tests/test_chip_smoke.py``, which calls the phase functions.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback

#: BASELINE configs[1] (bench.py ``_DEFAULT_RULES``/``_DEFAULT_FLOWS``
#: for "http")
HTTP_RULES, HTTP_FLOWS = 1000, 10000
#: phase 3: corpus tuples + l7proto frontend records (20k in all)
MIXED_FLOWS, PROTO_FLOWS = 18000, 2000
#: capture replay chunk (divides both flow counts: one compile)
REPLAY_CHUNK = 2000
#: phase 4 stream shape
STREAMS, CHUNKS_PER_STREAM, CHECK_OPS = 8, 4, 8
#: oracle wall budget before falling back to a seeded sample
ORACLE_BUDGET_S, ORACLE_SAMPLE = 60.0, 2000
#: output lanes the CPU oracle computes
ORACLE_LANES = ("verdict", "auth_required", "l7_log")
#: lanes every device path shares (``l7_match`` is group-space on the
#: fused plan and rule-space on the unfused DP step)
DEVICE_LANES = ("verdict", "allowed", "l3l4_allowed", "redirect",
                "l7_ok", "l7_log", "match_spec", "ruleset",
                "auth_required")


class SmokeFailure(Exception):
    """A phase found something wrong."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ----------------------------------------------------------- measuring --
class CompileMeter:
    """Counts XLA compilations (persistent-cache hits included), cache
    hits and compile seconds (lowering + backend compile; tracing is
    left out because nested jits report it nested) from JAX's
    monitoring events, process-wide."""

    _SECONDS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event in self._SECONDS:
            with self._lock:
                self.seconds += secs
                if event == self._SECONDS[-1]:
                    self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.compiles, self.cache_hits, self.seconds

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(
            self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


@contextlib.contextmanager
def timed_phase(name: str, meter: CompileMeter, report: dict):
    """Wall seconds of one phase, split into compile seconds and the
    rest (host work + device execution)."""
    c0, h0, s0 = meter.snapshot()
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    c1, h1, s1 = meter.snapshot()
    report[name] = {"wall_s": wall, "compile_s": s1 - s0,
                    "execute_s": wall - (s1 - s0),
                    "compiles": c1 - c0, "cache_hits": h1 - h0}
    log(f"phase {name}: wall={wall:.3f}s compile={s1 - s0:.3f}s "
        f"execute={wall - (s1 - s0):.3f}s compiles={c1 - c0} "
        f"cache_hits={h1 - h0}")


# ------------------------------------------------------------- worlds --
def http_world(n_rules: int = HTTP_RULES, n_flows: int = HTTP_FLOWS,
               seed: int = 0):
    """configs[1]: the 1k-rule HTTP path/header regex policy × 10k
    flows, as ``bench.py --config http`` builds it."""
    from cilium_tpu.ingest import synth

    scenario = synth.scenario_by_name("http", n_rules, n_flows,
                                      seed=seed)
    return synth.realize_scenario(scenario)


def families_world(n_mixed: int = MIXED_FLOWS,
                   n_proto: int = PROTO_FLOWS, seed: int = 0):
    """configs[3]'s ``examples/policies/`` corpus plus one l7proto
    frontend rule (cassandra/memcache/r2d2 port rules), one flow set."""
    from cilium_tpu.ingest import synth

    corpus = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "examples", "policies")
    mixed = synth.synth_mixed_scenario(corpus, n_tuples=n_mixed,
                                       seed=seed)
    proto = synth.synth_protocols_scenario(n_rules=12, n_flows=n_proto,
                                           seed=seed)
    for f in proto.flows:
        f._src_name, f._dst_name = "client", "polysvc"
    scenario = synth.SynthScenario(
        name="families", rules=mixed.rules + proto.rules,
        endpoints={**mixed.endpoints, **proto.endpoints},
        flows=mixed.flows + proto.flows)
    return synth.realize_scenario(scenario)


def stage_engine(per_identity, workdir: str, device, serve: bool = False,
                 **engine_cfg):
    """``Loader(enable_tpu_offload=True)`` on ``device`` → the serving
    ``VerdictEngine``, with its staged arrays checked to live on
    ``device`` and its per-field kernel arms printed. ``engine_cfg``
    overrides ``EngineConfig`` fields."""
    from cilium_tpu.core.config import Config
    from cilium_tpu.engine.verdict import VerdictEngine
    from cilium_tpu.runtime.loader import Loader

    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.serve.enabled = serve
    cfg.loader.cache_dir = os.path.join(workdir, "artifacts")
    for k, v in engine_cfg.items():
        setattr(cfg.engine, k, v)
    loader = Loader(cfg, device=device)
    engine = loader.regenerate(per_identity, revision=1)
    check(isinstance(engine, VerdictEngine),
          f"serving engine is {type(engine).__name__}, not VerdictEngine")
    off = sorted(k for k, a in engine._arrays.items()
                 if a.devices() != {device})
    check(not off, f"staged arrays not on {device}: {off[:5]}")
    log_kernel_plan(engine)
    return loader, engine


def log_kernel_plan(engine) -> list:
    """Print each field's planned scan arm; returns the fields whose
    NFA arm runs the Pallas kernel (on a TPU backend)."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    pallas = []
    for field, rep in sorted(engine.kernel_report.items()):
        compiled = rep["impl"] == "nfa-bitset" and on_tpu
        if compiled:
            pallas.append(field)
        log(f"kernel {field}: arm={rep['impl']}"
            f"{' (compiled Pallas NFA)' if compiled else ''} "
            f"banks={rep['banks']} dfa_states={rep['dfa_states']} "
            f"nfa_positions={rep['nfa_positions']}")
    log(f"compiled Pallas NFA arm: {pallas or 'no field'}")
    return pallas


# ------------------------------------------------------------ checking --
def oracle_reference(per_identity, flows, seed: int = 0,
                     budget_s: float = ORACLE_BUDGET_S,
                     sample: int = ORACLE_SAMPLE):
    """CPU-oracle lanes over every flow, or over a seeded sample of at
    least ``sample`` flows when the whole set would pass ``budget_s``.
    Returns ``(indices, lanes)``."""
    import numpy as np

    from cilium_tpu.policy.oracle import OracleVerdictEngine

    oracle = OracleVerdictEngine(per_identity)
    n = len(flows)
    probe = min(n, 200)
    t0 = time.perf_counter()
    head = oracle.verdict_flows(flows[:probe])
    est = (time.perf_counter() - t0) / probe * n
    if est <= budget_s or n <= sample:
        idx = np.arange(n)
        rest = oracle.verdict_flows(flows[probe:])
        lanes = {k: np.concatenate([head[k], rest[k]]) for k in head}
        log(f"oracle: all {n} flows")
        return idx, lanes
    rng = np.random.default_rng(seed)
    idx = np.sort(rng.choice(n, size=sample, replace=False))
    log(f"oracle: seeded sample of {sample} of {n} flows (seed={seed}; "
        f"the whole set would take ~{est:.0f}s)")
    return idx, oracle.verdict_flows([flows[i] for i in idx])


def compare_lanes(label: str, got, want, lanes, idx=None) -> int:
    """Mismatches per lane between ``got`` (all flows, or ``idx``'s
    positions of them) and ``want``; fails on any."""
    import numpy as np

    bad = {}
    for k in lanes:
        g = np.asarray(got[k])
        if idx is not None:
            g = g[idx]
        w = np.asarray(want[k])
        check(g.shape == w.shape,
              f"{label}: lane {k} shape {g.shape} != {w.shape}")
        bad[k] = int((g.astype(np.int64) != w.astype(np.int64)).sum())
    total = sum(bad.values())
    log(f"{label}: {len(lanes)} lanes x {len(want[lanes[0]])} flows, "
        f"mismatches={total} {bad}")
    check(total == 0, f"{label}: {total} mismatches {bad}")
    return total


# -------------------------------------------------------------- phases --
def phase_replay(name: str, per_identity, flows, workdir: str, device,
                 meter: CompileMeter, report: dict, seed: int = 0,
                 chunk: int = REPLAY_CHUNK) -> dict:
    """Stage the policy, write the flows as a capture, replay it through
    ``CaptureReplay`` (the ``bench.py`` e2e path) chunk by chunk and
    run the fused megakernel over the same flows; every lane of both
    must equal the oracle's, and each other's. Returns the replay's
    host lanes with the oracle's (``lanes``, ``oracle_idx``,
    ``oracle``)."""
    import numpy as np

    from cilium_tpu.engine.verdict import CaptureReplay
    from cilium_tpu.ingest import binary

    with timed_phase(f"{name}.oracle", meter, report):
        idx, want = oracle_reference(per_identity, flows, seed=seed)
    with timed_phase(f"{name}.stage", meter, report):
        loader, engine = stage_engine(per_identity, workdir, device)
        path = os.path.join(workdir, f"{name}.cap")
        binary.write_capture_l7(path, flows)
        if binary._native() is None:
            codec = "unavailable (numpy codec)"
        elif binary.native_built_this_run:
            codec = "built by this run"
        else:
            codec = "prebuilt"
        log(f"capture {path}: v{binary.capture_version(path)}, "
            f"{len(flows)} records; native codec {codec}")
        rec = binary.map_capture(path)
        l7, offsets, blob = binary.read_l7_sidecar(path)
        replay = CaptureReplay(engine, l7, offsets, blob,
                               loader.config.engine,
                               gen=binary.read_gen_sidecar(path))
        replay.stage_rows(rec, l7)
        ratio = replay.stage_unique(
            drop_if_ratio_at_least=(
                loader.config.engine.stage_unique_drop_ratio))
        log(f"{name}: unique rows {replay.n_unique}/{len(rec)} "
            f"({ratio:.4f}) → "
            f"{'id' if replay.row_idx is not None else 'row'} stream")
    n = len(rec)
    check(n % chunk == 0, f"{n} records do not split into {chunk}s")
    with timed_phase(f"{name}.warm", meter, report):
        replay.verdict_chunk(rec[:chunk], l7[:chunk], start=0)
    c0 = meter.snapshot()[0]
    with timed_phase(f"{name}.replay", meter, report):
        outs = [replay.verdict_chunk(rec[s:s + chunk], l7[s:s + chunk],
                                     start=s)
                for s in range(0, n, chunk)]
    window_compiles = meter.snapshot()[0] - c0
    log(f"{name}: compilations in the replay window: {window_compiles}")
    got = {k: np.concatenate([np.asarray(o[k]) for o in outs])
           for k in outs[0]}
    compare_lanes(f"{name} replay vs oracle", got, want, ORACLE_LANES,
                  idx)
    with timed_phase(f"{name}.fused", meter, report):
        fused = engine.verdict_flows(flows)
    compare_lanes(f"{name} fused step vs oracle", fused, want,
                  ORACLE_LANES, idx)
    compare_lanes(f"{name} replay vs fused step", got, fused,
                  DEVICE_LANES + ("l7_match",))
    report[f"{name}.replay"]["window_compiles"] = window_compiles
    return {"lanes": got, "oracle_idx": idx, "oracle": want}


def phase_nfa_arm(per_identity, flows, replayed, workdir: str, device,
                  meter: CompileMeter, report: dict) -> list:
    """The fused step on the bitset-NFA plan (``megakernel.nfa_plan``:
    every field whose banks fit the arm's 128-position tile, at bank
    size 4): on a TPU this compiles and runs the Pallas NFA kernel,
    which the rule's plan at the other phases' policies never reaches.
    Every lane must equal the replay's (``replayed``: the
    ``phase_replay`` result, itself checked against the oracle).
    Returns the fields that took the arm."""
    from cilium_tpu.engine.megakernel import IMPL_NFA, nfa_plan

    with timed_phase("nfa_arm.fused", meter, report):
        loader, engine = stage_engine(per_identity, workdir, device,
                                      bank_size=4)
        engine.stage_plan(nfa_plan(engine.policy, loader.config.engine))
        log_kernel_plan(engine)
        out = engine.verdict_flows(flows)
    arm = sorted(f for f, i in engine.impl_plan.items() if i == IMPL_NFA)
    check(arm, "no field took the NFA arm")
    compare_lanes("nfa arm vs oracle", out, replayed["oracle"],
                  ORACLE_LANES, replayed["oracle_idx"])
    compare_lanes("nfa arm vs replay", out, replayed["lanes"],
                  DEVICE_LANES)
    return arm


def phase_served(per_identity, flows, want_verdict, workdir: str,
                 device, meter: CompileMeter, report: dict,
                 oracle_idx=None, oracle_verdict=None,
                 streams: int = STREAMS,
                 chunks: int = CHUNKS_PER_STREAM,
                 checks: int = CHECK_OPS) -> dict:
    """``VerdictService`` on a unix socket with the serve loop on:
    ``StreamClient`` streams through ``ServeLoop`` + ``VerdictRing``
    and ``check`` ops through the ``MicroBatcher``; verdicts must equal
    ``want_verdict`` (the replay's, every flow) and the oracle's. Then
    the no-fallback audit (phase 5). Returns the audit."""
    import numpy as np

    from cilium_tpu.ingest.hubble import flow_to_dict
    from cilium_tpu.runtime.metrics import (
        BREAKER_FALLBACK_VERDICTS,
        BREAKER_TRIPS,
        METRICS,
    )
    from cilium_tpu.runtime.service import VerdictClient, VerdictService
    from cilium_tpu.runtime.stream import StreamClient

    trips0 = METRICS.get(BREAKER_TRIPS)
    fallback0 = METRICS.get(BREAKER_FALLBACK_VERDICTS)
    sock_dir = tempfile.mkdtemp(prefix="cts")
    with timed_phase("served.stage", meter, report):
        loader, _ = stage_engine(per_identity, workdir, device,
                                 serve=True)
        svc = VerdictService(loader, os.path.join(sock_dir, "v.sock"))
        svc.start()
    try:
        pieces = np.array_split(np.arange(len(flows)), streams * chunks)
        got = np.full(len(flows), -1, np.int64)
        with timed_phase("served.streams", meter, report):
            clients = [StreamClient(svc.socket_path)
                       for _ in range(streams)]
            sent = []
            for c in range(chunks):
                for s, cl in enumerate(clients):
                    ix = pieces[s * chunks + c]
                    sent.append((ix, cl, cl.send_flows(
                        [flows[i] for i in ix])))
            for ix, cl, seq in sent:
                got[ix] = np.asarray(cl.result(seq))
            for cl in clients:
                cl.finish()
                cl.close()
        check(svc.serveloop is not None,
              "streams did not ride the serve loop (serveloop is None)")
        bad = int((got != np.asarray(want_verdict)).sum())
        log(f"served streams: {streams}x{chunks} chunks, {len(flows)} "
            f"flows, mismatches vs replay={bad}")
        check(bad == 0, f"served streams: {bad} mismatches vs replay")
        if oracle_idx is not None:
            compare_lanes("served streams vs oracle", {"verdict": got},
                          {"verdict": oracle_verdict}, ("verdict",),
                          oracle_idx)
        with timed_phase("served.checks", meter, report):
            vc = VerdictClient(svc.socket_path)
            step = max(1, len(flows) // checks)
            check_ix = list(range(0, len(flows), step))[:checks]
            answers = [vc.call({"op": "check",
                                "flow": flow_to_dict(flows[i])})
                       for i in check_ix]
            vc.close()
        bad = [(i, a) for i, a in zip(check_ix, answers)
               if a.get("verdict") != int(want_verdict[i])]
        log(f"check ops: {len(check_ix)} through the MicroBatcher, "
            f"mismatches={len(bad)}")
        check(not bad, f"check ops disagree: {bad[:3]}")
        st = svc.serveloop.status()
        audit = {
            "breaker_trips": METRICS.get(BREAKER_TRIPS) - trips0,
            "breaker_transitions": list(svc.verdictor.breaker.events),
            "fallback_verdicts": (METRICS.get(BREAKER_FALLBACK_VERDICTS)
                                  - fallback0),
            "pack_failures": st["pack_failures"],
            "chunk_errors": st["chunk_errors"],
            "sheds": st["sheds"], "grants": st["grants"],
            "packs": st["packs"],
        }
    finally:
        svc.stop()
        shutil.rmtree(sock_dir, ignore_errors=True)
    log(f"no-fallback audit: {json.dumps(audit)}")
    check(audit["breaker_trips"] == 0
          and not audit["breaker_transitions"],
          f"breaker opened: {audit}")
    check(audit["fallback_verdicts"] == 0,
          f"verdicts came from the fallback lane: {audit}")
    check(audit["pack_failures"] == 0 and audit["chunk_errors"] == 0,
          f"a pack failed or was retried: {audit}")
    check(audit["sheds"] == 0 and audit["grants"] >= streams,
          f"a stream missed its ring lease: {audit}")
    return audit


def phase_fleet(per_identity, flows, want_verdict, devices,
                workdir: str, meter: CompileMeter, report: dict,
                streams: int = 16, chunks: int = 4) -> dict:
    """Four one-chip ``ServeLoop`` replicas behind ``FleetRouter``,
    each ``Loader`` on its own device; verdicts must equal the
    one-chip engine's."""
    import numpy as np

    from cilium_tpu.ingest.binary import capture_from_bytes, capture_to_bytes
    from cilium_tpu.runtime.fleetserve import FleetRouter, HostReplica

    with timed_phase("fleet.stage", meter, report):
        loaders = [stage_engine(per_identity, workdir, d)[0]
                   for d in devices]
        replicas = [HostReplica(i, ld, capacity=streams)
                    for i, ld in enumerate(loaders)]
        router = FleetRouter(replicas)
    placed = {r.name: str(r.loader.device) for r in replicas}
    log(f"fleet replicas: {placed}")
    check(len(set(placed.values())) == len(devices),
          f"replicas share devices: {placed}")
    pieces = np.array_split(np.arange(len(flows)), streams * chunks)
    got = np.full(len(flows), -1, np.int64)
    with timed_phase("fleet.serve", meter, report):
        leases = [router.connect(f"s{s}") for s in range(streams)]
        tickets = []
        for c in range(chunks):
            for s, (_, lease) in enumerate(leases):
                ix = pieces[s * chunks + c]
                sections = capture_from_bytes(
                    capture_to_bytes([flows[i] for i in ix]))
                tickets.append((ix, router.submit(f"s{s}", lease,
                                                  sections)))
        while not all(t.done for _, t in tickets):
            router.step_all()
        for ix, t in tickets:
            check(t.error is None, f"fleet chunk failed: {t.error}")
            got[ix] = np.asarray(t.verdicts)
    hosts = {h for h, _ in leases}
    per_host = {r.name: r.loop.status()["served_records"]
                for r in replicas}
    log(f"fleet: {streams} streams on {len(hosts)} hosts, served "
        f"records per host {per_host}")
    check(len(hosts) == len(devices),
          f"streams placed on {len(hosts)} of {len(devices)} hosts")
    check(all(r.loop.status()["pack_failures"] == 0 for r in replicas),
          "a fleet pack failed")
    bad = int((got != np.asarray(want_verdict)).sum())
    log(f"fleet vs one-chip engine: {len(flows)} flows, "
        f"mismatches={bad}")
    check(bad == 0, f"fleet: {bad} mismatches vs the one-chip engine")
    return {"hosts": len(hosts), "per_host": per_host}


def phase_dp(engine, flows, devices, meter: CompileMeter,
             report: dict, want=None) -> None:
    """The ``[parallel] lane = dp`` step over a mesh of ``devices``,
    bit-equal on every shared lane to the one-chip engine (``want``,
    computed here when not given)."""
    import numpy as np

    from cilium_tpu.core.config import Config
    from cilium_tpu.engine.verdict import encode_flows, flowbatch_to_host_dict
    from cilium_tpu.parallel.sharding import stage_for_lane

    cfg = Config()
    cfg.parallel.lane = "dp"
    n = len(flows) - len(flows) % len(devices)
    with timed_phase("dp.step", meter, report):
        host = flowbatch_to_host_dict(encode_flows(
            flows[:n], engine.policy.kafka_interns, cfg.engine))
        step, arrays, batch = stage_for_lane(cfg, engine.policy.arrays,
                                             host, devices=devices)
        out = step(arrays, batch)
        out = {k: np.asarray(v) for k, v in out.items()}
    spread = len(step(arrays, batch)["verdict"].sharding.device_set)
    log(f"dp lane: {n} flows over a {len(devices)}-device mesh "
        f"(output on {spread} devices)")
    check(spread == len(devices), f"dp output on {spread} devices")
    if want is None:
        want = engine.verdict_flows(flows[:n])
    compare_lanes("dp lane vs one-chip engine", out,
                  {k: np.asarray(want[k])[:n] for k in DEVICE_LANES},
                  DEVICE_LANES)


# ---------------------------------------------------------------- main --
def device_gate(chips: int):
    """The TPU devices, or exit non-zero before any other work."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              f"this script runs on the chip only", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} devices, "
              f"JAX found {len(devs)}", file=sys.stderr)
        sys.exit(2)
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — the version is informational
        libtpu = "unknown"
    log(f"device: platform={devs[0].platform} "
        f"kind={devs[0].device_kind} count={len(devs)} "
        f"jax={jax.__version__} libtpu={libtpu}")
    return devs


def run(chips: int, devices, seed: int = 0) -> dict:
    from cilium_tpu.runtime.xla_cache import cache_dir

    meter = CompileMeter()
    report: dict = {}
    log(f"compile cache: {cache_dir()}")
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.perf_counter()
    try:
        per_http, http = http_world(seed=seed)
        if chips == 1:
            cap = phase_replay("capture", per_http, http.flows,
                               workdir, devices[0], meter, report,
                               seed=seed)
            per_fam, fam = families_world(seed=seed)
            famr = phase_replay("families", per_fam, fam.flows,
                                workdir, devices[0], meter, report,
                                seed=seed)
            phase_nfa_arm(per_fam, fam.flows, famr, workdir, devices[0],
                          meter, report)
            phase_served(per_http, http.flows, cap["lanes"]["verdict"],
                         workdir, devices[0], meter, report,
                         oracle_idx=cap["oracle_idx"],
                         oracle_verdict=cap["oracle"]["verdict"])
        else:
            with timed_phase("one_chip.reference", meter, report):
                _, engine = stage_engine(per_http, workdir, devices[0])
                ref = engine.verdict_flows(http.flows)
            phase_fleet(per_http, http.flows, ref["verdict"],
                        devices[:chips], workdir, meter, report)
            phase_dp(engine, http.flows, devices[:chips], meter, report,
                     want=ref)
    finally:
        meter.close()
        shutil.rmtree(workdir, ignore_errors=True)
    c, h, s = meter.snapshot()
    log(f"total: wall={time.perf_counter() - t0:.3f}s compiles={c} "
        f"cache_hits={h} compile_s={s:.3f}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the multi-chip path (fleet replicas "
                         "+ DP lane) and its one-chip reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = device_gate(args.chips)
    try:
        run(args.chips, devices, seed=args.seed)
    except Exception:  # noqa: BLE001 — any failure fails the smoke
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
