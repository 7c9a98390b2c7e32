"""Device-time attribution: where a verdict batch actually spends it.

The jitted hot path is ONE fused program by design (that is the whole
perf story), so per-phase numbers cannot come from instrumenting the
hot path — they come from a **probe** that re-runs the same staged
batch through separately-jitted sub-steps, each ending in a forced
2-element readback (the bench ``_force`` contract):

=================  ======================================================
``featurize``      host encode: flows → packed numpy batch
``h2d``            host→device transfer of the packed batch, forced
``mapstate``       the L3/L4 mapstate gather (``mapstate_kernel``)
``dfa-scan``       the five per-field banked DFA scans (live path), or
``gather``         the staged-table match-word gathers (capture path)
``resolve``        per-rule conjunction → ruleset-any → priority/auth/
                   audit (the LEGACY formulation — the three-op
                   baseline the megakernel is judged against)
``fused-verdict``  the engine's staged megakernel step
                   (``engine/megakernel.py``): mapstate + scans +
                   factored resolve in ONE device dispatch, where the
                   three rows above are three
``dfa-dense``      the planned fields' scans through the dense-gather
                   DFA arm, per the engine's kernel plan
``nfa-bitset``     the planned fields' scans through the bitset-NFA
                   rules-as-lanes arm (only reported when the plan
                   uses it)
``compile``        first-call cost minus steady-state (the compile
                   half of the compile-vs-execute split)
``execute``        steady-state fused-step wall (the execute half)
=================  ======================================================

Coverage contract: ``attributed / wall``. Sub-steps jitted separately
forgo cross-phase fusion, so the device-side decomposition sums to
≥ the fused step on every platform measured — a coverage below ~0.9
means a phase is MISSING from the decomposition, which is exactly what
the number exists to catch. (With the megakernel staged, wall is the
ONE-dispatch fused step, so coverage well above 1 is the speedup
showing.) ``three_op_ms``/``fused_ms``/``fused_speedup`` on the
report carry the dispatch-count story explicitly:
``three_op_dispatches`` is 3 (mapstate, scan, resolve, each
completion-forced), ``fused_dispatches`` is 1. Results feed the
flight recorder (``runtime/tracing.py`` spans under an
``engine.phase_probe`` root) and the
``cilium_tpu_engine_phase_seconds{phase=...}`` family — and the bench
artifacts, where ROADMAP's open perf items (megakernel, multichip)
are judged against them.

This is an inspection instrument, not a hot-path layer: nothing here
runs per request.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from cilium_tpu.core.flow import TrafficDirection
from cilium_tpu.engine.dfa_kernel import dfa_scan_banked
from cilium_tpu.engine.mapstate_kernel import mapstate_lookup
from cilium_tpu.engine.verdict import (
    _ROW_COLS,
    _verdict_core,
    batch_field,
    encode_flows,
    flowbatch_to_host_dict,
    unpack_batch,
)
from cilium_tpu.runtime import simclock
from cilium_tpu.runtime.metrics import (ENGINE_HOST_SYNCS,
                                        ENGINE_PHASE_SECONDS, METRICS)
from cilium_tpu.runtime.tracing import PHASE_DEVICE, PHASE_HOST, TRACER

#: phase label values the probes emit (obs-doc-parity: each must be
#: documented in docs/OBSERVABILITY.md)
ENGINE_PHASES = ("featurize", "h2d", "mapstate", "dfa-scan", "resolve",
                 "fused-verdict", "dfa-dense", "nfa-bitset",
                 "compile", "execute")
CAPTURE_PHASES = ("gather", "mapstate", "resolve")


def _force(out, site: str = "") -> None:
    """Force remote completion via a tiny readback of the first array
    leaf (in-order queue: the last op's readback implies the rest).
    Each call is an INTENTIONAL host↔device sync — counted under
    ``cilium_tpu_engine_host_syncs_total{site=…}`` so the allowlisted
    sync points the ctlint device-dataflow family exempts stay
    observable at runtime (docs/ANALYSIS.md v4)."""
    METRICS.inc(ENGINE_HOST_SYNCS, labels={"site": site or "probe"})
    leaf = out
    while isinstance(leaf, dict):
        leaf = leaf[sorted(leaf)[0]]
    while isinstance(leaf, (tuple, list)):
        leaf = leaf[0]
    np.asarray(leaf[:2] if getattr(leaf, "ndim", 0) else leaf)


def _timed(fn, reps: int, site: str = ""):
    """(steady median s, first-call s, last output). The first call
    compiles; steady is the median of ``reps`` forced calls."""
    t0 = time.perf_counter()
    out = fn()
    _force(out, site)
    first = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        _force(out, site)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2], first, out


def _unpacked(batch):
    return unpack_batch(batch) if "scalars" in batch else batch


def _live_mapstate(arrays, batch):
    b = _unpacked(batch)
    return mapstate_lookup(
        arrays["ms_key_w0"], arrays["ms_key_w1"], arrays["ms_key_w2"],
        arrays["ms_deny"], arrays["ms_ruleset"],
        arrays["ms_enf_ids"], arrays["ms_enf_flags"],
        b["ep_ids"], b["peer_ids"], b["dports"],
        b["protos"], b["directions"],
        auth=arrays.get("ms_auth"),
        port_plens=arrays.get("ms_plens"),
        tmpl_ids=arrays.get("ms_tmpl_ids"))


def _live_scan(arrays, batch):
    b = _unpacked(batch)

    def scan_field(prefix, data, lengths, valid):
        words = dfa_scan_banked(
            arrays[f"{prefix}_trans"], arrays[f"{prefix}_byteclass"],
            arrays[f"{prefix}_start"], arrays[f"{prefix}_accept"],
            data, lengths)
        flat = words.reshape(words.shape[0], -1)
        return jnp.where(valid[:, None], flat, 0)

    words = (scan_field("path", *batch_field(b, "path")),
             scan_field("method", *batch_field(b, "method")),
             scan_field("host", *batch_field(b, "host")),
             scan_field("hdr", *batch_field(b, "headers")),
             scan_field("dns", *batch_field(b, "qname")))
    if "l7g_trans" in arrays:   # frontend automaton staged (static)
        words = words + (scan_field("l7g", *batch_field(b, "l7g")),)
    return words


def _live_resolve(arrays, ms, words, batch):
    b = _unpacked(batch)
    ingress = b["directions"] == int(TrafficDirection.INGRESS)
    src = jnp.where(ingress, b["peer_ids"], b["ep_ids"])
    dst = jnp.where(ingress, b["ep_ids"], b["peer_ids"])
    return _verdict_core(
        arrays, ms, b["l7_types"], words,
        (b["kafka_api_key"], b["kafka_api_version"],
         b["kafka_client"], b["kafka_topic"]),
        (src, dst), b, gen_cols=(b["gen_proto"], b["gen_pairs"]))


def _cap_rows(batch):
    rows = batch["rows"]
    idx = batch.get("idx")
    if idx is not None:
        rows = rows[idx.astype(jnp.int32)]
    return rows


def _cap_gather(table_words, batch):
    rows = _cap_rows(batch)
    col = {c: i for i, c in enumerate(_ROW_COLS)}
    words = tuple(
        table_words[field][rows[:, col[f"{field}_row"]]]
        for field in ("path", "method", "host", "headers", "qname"))
    # ctlint: disable=recompile-hazard  # row width is static per capture layout: one compile per layout, by design
    if "l7g" in table_words and rows.shape[1] > len(_ROW_COLS):
        # frontend serialized-record words ride the gen block's l7g
        # row column (gen layout: proto, family, l7g row, pairs...)
        words = words + (
            table_words["l7g"][rows[:, len(_ROW_COLS) + 2]],)
    return rows, words


def _cap_mapstate(arrays, batch):
    rows = _cap_rows(batch)
    col = {c: i for i, c in enumerate(_ROW_COLS)}
    return mapstate_lookup(
        arrays["ms_key_w0"], arrays["ms_key_w1"], arrays["ms_key_w2"],
        arrays["ms_deny"], arrays["ms_ruleset"],
        arrays["ms_enf_ids"], arrays["ms_enf_flags"],
        rows[:, col["ep_ids"]], rows[:, col["peer_ids"]],
        rows[:, col["dports"]], rows[:, col["protos"]],
        rows[:, col["directions"]],
        auth=arrays.get("ms_auth"),
        port_plens=arrays.get("ms_plens"),
        tmpl_ids=arrays.get("ms_tmpl_ids"))


def _cap_resolve(arrays, ms, rows, words, batch):
    col = {c: i for i, c in enumerate(_ROW_COLS)}

    def c(name):
        return rows[:, col[name]]

    ingress = c("directions") == int(TrafficDirection.INGRESS)
    src = jnp.where(ingress, c("peer_ids"), c("ep_ids"))
    dst = jnp.where(ingress, c("ep_ids"), c("peer_ids"))
    n = len(_ROW_COLS)
    # ctlint: disable=recompile-hazard  # row width is static per capture layout: one compile per layout, by design
    gen_cols = ((rows[:, n], rows[:, n + 3:])
                if rows.shape[1] > n else None)
    return _verdict_core(
        arrays, ms, c("l7_types"), words,
        (c("kafka_api_key"), c("kafka_api_version"),
         c("kafka_client"), c("kafka_topic")),
        (src, dst), batch, gen_cols=gen_cols)


def _record(report: Dict, reps: int) -> None:
    """Publish a probe report into METRICS + the flight recorder."""
    now = simclock.wall()
    with TRACER.trace("engine.phase_probe", batch=report.get("batch"),
                      reps=reps) as ctx:
        for phase, ms in report["phases_ms"].items():
            METRICS.observe(ENGINE_PHASE_SECONDS, ms / 1e3,
                            labels={"phase": phase})
            TRACER.add_span(
                ctx, f"engine.phase.{phase}",
                PHASE_HOST if phase == "featurize" else PHASE_DEVICE,
                now, ms / 1e3)
        for phase, key in (("compile", "compile_ms"),
                           ("execute", "execute_ms")):
            if report.get(key) is not None:
                METRICS.observe(ENGINE_PHASE_SECONDS,
                                report[key] / 1e3,
                                labels={"phase": phase})


def _impl_scan(arrays, batch, impl_plan, wanted: str):
    """Scan only the fields the engine's kernel plan runs through
    ``wanted`` — the per-impl attribution lanes (dfa-dense /
    nfa-bitset phase labels)."""
    from cilium_tpu.engine.megakernel import fused_scan_field, scan_fields

    b = _unpacked(batch)
    impls = dict(impl_plan)
    out = []
    for prefix, field in scan_fields(arrays):
        if impls.get(prefix, "dfa-dense") != wanted:
            continue
        w, _ = fused_scan_field(
            arrays, prefix, *batch_field(b, field), impl=wanted)
        out.append(w)
    return tuple(out)


#: jitted once at module scope — per-call wrapping would churn the jit
#: cache (the recompile-hazard rule's own lesson)
_IMPL_SCAN = jax.jit(_impl_scan, static_argnums=(2, 3))


class EnginePhaseProbe:
    """Per-phase attribution of the LIVE verdict path (featurize →
    h2d → mapstate → dfa-scan → resolve, plus the fused megakernel
    step and its per-impl scan lanes) for one engine."""

    def __init__(self, engine):
        self.engine = engine
        self._ms = jax.jit(_live_mapstate)
        self._scan = jax.jit(_live_scan)
        self._resolve = jax.jit(_live_resolve)
        # the engine's STAGED step (the fused megakernel) — the wall
        # the decomposition covers
        self._full = engine._step
        self._impl_plan = tuple(sorted(
            getattr(engine, "impl_plan", {}).items()))

    def measure_flows(self, flows: Sequence, cfg=None, reps: int = 5
                      ) -> Dict:
        """Featurize ``flows`` (timed: the ``featurize`` phase), then
        :meth:`measure` the resulting packed batch."""
        t0 = time.perf_counter()
        host = flowbatch_to_host_dict(
            encode_flows(flows, self.engine.policy.kafka_interns, cfg))
        feat_ms = (time.perf_counter() - t0) * 1e3
        report = self.measure(host, reps=reps, _defer_record=True)
        report["phases_ms"]["featurize"] = round(feat_ms, 3)
        report["attributed_ms"] = round(
            report["attributed_ms"] + feat_ms, 3)
        _record(report, reps)
        return report

    def measure(self, host_batch: Dict[str, np.ndarray], reps: int = 5,
                authed_pairs=None, _defer_record: bool = False) -> Dict:
        """``host_batch`` is the packed host layout
        (:func:`flowbatch_to_host_dict`). Returns the phase report;
        also records it (metrics + tracer spans)."""
        engine, arrays = self.engine, self.engine._arrays

        def put():
            batch = {k: jax.device_put(v, engine.device)
                     for k, v in host_batch.items()}
            engine._stage_auth(batch, authed_pairs)
            return batch

        h2d_s, _, batch = _timed(put, reps, site="engine-h2d")
        ms_s, _, ms = _timed(lambda: self._ms(arrays, batch), reps,
                             site="engine-mapstate")
        scan_s, _, words = _timed(lambda: self._scan(arrays, batch),
                                  reps, site="engine-dfa-scan")
        res_s, _, _ = _timed(
            lambda: self._resolve(arrays, ms, words, batch), reps,
            site="engine-resolve")
        full_s, full_first, _ = _timed(
            lambda: self._full(arrays, batch), reps,
            site="engine-fused-verdict")

        # the three-op baseline the megakernel replaces: mapstate →
        # scan → resolve as three completion-forced device dispatches
        # (the pre-fused execution shape, HBM round-trips included)
        def three_op():
            m = self._ms(arrays, batch)
            _force(m, "engine-three-op")
            w = self._scan(arrays, batch)
            _force(w, "engine-three-op")
            return self._resolve(arrays, m, w, batch)

        three_s, _, _ = _timed(three_op, reps, site="engine-three-op")

        phases_ms = {"h2d": round(h2d_s * 1e3, 3),
                     "mapstate": round(ms_s * 1e3, 3),
                     "dfa-scan": round(scan_s * 1e3, 3),
                     "resolve": round(res_s * 1e3, 3),
                     "fused-verdict": round(full_s * 1e3, 3)}
        # per-impl scan lanes, per the engine's kernel plan
        for impl in sorted({v for _, v in self._impl_plan} or
                           {"dfa-dense"}):
            impl_s, _, _ = _timed(
                lambda: _IMPL_SCAN(arrays, batch, self._impl_plan, impl),
                reps, site="engine-impl-scan")
            phases_ms[impl] = round(impl_s * 1e3, 3)
        attributed = (ms_s + scan_s + res_s) * 1e3
        report = {
            "batch": int(len(host_batch["scalars"])),
            "phases_ms": phases_ms,
            "wall_ms": round(full_s * 1e3, 3),
            "attributed_ms": round(attributed, 3),
            "coverage": round(attributed / max(full_s * 1e3, 1e-9), 4),
            "compile_ms": round(max(0.0, full_first - full_s) * 1e3, 3),
            "execute_ms": round(full_s * 1e3, 3),
            # the dispatch-count story the megakernel exists for: ONE
            # device dispatch where the baseline pays three
            "fused_ms": round(full_s * 1e3, 3),
            "fused_dispatches": 1,
            "three_op_ms": round(three_s * 1e3, 3),
            "three_op_dispatches": 3,
            "fused_speedup": round(three_s / max(full_s, 1e-9), 3),
            "impl_plan": dict(self._impl_plan),
        }
        if not _defer_record:
            _record(report, reps)
        return report


class CapturePhaseProbe:
    """Per-phase attribution of the CAPTURE-REPLAY path (h2d →
    gather → mapstate → resolve) for one staged
    :class:`~cilium_tpu.engine.verdict.CaptureReplay` session."""

    def __init__(self, replay):
        self.replay = replay
        self._gather = jax.jit(_cap_gather)
        self._ms = jax.jit(_cap_mapstate)
        self._resolve = jax.jit(_cap_resolve)
        # the session's staged step (fused when the policy carries a
        # resolve plan) — the wall the decomposition covers
        self._full = replay._step

    def measure(self, start: int = 0, n: Optional[int] = None,
                reps: int = 5, authed_pairs=None) -> Dict:
        """Attribute one chunk (records ``[start:start+n]`` of the
        staged capture; dedup id stream when the session staged one)."""
        replay, engine = self.replay, self.replay.engine
        arrays = engine._arrays
        assert replay.rows_all is not None, "stage_rows first"
        n = n if n is not None else min(len(replay.rows_all), 8192)

        if replay.row_idx is not None:
            idx_host = replay.row_idx[start:start + n]
            table = replay.stage_unique_device()

            def put():
                batch = {"rows": table,
                         "idx": jax.device_put(idx_host, engine.device)}
                engine._stage_auth(batch, authed_pairs)
                return batch
        else:
            rows_host = replay.rows_all[start:start + n]

            def put():
                batch = {"rows": jax.device_put(rows_host,
                                                engine.device)}
                engine._stage_auth(batch, authed_pairs)
                return batch

        h2d_s, _, batch = _timed(put, reps, site="capture-h2d")
        tw = replay.table_words

        # the end-to-end chunk wall the phases must cover: fresh H2D +
        # fused step + forced completion, as the replay loop pays it
        def chunk():
            return self._full(arrays, tw, put())

        wall_s, wall_first, _ = _timed(chunk, reps,
                                       site="capture-chunk")
        g_s, _, (rows, words) = _timed(
            lambda: self._gather(tw, batch), reps,
            site="capture-gather")
        ms_s, _, ms = _timed(lambda: self._ms(arrays, batch), reps,
                             site="capture-mapstate")
        res_s, _, _ = _timed(
            lambda: self._resolve(arrays, ms, rows, words, batch),
            reps, site="capture-resolve")
        step_s, _, _ = _timed(
            lambda: self._full(arrays, tw, batch), reps,
            site="capture-step")

        phases_ms = {"h2d": round(h2d_s * 1e3, 3),
                     "gather": round(g_s * 1e3, 3),
                     "mapstate": round(ms_s * 1e3, 3),
                     "resolve": round(res_s * 1e3, 3)}
        attributed = (h2d_s + g_s + ms_s + res_s) * 1e3
        report = {
            "batch": int(n),
            "stream": "id" if replay.row_idx is not None else "row",
            "phases_ms": phases_ms,
            "wall_ms": round(wall_s * 1e3, 3),
            "step_ms": round(step_s * 1e3, 3),
            "attributed_ms": round(attributed, 3),
            "coverage": round(attributed / max(wall_s * 1e3, 1e-9), 4),
            "compile_ms": round(max(0.0, wall_first - wall_s) * 1e3, 3),
            "execute_ms": round(wall_s * 1e3, 3),
        }
        _record(report, reps)
        return report
