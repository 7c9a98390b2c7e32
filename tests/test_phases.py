"""Device-time attribution (engine/phases.py) + the trace-time
collective ledger (parallel/collectives.py): the perf ledger's
instruments. The attribution coverage contract — attributed phase time
≥ ~90% of measured wall — is asserted here on the CPU backend, the
same decomposition every bench artifact carries."""

import numpy as np
import pytest

from cilium_tpu.core.config import Config
from cilium_tpu.ingest import synth
from cilium_tpu.runtime.loader import Loader
from cilium_tpu.runtime.metrics import (
    CAPTURE_STAGE_SECONDS,
    COLLECTIVE_BYTES,
    COLLECTIVE_OPS,
    ENGINE_PHASE_SECONDS,
    METRICS,
)


@pytest.fixture(scope="module")
def engine_and_scenario():
    per_identity, scenario = synth.realize_scenario(
        synth.synth_http_scenario(n_rules=24, n_flows=256))
    cfg = Config.from_env()
    cfg.enable_tpu_offload = True
    loader = Loader(cfg)
    engine = loader.regenerate(per_identity, revision=1)
    return engine, scenario, cfg


# -- live-path probe --------------------------------------------------------

def test_engine_phase_probe_covers_the_wall(engine_and_scenario):
    from cilium_tpu.engine.phases import ENGINE_PHASES, EnginePhaseProbe

    engine, scenario, cfg = engine_and_scenario
    probe = EnginePhaseProbe(engine)
    report = probe.measure_flows(scenario.flows, cfg.engine, reps=5)
    for phase in ("featurize", "h2d", "mapstate", "dfa-scan",
                  "resolve"):
        assert phase in ENGINE_PHASES
        assert report["phases_ms"][phase] > 0, report
    # the attribution contract: the decomposition covers the fused
    # step's wall (separately-jitted phases forgo fusion, so the sum
    # is ≥ the fused wall minus noise)
    assert report["coverage"] >= 0.9, report
    assert report["wall_ms"] > 0
    # compile-vs-execute split: first call compiled, so compile >> 0
    assert report["compile_ms"] > report["execute_ms"]
    # the probe feeds the Prometheus family
    for phase in ("mapstate", "dfa-scan", "resolve"):
        assert METRICS.histo_count(ENGINE_PHASE_SECONDS,
                                   {"phase": phase}) > 0


def test_engine_phase_probe_verdicts_unchanged(engine_and_scenario):
    """The probe's sub-steps decompose the SAME semantics: resolve's
    output verdicts equal the fused step's."""
    import jax

    from cilium_tpu.engine.phases import (
        _live_mapstate,
        _live_resolve,
        _live_scan,
    )
    from cilium_tpu.engine.verdict import (
        encode_flows,
        flowbatch_to_host_dict,
        verdict_step,
    )

    engine, scenario, cfg = engine_and_scenario
    host = flowbatch_to_host_dict(
        encode_flows(scenario.flows[:128],
                     engine.policy.kafka_interns, cfg.engine))
    batch = {k: jax.device_put(v) for k, v in host.items()}
    ms = _live_mapstate(engine._arrays, batch)
    words = _live_scan(engine._arrays, batch)
    via_phases = _live_resolve(engine._arrays, ms, words, batch)
    fused = verdict_step(engine._arrays, batch)
    np.testing.assert_array_equal(np.asarray(via_phases["verdict"]),
                                  np.asarray(fused["verdict"]))


# -- capture-path probe + staging split -------------------------------------

def test_capture_probe_and_stage_phase_split(tmp_path,
                                             engine_and_scenario):
    from cilium_tpu.engine.phases import CapturePhaseProbe
    from cilium_tpu.engine.verdict import CaptureReplay
    from cilium_tpu.ingest import binary

    engine, scenario, cfg = engine_and_scenario
    cap = str(tmp_path / "cap.bin")
    binary.write_capture_l7(cap, (scenario.flows * 10)[:2000])
    rec = binary.map_capture(cap)
    l7, offsets, blob = binary.read_l7_sidecar(cap)
    gen = binary.read_gen_sidecar(cap)

    marks = {ph: METRICS.histo_sum(CAPTURE_STAGE_SECONDS,
                                   {"phase": ph})
             for ph in ("tables", "featurize", "dedup", "table-h2d")}
    replay = CaptureReplay(engine, l7, offsets, blob, cfg.engine,
                           gen=gen)
    replay.stage_rows(rec, l7)
    replay.stage_unique(drop_if_ratio_at_least=0.5)
    if replay.row_idx is not None:
        replay.stage_unique_device()
    # every staging phase the session ran left its span
    for ph in ("tables", "featurize", "dedup"):
        assert METRICS.histo_sum(CAPTURE_STAGE_SECONDS,
                                 {"phase": ph}) > marks[ph], ph
    if replay.row_idx is not None:
        assert METRICS.histo_sum(CAPTURE_STAGE_SECONDS,
                                 {"phase": "table-h2d"}) \
            > marks["table-h2d"]

    report = CapturePhaseProbe(replay).measure(0, 1024, reps=5)
    for phase in ("h2d", "gather", "mapstate", "resolve"):
        assert report["phases_ms"][phase] > 0, report
    assert report["coverage"] >= 0.9, report
    assert report["stream"] == ("id" if replay.row_idx is not None
                                else "row")


def test_capture_probe_resolve_matches_full_step(tmp_path,
                                                 engine_and_scenario):
    import jax

    from cilium_tpu.engine.phases import (
        _cap_gather,
        _cap_mapstate,
        _cap_resolve,
    )
    from cilium_tpu.engine.verdict import CaptureReplay, \
        verdict_step_capture
    from cilium_tpu.ingest import binary

    engine, scenario, cfg = engine_and_scenario
    cap = str(tmp_path / "cap2.bin")
    binary.write_capture_l7(cap, scenario.flows[:200])
    rec = binary.map_capture(cap)
    l7, offsets, blob = binary.read_l7_sidecar(cap)
    replay = CaptureReplay(engine, l7, offsets, blob, cfg.engine,
                           gen=binary.read_gen_sidecar(cap))
    rows = replay.stage_rows(rec, l7)
    batch = {"rows": jax.device_put(rows)}
    rows_d, words = _cap_gather(replay.table_words, batch)
    ms = _cap_mapstate(engine._arrays, batch)
    via = _cap_resolve(engine._arrays, ms, rows_d, words, batch)
    full = verdict_step_capture(engine._arrays, replay.table_words,
                                batch)
    np.testing.assert_array_equal(np.asarray(via["verdict"]),
                                  np.asarray(full["verdict"]))


# -- collective ledger ------------------------------------------------------

def test_ledger_tp_counts_collective_per_byte():
    """The TP lane's indictment, quantified: the scan-step psum
    executes once per scanned byte per block."""
    import jax
    import jax.numpy as jnp

    from cilium_tpu.parallel.collectives import LEDGER
    from cilium_tpu.parallel.mesh import make_mesh
    from cilium_tpu.parallel.tp import dfa_scan_banked_tp, pad_states
    from cilium_tpu.policy.compiler.dfa import compile_patterns

    n = 8
    devices = jax.devices()[:n]
    arrs = compile_patterns(["/api/v[0-9]+", "/health", "abc+",
                             "x.y"], bank_size=2).stacked()
    L = 37  # distinctive payload length → fresh trace in this test
    rng = np.random.default_rng(0)
    data = rng.integers(0, 128, size=(16, L), dtype=np.uint8)
    lengths = np.full((16,), L, dtype=np.int32)
    mesh = make_mesh((n,), ("state",), devices)
    trans_p, accept_p = pad_states(arrs["trans"], arrs["accept"], n)

    LEDGER.reset()
    out = dfa_scan_banked_tp(
        mesh, jnp.asarray(trans_p), jnp.asarray(arrs["byteclass"]),
        jnp.asarray(arrs["start"]), jnp.asarray(accept_p),
        jnp.asarray(data), jnp.asarray(lengths))
    jax.block_until_ready(out)
    snap = {(r["site"], r["op"]): r for r in LEDGER.snapshot()}
    scan = snap[("tp.scan_step", "psum")]
    # per block: one psum per scanned byte
    assert scan["count_per_block"] == L
    assert scan["axis"] == "state"
    assert scan["bytes_per_block"] == L * scan["bytes_per_call"]
    accept = snap[("tp.accept_plane", "psum")]
    assert accept["count_per_block"] == 4  # one per byte plane

    # publish is delta-idempotent
    before = METRICS.get(COLLECTIVE_OPS,
                         {"site": "tp.scan_step", "op": "psum",
                          "axis": "state"})
    LEDGER.publish_metrics()
    LEDGER.publish_metrics()
    after = METRICS.get(COLLECTIVE_OPS,
                        {"site": "tp.scan_step", "op": "psum",
                         "axis": "state"})
    assert after - before == L
    assert METRICS.get(COLLECTIVE_BYTES,
                       {"site": "tp.scan_step", "op": "psum",
                        "axis": "state"}) > 0


def test_ledger_ulysses_records_gather_and_switch():
    import jax
    import jax.numpy as jnp

    from cilium_tpu.parallel.collectives import LEDGER
    from cilium_tpu.parallel.mesh import make_mesh
    from cilium_tpu.parallel.ulysses import ulysses_scan_banked
    from cilium_tpu.policy.compiler.dfa import compile_patterns

    n = 8
    devices = jax.devices()[:n]
    pats = [f"/u{i}[0-9]*" for i in range(8)]
    arrs = compile_patterns(pats, bank_size=1).stacked()
    L = 41  # distinctive → fresh trace
    rng = np.random.default_rng(1)
    data = rng.integers(0, 128, size=(n * 4, L), dtype=np.uint8)
    lengths = np.full((n * 4,), L, dtype=np.int32)
    mesh = make_mesh((n,), ("data",), devices)

    LEDGER.reset()
    out = ulysses_scan_banked(
        mesh, jnp.asarray(arrs["trans"]), jnp.asarray(arrs["byteclass"]),
        jnp.asarray(arrs["start"]), jnp.asarray(arrs["accept"]),
        jnp.asarray(data), jnp.asarray(lengths))
    jax.block_until_ready(out)
    snap = {(r["site"], r["op"]): r for r in LEDGER.snapshot()}
    # ONE packed gather (payload bytes + lengths ride one collective —
    # the round-7 rework fused the former two) brackets one
    # bank↔batch switch
    gather = snap[("ulysses.gather", "all_gather")]
    assert gather["count_per_block"] == 1
    # the packed buffer carries the payload plus 4 length bytes/row
    assert gather["bytes_per_call"] == (n * 4 // n) * (L + 4)
    assert snap[("ulysses.switch", "all_to_all")]["count_per_block"] == 1


def test_ledger_cp_ring_scales_by_hops():
    import jax
    import jax.numpy as jnp

    from cilium_tpu.engine.longscan import payload_scan_cp
    from cilium_tpu.parallel.collectives import LEDGER
    from cilium_tpu.parallel.mesh import make_mesh
    from cilium_tpu.policy.compiler.dfa import compile_patterns

    n = 8
    devices = jax.devices()[:n]
    bank = compile_patterns(["ab+c"], bank_size=1).banks[0]
    L = n * 43  # distinctive → fresh trace
    rng = np.random.default_rng(2)
    data = rng.integers(0, 128, size=(4, L), dtype=np.uint8)
    lengths = np.full((4,), L, dtype=np.int32)
    mesh = make_mesh((n,), ("seq",), devices)

    LEDGER.reset()
    out = payload_scan_cp(
        mesh, jnp.asarray(bank.trans), jnp.asarray(bank.byteclass),
        bank.start, jnp.asarray(data), jnp.asarray(lengths))
    jax.block_until_ready(out)
    snap = {(r["site"], r["op"]): r for r in LEDGER.snapshot()}
    # the ring carry exchange runs n-1 hops per block
    assert snap[("cp.ring_carry", "ppermute")]["count_per_block"] \
        == n - 1
    assert snap[("cp.final_gather", "all_gather")]["count_per_block"] \
        == 1


# -- megakernel attribution (ISSUE 9) ---------------------------------------

def test_engine_probe_attributes_fused_step_as_one_dispatch(
        engine_and_scenario):
    """The acceptance check: the probe attributes the fused megakernel
    step as ONE device dispatch where the three-op decomposition pays
    three — and the fused step is faster than the three-op chain."""
    from cilium_tpu.engine.phases import ENGINE_PHASES, EnginePhaseProbe

    engine, scenario, cfg = engine_and_scenario
    assert engine.impl_plan, "default engines stage the megakernel"
    probe = EnginePhaseProbe(engine)
    report = probe.measure_flows(scenario.flows[:512], cfg.engine,
                                 reps=3)
    assert report["fused_dispatches"] == 1
    assert report["three_op_dispatches"] == 3
    assert report["fused_ms"] > 0
    assert report["three_op_ms"] >= report["fused_ms"] * 0.5
    assert report["fused_speedup"] > 0
    # fused-verdict + the plan's impls are first-class phase labels
    assert "fused-verdict" in ENGINE_PHASES
    assert report["phases_ms"]["fused-verdict"] > 0
    for impl in set(engine.impl_plan.values()):
        assert impl in ENGINE_PHASES
        assert report["phases_ms"][impl] > 0
    assert report["impl_plan"] == engine.impl_plan
    # the coverage contract still holds: the decomposition covers (or,
    # fused, exceeds) the staged step's wall
    assert report["coverage"] >= 0.9, report


def test_engine_probe_nfa_impl_phase_label():
    """A plan that uses the bitset-NFA arm reports its phase lane."""
    from cilium_tpu.core.config import Config
    from cilium_tpu.engine.megakernel import nfa_plan
    from cilium_tpu.engine.phases import EnginePhaseProbe
    from cilium_tpu.runtime.loader import Loader

    per_identity, scenario = synth.realize_scenario(
        synth.synth_http_scenario(n_rules=12, n_flows=64))
    cfg = Config.from_env()
    cfg.enable_tpu_offload = True
    cfg.engine.bank_size = 4
    engine = Loader(cfg).regenerate(per_identity, revision=1)
    engine.stage_plan(nfa_plan(engine.policy, cfg.engine))
    assert "nfa-bitset" in engine.impl_plan.values()
    report = EnginePhaseProbe(engine).measure_flows(
        scenario.flows[:64], cfg.engine, reps=2)
    assert report["phases_ms"]["nfa-bitset"] > 0
