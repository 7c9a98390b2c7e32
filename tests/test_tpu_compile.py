"""Chip-compile guards: the verdict hot path's kernels and steps,
compiled at real widths for a described (not attached) TPU v5e.

Interpret mode runs a Pallas kernel's semantics on the CPU; it cannot
show what the TPU compiler refuses (unaligned slices, VMEM over-use,
a program past HBM). These tests compile for ``v5e:2x2`` — one chip of
it, or the 4-device mesh — and hold each program's
``memory_analysis()`` to one chip's 16 GiB:

* ``nfa_finals_pallas`` at path width 256 and header width 1024,
  batch 8192, at the 1k-rule http policy's bank and class counts;
* ``dfa_finals_pallas`` at its state ceiling, and at the 1k-rule path
  stack's own bank, state and class counts (widths 32 and 256);
* ``fused_verdict_step`` at the 1k-rule http policy's array shapes
  with the impl plan the rule picks on a TPU;
* the DP lane step over a 4-device mesh.

The topology is described inside a module fixture (never at import):
only the worker that runs this file loads the TPU compiler.
"""

import functools
import os

import numpy as np
import pytest

#: one v5e chip's HBM
HBM_BYTES = 16 * 2**30
BATCH = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache(topo):
    """A compile for a described chip is written to the persistent
    cache but can never be read back without one: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def http_1k(tmp_path_factory):
    """The 1k-rule http policy (BASELINE configs[1]) as the Loader
    stages it, plus the NFA tensors and impl plan the rule picks for
    it on a TPU (``plan_for_engine`` reads the backend, so the
    test steers it to "tpu" here)."""
    import jax

    from cilium_tpu.core.config import Config
    from cilium_tpu.engine import megakernel
    from cilium_tpu.ingest import synth
    from cilium_tpu.runtime.loader import Loader

    per_identity, scenario = synth.realize_scenario(
        synth.synth_http_scenario(n_rules=1000, n_flows=BATCH))
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = str(tmp_path_factory.mktemp("artifacts"))
    engine = Loader(cfg).regenerate(per_identity, revision=1)
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    try:
        plan, extra, report = megakernel.plan_for_engine(engine.policy,
                                                         cfg.engine)
    finally:
        mp.undo()
    return {"engine": engine, "cfg": cfg, "flows": scenario.flows,
            "plan": plan, "arrays": {**engine.policy.arrays, **extra},
            "report": report}


def _sds(tree, sharding):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total <= HBM_BYTES, (
        f"{total / 2**30:.2f} GiB per chip > 16 GiB: {m}")
    return total


def test_tpu_auto_plan_at_1k_rules(http_1k):
    """The plan the fused-step compile below uses. The 1k-rule path
    banks blow the NFA arm's 128-position tile, so the rule on a TPU
    keeps every field on the dense arm at this policy (within it,
    ``dfa_kernel.pick_arm`` sends the path banks to the MXU kernel
    over batches of a full tile)."""
    from cilium_tpu.engine.megakernel import IMPL_DENSE

    rep = http_1k["report"]["path"]
    assert rep["dfa_states"] > 128 and rep["nfa_positions"] is None
    assert set(http_1k["plan"].values()) == {IMPL_DENSE}


@pytest.mark.parametrize("field,width", [("path", 256), ("hdr", 1024)])
def test_nfa_pallas_compiles_at_real_widths(http_1k, one_chip, field,
                                            width):
    """The Pallas NFA kernel at the policy's bank count and byte-class
    count for the field, a full 128-position tile per bank."""
    import jax

    from cilium_tpu.engine.nfa_kernel import MAX_POSITIONS
    from cilium_tpu.engine.pallas_nfa import nfa_finals_pallas

    arrays = http_1k["arrays"]
    nb, _, k = arrays[f"{field}_trans"].shape
    p = MAX_POSITIONS
    args = _sds([np.zeros((nb, p, p), np.float32),
                 np.zeros((nb, p, k), np.float32),
                 np.zeros((nb, 256), np.int32),
                 np.zeros((nb, p), np.float32),
                 np.zeros((BATCH, width), np.uint8),
                 np.zeros((BATCH,), np.int32)], one_chip)
    compiled = jax.jit(functools.partial(
        nfa_finals_pallas, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_dfa_pallas_compiles_at_state_budget(one_chip):
    import jax

    from cilium_tpu.engine.pallas_dfa import MAX_STATES, dfa_finals_pallas

    nb, k, width = 2, 32, 1024
    args = _sds([np.zeros((nb, MAX_STATES, k), np.int32),
                 np.zeros((nb, 256), np.int32),
                 np.zeros((nb,), np.int32),
                 np.zeros((BATCH, width), np.uint8),
                 np.zeros((BATCH,), np.int32)], one_chip)
    compiled = jax.jit(functools.partial(
        dfa_finals_pallas, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("width", [32, 256])
def test_dfa_pallas_compiles_at_1k_rule_path_stack(http_1k, one_chip,
                                                   width):
    """The MXU kernel at the shape the default pick sends it on the
    chip: the 1k-rule path banks (>128 states each), batch 8192."""
    import jax

    from cilium_tpu.engine.dfa_kernel import pick_arm
    from cilium_tpu.engine.pallas_dfa import dfa_finals_pallas

    nb, s, k = http_1k["arrays"]["path_trans"].shape
    assert pick_arm("tpu", s, k, BATCH) == "pallas"
    args = _sds([np.zeros((nb, s, k), np.int32),
                 np.zeros((nb, 256), np.int32),
                 np.zeros((nb,), np.int32),
                 np.zeros((BATCH, width), np.uint8),
                 np.zeros((BATCH,), np.int32)], one_chip)
    compiled = jax.jit(functools.partial(
        dfa_finals_pallas, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def _host_batch(http_1k):
    from cilium_tpu.engine.verdict import (
        encode_flows,
        flowbatch_to_host_dict,
    )

    engine = http_1k["engine"]
    return flowbatch_to_host_dict(encode_flows(
        http_1k["flows"][:BATCH], engine.policy.kafka_interns,
        http_1k["cfg"].engine))


def test_fused_step_compiles_at_1k_rule_shapes(http_1k, one_chip):
    import jax

    from cilium_tpu.engine.megakernel import fused_verdict_step

    step = jax.jit(functools.partial(
        fused_verdict_step,
        impl_plan=tuple(sorted(http_1k["plan"].items()))))
    compiled = step.lower(_sds(http_1k["arrays"], one_chip),
                          _sds(_host_batch(http_1k), one_chip)).compile()
    _fits(compiled)


def test_dp_step_compiles_on_4_device_mesh(http_1k, topo,
                                           no_compile_cache):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cilium_tpu.parallel.mesh import make_mesh
    from cilium_tpu.parallel.sharding import make_sharded_step

    mesh = make_mesh(None, ("data",), topo.devices[:4])
    step = make_sharded_step(mesh, "data")
    arrays = _sds(http_1k["engine"].policy.arrays,
                  NamedSharding(mesh, P()))
    batch = _sds(_host_batch(http_1k), NamedSharding(mesh, P("data")))
    compiled = step.lower(arrays, batch).compile()
    assert len(compiled.output_shardings["verdict"].device_set) == 4
    _fits(compiled)


@pytest.mark.parametrize("arm", ["gather", "pallas"])
def test_table_piece_scans_compile_at_1k_rule_shapes(http_1k, one_chip,
                                                     arm):
    """The capture string-table scan of one row piece, and of the last
    piece joined to the earlier ones (``stage_capture_tables``), at the
    1k-rule policy's path banks: a 65,536-row path table, 32 bytes
    wide. The arm is named: the default pick reads the backend, which
    is the CPU here; on the chip it takes "pallas" for these banks."""
    import jax

    from cilium_tpu.engine import verdict as V

    rows = V.TABLE_PIECE_ROWS
    pieces = 65536 // rows
    step = V._stage_tables_jit(arm)
    arrays = _sds(http_1k["engine"]._arrays, one_chip)
    piece = _sds({"path": (np.zeros((rows, 32), np.uint8),
                           np.zeros(rows, np.int32),
                           np.zeros(rows, bool))}, one_chip)
    words = jax.eval_shape(step, arrays, piece, {})["path"]
    earlier = _sds({"path": tuple(
        {k: np.zeros(s.shape, s.dtype) for k, s in words.items()}
        for _ in range(pieces - 1))}, one_chip)
    for prior, n in (({}, 1), (earlier, pieces)):
        compiled = step.lower(arrays, piece, prior).compile()
        _fits(compiled)
        assert ("tpu_custom_call" in compiled.as_text()) == (
            arm == "pallas")
        out = jax.eval_shape(step, arrays, piece, prior)["path"]
        assert {s.shape[0] for s in out.values()} == {n * rows}
