"""Capture string tables staged in row pieces (``stage_capture_tables``):
the device scans piece k while the host builds piece k+1. These tests
pin that the pieced staging gives the words of one scan of each whole
table, bit for bit, that it counts and spans every piece, and that the
piece scan keeps the module name the benchmark's roofline reads."""

import dataclasses
import importlib

import jax
import numpy as np

from cilium_tpu.core.config import Config
from cilium_tpu.engine import verdict as V
from cilium_tpu.ingest import binary, synth
from cilium_tpu.runtime import tracing
from cilium_tpu.runtime.loader import Loader
from cilium_tpu.runtime.metrics import CAPTURE_TABLE_PIECES, METRICS

#: the string fields every capture stages, and the words they give
FIELDS = ("path", "method", "host", "headers", "qname")


def _capture(tmp_path, n_rules=25, n=150):
    """A capture whose every record carries its own path (``n`` unique
    paths, so the path table pads to 256 rows), and its policy."""
    per_identity, scenario = synth.realize_scenario(
        synth.synth_http_scenario(n_rules=n_rules, n_flows=n))
    flows = [dataclasses.replace(
        f, http=dataclasses.replace(f.http, path=f"{f.http.path}/{i:x}"))
        if f.http is not None else f
        for i, f in enumerate(scenario.flows)]
    path = str(tmp_path / "cap.bin")
    binary.write_capture_l7(path, flows)
    rec = binary.map_capture(path)
    l7, offsets, blob = binary.read_l7_sidecar(path)
    return per_identity, flows, rec, l7, offsets, blob


def _config(tmp_path):
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = str(tmp_path / "cache")
    return cfg


def _featurizer(engine, cfg, l7, offsets, blob):
    return V.CaptureFeaturizer(l7, offsets, blob,
                               engine.policy.kafka_interns, cfg.engine)


def _whole_scan(engine, feat):
    """Each whole table through one program: staging as one piece."""
    tables = {f: feat.tables[f] for f, _ in V._TABLE_FIELDS}
    step = V._stage_tables_jit()
    out = step(engine._arrays, jax.device_put(tables, engine.device), {})
    return {k: w for words in out.values() for k, w in words.items()}


def _assert_words_equal(got, want):
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)


def _pieces():
    # every field takes the gather off the TPU (dfa_kernel.pick_arm)
    return {f: METRICS.get(CAPTURE_TABLE_PIECES,
                           {"field": f, "arm": "gather"})
            for f in FIELDS}


def test_pieced_table_words_equal_whole_table_scan(tmp_path, monkeypatch):
    """Eight 32-row pieces of the 256-row path table, the fifth part
    padding and the last three all padding: every entry of ``table_words``
    (the path's words and group words, and the four small fields')
    equals the whole-table scan's, and the host tables built piece by
    piece equal those built in one go."""
    per_identity, _, _, l7, offsets, blob = _capture(tmp_path)
    cfg = _config(tmp_path)
    engine = Loader(cfg).regenerate(per_identity, revision=1)
    monkeypatch.setattr(V, "TABLE_PIECE_ROWS", 32)
    feat = _featurizer(engine, cfg, l7, offsets, blob)
    n_paths = len(np.unique(l7["path"]))
    assert feat.padded_rows()["path"] == 256 and 128 < n_paths < 160
    got = V.stage_capture_tables(engine, feat)
    assert {"path", "path_groups", *FIELDS} <= set(got)
    assert got["path"].shape[0] == 256
    _assert_words_equal(got, _whole_scan(engine, feat))
    whole = _featurizer(engine, cfg, l7, offsets, blob).tables
    for f in FIELDS:
        for a, b in zip(feat.tables[f], whole[f]):
            np.testing.assert_array_equal(a, b, err_msg=f)


def test_one_piece_tables_stage_in_one_dispatch(tmp_path):
    """A capture whose tables all fit one piece stages through the same
    loop once: one piece counted per field, words equal to the
    whole-table scan's."""
    per_identity, _, _, l7, offsets, blob = _capture(tmp_path)
    cfg = _config(tmp_path)
    engine = Loader(cfg).regenerate(per_identity, revision=1)
    feat = _featurizer(engine, cfg, l7, offsets, blob)
    assert max(feat.padded_rows().values()) <= V.TABLE_PIECE_ROWS
    before = _pieces()
    got = V.stage_capture_tables(engine, feat)
    assert {f: n - before[f] for f, n in _pieces().items()} \
        == dict.fromkeys(FIELDS, 1)
    _assert_words_equal(got, _whole_scan(engine, feat))


def test_table_pieces_counted_and_spanned_inside_stage_tables(
        tmp_path, monkeypatch):
    """Four 64-row pieces of the path table: the counter reads 4 for
    the path and 1 for each one-piece field, and each piece's
    ``capture.stage.table_piece`` span lies inside the session's
    ``capture.stage.tables`` span, on its thread."""
    per_identity, _, _, l7, offsets, blob = _capture(tmp_path)
    cfg = _config(tmp_path)
    engine = Loader(cfg).regenerate(per_identity, revision=1)
    monkeypatch.setattr(V, "TABLE_PIECE_ROWS", 64)
    before = _pieces()
    tracing.clear_profiled_spans()
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        V.CaptureReplay(engine, l7, offsets, blob, cfg.engine)
    finally:
        jax.profiler.stop_trace()
    spans = tracing.profiled_spans()
    tracing.clear_profiled_spans()
    assert {f: n - before[f] for f, n in _pieces().items()} \
        == {**dict.fromkeys(FIELDS, 1), "path": 4}
    (_, t0, t1, tid), = [s for s in spans
                         if s[0] == "cilium_tpu.capture.stage.tables"]
    pieces = [s for s in spans
              if s[0] == "cilium_tpu.capture.stage.table_piece"]
    assert len(pieces) == 4
    assert all(t0 <= a < b <= t1 and th == tid for _, a, b, th in pieces)


def test_pieced_session_after_policy_swap_matches_fresh_session(
        tmp_path, monkeypatch):
    """A session staged in pieces, re-staged through ``_ensure_current``
    after a policy swap, holds the table words and gives the verdicts
    of a session opened fresh on the new policy, and those of the
    per-flow path."""
    per1, flows, rec, l7, offsets, blob = _capture(tmp_path)
    per2, _ = synth.realize_scenario(
        synth.synth_http_scenario(n_rules=12, n_flows=150))
    cfg = _config(tmp_path)
    loader = Loader(cfg)
    loader.regenerate(per1, revision=1)
    monkeypatch.setattr(V, "TABLE_PIECE_ROWS", 32)

    def session():
        s = V.CaptureReplay(loader.engine, l7, offsets, blob, cfg.engine,
                            loader=loader)
        s.stage_rows(rec, l7)
        s.stage_unique()
        return s

    swapped = session()
    before = swapped.verdict_chunk(rec, l7)
    loader.regenerate(per2, revision=2)
    after = swapped.verdict_chunk(rec, l7)
    fresh = session()
    _assert_words_equal(swapped.table_words, fresh.table_words)
    want = fresh.verdict_chunk(rec, l7)
    assert set(after) == set(want)
    for lane in want:
        np.testing.assert_array_equal(after[lane], want[lane],
                                      err_msg=lane)
    np.testing.assert_array_equal(
        after["verdict"], loader.engine.verdict_flows(flows)["verdict"])
    assert not np.array_equal(before["verdict"], after["verdict"])


def test_piece_scan_module_is_counted_by_the_roofline(tmp_path):
    """The piece scan lowers to a module whose name the benchmark's
    ``megakernel_roofline`` counts as the scan's device time: renamed,
    the metric would silently lose the scan."""
    roofline = importlib.import_module(
        "benchmark.metrics.megakernel_roofline")
    per_identity, _, _, l7, offsets, blob = _capture(tmp_path)
    cfg = _config(tmp_path)
    engine = Loader(cfg).regenerate(per_identity, revision=1)
    feat = _featurizer(engine, cfg, l7, offsets, blob)
    step = V._stage_tables_jit(getattr(engine, "_dfa_impl", "gather"))
    piece = {f: feat.table_rows(f, 0, 64) for f in FIELDS}
    earlier = {"path": (step(engine._arrays, piece, {})["path"],)}
    for prior in ({}, earlier):
        lowered = step.lower(engine._arrays, piece, prior)
        name = str(lowered.compiler_ir().operation.attributes["sym_name"])
        assert roofline.MODULES.match(name.strip('"')), name
