"""Columnar ingest + device verdict memo (ISSUE 7).

Differential discipline: the columnar encoders are pinned to the
per-record reference encoders (``binary.flows_to_capture_l7`` /
the Flow-object JSONL path) field by field, the streaming record-batch
writer (native AND numpy fallback) is pinned byte-for-byte, the
hash-keyed dedup is pinned to the exact row sort (its drop, decided
early where one string column tells every row apart, to the
build-then-decide dedup), and the memo-backed
replay is pinned bit-for-bit to ``verdict_flows`` — including across
policy-generation invalidations and auth-view changes.
"""

import itertools

import numpy as np
import pytest

from cilium_tpu.core.config import Config
from cilium_tpu.core.flow import Flow, Protocol, TrafficDirection
from cilium_tpu.ingest import binary, synth
from cilium_tpu.ingest.columnar import (
    CaptureColumns,
    flows_to_columns,
    jsonl_to_columns,
    tuples_to_columns,
)
from cilium_tpu.runtime.loader import Loader


def _scenario(which, n_rules=12, n_flows=160):
    scenario = synth.scenario_by_name(which, n_rules, n_flows)
    return synth.realize_scenario(scenario)


def _engine_for(which, n_rules=12, n_flows=160, loader_out=None):
    per_identity, scenario = _scenario(which, n_rules, n_flows)
    cfg = Config()
    cfg.enable_tpu_offload = True
    loader = Loader(cfg)
    engine = loader.regenerate(per_identity, revision=1)
    if loader_out is not None:
        loader_out.append(loader)
    return cfg, engine, scenario


def _replay_for(engine, cfg, flows, loader=None):
    from cilium_tpu.engine.verdict import CaptureReplay

    cols = flows_to_columns(flows)
    replay = CaptureReplay(engine, cols.l7, cols.offsets, cols.blob,
                           cfg.engine, gen=cols.gen, loader=loader)
    replay.stage_rows(cols.rec, cols.l7)
    replay.stage_unique()
    return replay, cols


# ---------------------------------------------------------------------------
# columnar encoder vs the per-record reference


@pytest.mark.parametrize("which", ["http", "fqdn", "kafka", "generic"])
def test_flows_to_columns_matches_rowmajor_reference(which):
    """Every field a capture resolves — records, strings, generic
    pairs — must be identical between the columnar encoder and the
    historical per-record writer (intern ORDER may differ; resolved
    content may not)."""
    _, scenario = _scenario(which)
    flows = scenario.flows
    rec, l7, offsets, blob, gen, fmax = \
        binary.flows_to_capture_l7(flows)
    want = binary.records_to_flows_l7(rec, l7, offsets, blob, gen=gen)
    cols = flows_to_columns(flows)
    got = binary.records_to_flows_l7(cols.rec, cols.l7, cols.offsets,
                                     cols.blob, gen=cols.gen)
    assert got == want


def test_write_capture_l7_roundtrips_via_columnar(tmp_path):
    """The product write path (columnar + streaming batch writer)
    round-trips to the same resolved flows as the per-record
    reference writer."""
    _, scenario = _scenario("http")
    a = str(tmp_path / "a.bin")
    b = str(tmp_path / "b.bin")
    binary.write_capture_l7(a, scenario.flows)
    binary._write_capture_l7_rowmajor(b, scenario.flows)
    assert binary.capture_count(a) == binary.capture_count(b)
    assert binary.read_capture_flows_l7(a) == \
        binary.read_capture_flows_l7(b)


def test_batch_writer_chunking_is_byte_identical(tmp_path):
    """Multi-batch streaming writes produce the IDENTICAL file as a
    single-batch write (v2 and v3)."""
    for which in ("http", "generic"):
        _, scenario = _scenario(which)
        cols = flows_to_columns(scenario.flows)
        one = str(tmp_path / f"one_{which}.bin")
        many = str(tmp_path / f"many_{which}.bin")
        binary.write_capture_columns(one, cols)
        binary.write_capture_columns(many, cols, batch_size=17)
        assert open(one, "rb").read() == open(many, "rb").read()


def test_numpy_fallback_writer_matches_native(tmp_path, monkeypatch):
    """The pure-numpy CaptureWriter fallback writes byte-identical
    files to the native streaming writer."""
    _, scenario = _scenario("generic")
    cols = flows_to_columns(scenario.flows)
    native = str(tmp_path / "native.bin")
    fallback = str(tmp_path / "fallback.bin")
    binary.write_capture_columns(native, cols, batch_size=23)
    monkeypatch.setattr(binary, "_native", lambda: None)
    binary.write_capture_columns(fallback, cols, batch_size=23)
    assert open(native, "rb").read() == open(fallback, "rb").read()
    assert binary.capture_count(fallback) == len(scenario.flows)


def test_aborted_writer_leaves_rejectable_file(tmp_path):
    """An abandoned streaming writer must leave a file readers REJECT
    (truncated), never misparse."""
    _, scenario = _scenario("http")
    cols = flows_to_columns(scenario.flows)
    p = str(tmp_path / "aborted.bin")
    w = binary.CaptureWriter(p, fmax=cols.fmax)
    w.write_batch(cols.rec, cols.l7, cols.gen)
    w.abort()
    with pytest.raises(binary.CaptureError):
        binary.capture_count(p)


def test_jsonl_to_columns_differential(tmp_path):
    """JSONL parses straight into columns identical to the Flow-object
    path (read_jsonl → flows_to_columns), for flowpb AND accesslog
    lines mixed in one file."""
    import json

    from cilium_tpu.ingest.hubble import flow_to_dict, read_jsonl

    _, scenario = _scenario("http", n_rules=8, n_flows=60)
    for f in scenario.flows:
        f.src_labels = ()
        f.dst_labels = ()
    lines = [json.dumps(flow_to_dict(f)) for f in scenario.flows]
    # a couple of accesslog-schema lines ride the same file
    lines.append(json.dumps({
        "entry_type": "Request", "is_ingress": True,
        "source_security_id": 7, "destination_security_id": 9,
        "source_address": "10.0.0.1:4242",
        "destination_address": "10.0.0.2:80",
        "http": {"method": "GET", "path": "/x", "host": "SVC.Local",
                 "headers": [{"key": "X-A", "value": "b"}]}}))
    lines.append(json.dumps({
        "entry_type": "Denied", "is_ingress": False,
        "source_security_id": 9, "destination_security_id": 7,
        "destination_address": "10.0.0.1:9092",
        "kafka": {"api_key": 0, "api_version": 3, "topic": "t",
                  "client_id": "c"}}))
    p = str(tmp_path / "cap.jsonl")
    with open(p, "w") as fp:
        fp.write("\n".join(lines) + "\n")
    got = jsonl_to_columns(p)
    want = flows_to_columns(list(read_jsonl(p)))
    assert got.rec.tobytes() == want.rec.tobytes()
    assert got.l7.tobytes() == want.l7.tobytes()
    assert got.offsets.tobytes() == want.offsets.tobytes()
    assert got.blob.tobytes() == want.blob.tobytes()
    assert (got.gen is None) == (want.gen is None)


def test_uncarriable_generic_flattens_and_counts():
    from cilium_tpu.core.flow import GenericL7Info, L7Type

    flows = [Flow(src_identity=1, dst_identity=2, dport=80,
                  l7=L7Type.GENERIC, generic=None),
             Flow(src_identity=1, dst_identity=2, dport=81,
                  l7=L7Type.GENERIC,
                  generic=GenericL7Info(proto="", fields={})),
             Flow(src_identity=1, dst_identity=2, dport=82,
                  l7=L7Type.GENERIC,
                  generic=GenericL7Info(proto="r2d2",
                                        fields={"cmd": "get"}))]
    cols = flows_to_columns(flows)
    assert cols.gen_dropped == 2
    assert [int(t) for t in cols.rec["l7_type"]] == \
        [int(L7Type.NONE), int(L7Type.NONE), int(L7Type.GENERIC)]
    assert cols.gen is not None and cols.fmax == 1


# ---------------------------------------------------------------------------
# hash-keyed dedup


def test_hash_dedup_matches_exact_row_sort():
    """stage_unique's hash-keyed dedup must assign ids that expand to
    the identical rows as the exact lexicographic unique."""
    cfg, engine, scenario = _engine_for("http", n_rules=10,
                                        n_flows=200)
    replay, cols = _replay_for(engine, cfg, scenario.flows)
    rows = replay.rows_all
    uniq_exact = np.unique(rows, axis=0)
    assert replay.n_unique == len(uniq_exact)
    # ids are lossless: expanding the unique table reproduces rows
    expanded = replay._uniq_host[replay.row_idx]
    np.testing.assert_array_equal(expanded, rows)


def test_hash_collision_falls_back_to_exact(monkeypatch):
    """A (forced) total hash collision must still dedup EXACTLY via
    the row-sort fallback."""
    cfg, engine, scenario = _engine_for("http", n_rules=6,
                                        n_flows=80)

    import cilium_tpu.engine.memo as memo_mod

    monkeypatch.setattr(
        memo_mod, "hash_rows",
        lambda rows: np.zeros(len(rows), dtype=np.uint64))
    replay, cols = _replay_for(engine, cfg, scenario.flows)
    rows = replay.rows_all
    assert replay.n_unique == len(np.unique(rows, axis=0))
    np.testing.assert_array_equal(
        replay._uniq_host[replay.row_idx], rows)


def _build_then_decide(rows, drop):
    """The reference dedup: build the whole hash-keyed table, then
    look at the ratio. Returns
    ``(ratio, n_unique, unique table or None, row ids or None)``."""
    from cilium_tpu.engine.memo import hash_rows
    from cilium_tpu.engine.verdict import _pad_rows_pow2

    h = hash_rows(rows)
    _, first, inverse = np.unique(h, return_index=True,
                                  return_inverse=True)
    uniq = rows[first]
    if not np.array_equal(uniq[inverse], rows):
        uniq, inverse = np.unique(rows, axis=0, return_inverse=True)
    n_true = len(uniq)
    ratio = n_true / max(1, len(rows))
    if drop is not None and ratio >= drop:
        return ratio, n_true, None, None
    uniq = _pad_rows_pow2(uniq)
    idx_dtype = np.uint16 if len(uniq) <= (1 << 16) else np.int32
    return ratio, n_true, uniq, inverse.astype(idx_dtype)


def _dedup_flows(n, k, vary, n_paths=4):
    """``n`` HTTP flows cycling over ``k`` distinct rows. ``vary`` says
    what tells the distinct rows apart: ``paths`` gives each its own
    path (the path column counts ``k`` values), ``ports`` its own port
    over ``n_paths`` shared paths (the path column counts ``n_paths``)."""
    from cilium_tpu.core.flow import HTTPInfo, L7Type

    def row(i):
        path = f"/r{i}" if vary == "paths" else f"/s{i % n_paths}"
        port = 80 if vary == "paths" else 1000 + i
        return Flow(src_identity=1, dst_identity=2, dport=port,
                    protocol=Protocol.TCP,
                    direction=TrafficDirection.INGRESS, l7=L7Type.HTTP,
                    http=HTTPInfo(method="GET", path=path, host="h"))

    return [row(i % k) for i in range(n)]


def _decisions():
    from cilium_tpu.runtime.metrics import (
        CAPTURE_DEDUP_DECISIONS,
        METRICS,
    )

    return {(by, out): METRICS.get(CAPTURE_DEDUP_DECISIONS,
                                   {"by": by, "outcome": out})
            for by in ("strings", "rows")
            for out in ("dropped", "kept")}


def _staged(engine, cfg, flows, lo=0, hi=None):
    from cilium_tpu.engine.verdict import CaptureReplay

    cols = flows_to_columns(flows)
    replay = CaptureReplay(engine, cols.l7, cols.offsets, cols.blob,
                           cfg.engine, gen=cols.gen)
    replay.stage_rows(cols.rec[lo:hi], cols.l7[lo:hi])
    return replay


def _stage_counted(replay, drop):
    """``stage_unique(drop)`` → (ratio, the one decision it counted)."""
    before = _decisions()
    ratio = replay.stage_unique(drop_if_ratio_at_least=drop)
    moved = [k for k, v in _decisions().items() if v != before[k]]
    assert len(moved) == 1
    assert _decisions()[moved[0]] == before[moved[0]] + 1
    return ratio, moved[0]


def _assert_matches_build_first(replay, drop, ratio):
    want_ratio, want_n, want_uniq, want_idx = _build_then_decide(
        replay.rows_all, drop)
    assert ratio == want_ratio
    assert replay.n_unique == want_n
    if want_uniq is None:
        assert replay.row_idx is None and replay._uniq_host is None
    else:
        assert replay._uniq_host.dtype == want_uniq.dtype
        np.testing.assert_array_equal(replay._uniq_host, want_uniq)
        assert replay.row_idx.dtype == want_idx.dtype
        np.testing.assert_array_equal(replay.row_idx, want_idx)


def test_all_distinct_paths_drop_without_hashing(monkeypatch):
    """Every path distinct: the path column alone tells every row
    apart, so the table is dropped and no row is hashed."""
    import cilium_tpu.engine.memo as memo_mod

    cfg, engine, _ = _engine_for("http", n_rules=6, n_flows=80)
    replay = _staged(engine, cfg, _dedup_flows(96, 96, "paths"))

    def no_hash(rows):
        raise AssertionError("hash_rows called")

    monkeypatch.setattr(memo_mod, "hash_rows", no_hash)
    ratio, decided = _stage_counted(replay, 0.5)
    assert ratio == 1.0
    assert replay.n_unique == 96
    assert replay.row_idx is None and replay._uniq_host is None
    assert decided == ("strings", "dropped")


@pytest.mark.parametrize("vary", ["paths", "ports"])
@pytest.mark.parametrize("k", [30, 49, 50, 51, 90, 100])
def test_bounded_drop_matches_exact_unique(k, vary):
    """At distinct-row shares either side of the 0.5 drop ratio, the
    ratio, ``n_unique`` and the drop equal the exact row unique and
    the build-then-decide dedup, bit for bit where the table is kept.
    ``ports`` keeps every string column's distinct count under the
    row count, so the exact count decides; ``paths`` at 1.0 gives every
    row its own path, so that column alone decides."""
    cfg, engine, _ = _engine_for("http", n_rules=6, n_flows=80)
    replay = _staged(engine, cfg, _dedup_flows(100, k, vary))
    ratio, decided = _stage_counted(replay, 0.5)
    n_exact = len(np.unique(replay.rows_all, axis=0))
    assert n_exact == k
    assert replay.n_unique == k and ratio == k / 100
    assert (replay.row_idx is None) == (k / 100 >= 0.5)
    _assert_matches_build_first(replay, 0.5, ratio)
    if k / 100 < 0.5:
        want = ("rows", "kept")
    else:
        want = ("strings" if vary == "paths" and k == 100 else "rows",
                "dropped")
    assert decided == want


@pytest.mark.parametrize("vary,k,collide,by", [
    ("paths", 90, "total", "rows"),
    ("ports", 90, "total", "rows"),
    ("ports", 90, "pair", "rows"),
    ("paths", 100, "total", "strings"),
])
def test_bounded_drop_exact_under_hash_collision(monkeypatch, vary, k,
                                                 collide, by):
    """Planted hash collisions in the drop regime: whichever check
    decides, ``n_unique`` stays the exact distinct-row count."""
    import cilium_tpu.engine.memo as memo_mod

    real = memo_mod.hash_rows
    def pair(rows):  # the rows of the largest hash take the least
        h = real(rows)
        return np.where(h == h.max(), h.min(), h)

    planted = {"total": lambda r: np.zeros(len(r), dtype=np.uint64),
               "pair": pair}[collide]
    monkeypatch.setattr(memo_mod, "hash_rows", planted)
    cfg, engine, _ = _engine_for("http", n_rules=6, n_flows=80)
    replay = _staged(engine, cfg, _dedup_flows(100, k, vary))
    d = len(np.unique(planted(replay.rows_all)))
    assert d < k  # the planted hash does merge distinct rows
    ratio, decided = _stage_counted(replay, 0.5)
    assert replay.n_unique == k and ratio == k / 100
    assert replay.row_idx is None
    assert decided == (by, "dropped")


@pytest.mark.parametrize("drop", [None, 0.5, 0.9, 1.0])
def test_kept_dedup_bit_identical_to_build_first(drop):
    """Where the table is kept, its rows, the per-flow ids and their
    dtypes are the build-then-decide dedup's, bit for bit: the memo
    rides these ids."""
    cfg, engine, scenario = _engine_for("http", n_rules=10,
                                        n_flows=200)
    replay = _staged(engine, cfg, scenario.flows)
    ratio, decided = _stage_counted(replay, drop)
    assert replay.row_idx is not None
    assert decided == ("rows", "kept")
    _assert_matches_build_first(replay, drop, ratio)


@pytest.mark.parametrize("lo,hi", [(50, 100), (0, 50)])
def test_bound_counts_the_staged_slice(lo, hi):
    """``stage_rows`` of a slice: the string columns are counted over
    the staged rows, not the capture's strings. The capture's first half has 50
    distinct paths, its second half one path over 5 ports."""
    from cilium_tpu.core.flow import HTTPInfo, L7Type

    flows = _dedup_flows(50, 50, "paths") + [
        Flow(src_identity=1, dst_identity=2, dport=1000 + i % 5,
             protocol=Protocol.TCP, direction=TrafficDirection.INGRESS,
             l7=L7Type.HTTP, http=HTTPInfo(method="GET", path="/a",
                                           host="h"))
        for i in range(50)]
    cfg, engine, _ = _engine_for("http", n_rules=6, n_flows=80)
    replay = _staged(engine, cfg, flows, lo, hi)
    ratio, decided = _stage_counted(replay, 0.5)
    _assert_matches_build_first(replay, 0.5, ratio)
    if lo == 50:
        assert replay.n_unique == 5 and decided == ("rows", "kept")
    else:
        assert replay.n_unique == 50
        assert decided == ("strings", "dropped")


# ---------------------------------------------------------------------------
# verdict memo


def test_memo_replay_bit_equal_and_counted():
    """Memo-backed chunked replay ≡ verdict_flows bit-for-bit; hits
    and misses land in the counters (hit ratio ≈ 1 - unique/total)."""
    cfg, engine, scenario = _engine_for("http", n_rules=12,
                                        n_flows=240)
    replay, cols = _replay_for(engine, cfg, scenario.flows)
    want = engine.verdict_flows(scenario.flows)["verdict"]
    got = list(itertools.chain.from_iterable(
        replay.verdict_chunk(cols.rec[s:s + 64], cols.l7[s:s + 64],
                             start=s)["verdict"].tolist()
        for s in range(0, len(cols.rec), 64)))
    np.testing.assert_array_equal(got, want)
    m = replay.memo
    assert m is not None
    assert m.misses == replay.n_unique
    assert m.hits == len(cols.rec)
    assert len(set(int(v) for v in want)) > 1


def test_memo_disabled_by_config_knob():
    cfg, engine, scenario = _engine_for("http", n_rules=6,
                                        n_flows=80)
    cfg.engine.verdict_memo = False
    replay, cols = _replay_for(engine, cfg, scenario.flows)
    want = engine.verdict_flows(scenario.flows)["verdict"]
    out = replay.verdict_chunk(cols.rec, cols.l7)
    np.testing.assert_array_equal(out["verdict"], want)
    assert replay.memo is None


def test_memo_invalidated_on_policy_generation_bump():
    """Any committed Loader revision (here: the raw generation bump)
    drops the memo; the next chunk refills and verdicts stay
    bit-equal."""
    from cilium_tpu.engine.memo import POLICY_GENERATION

    cfg, engine, scenario = _engine_for("http", n_rules=8,
                                        n_flows=120)
    replay, cols = _replay_for(engine, cfg, scenario.flows)
    want = engine.verdict_flows(scenario.flows)["verdict"]
    out1 = replay.verdict_chunk(cols.rec, cols.l7)
    np.testing.assert_array_equal(out1["verdict"], want)
    m = replay.memo
    inv0 = m.invalidations
    POLICY_GENERATION.bump()
    out2 = replay.verdict_chunk(cols.rec, cols.l7)
    np.testing.assert_array_equal(out2["verdict"], want)
    assert m.invalidations == inv0 + 1
    assert m.misses == 2 * replay.n_unique  # refilled once


def test_memo_keys_on_auth_view():
    """A different auth view can never read another view's memoized
    verdicts: the memo invalidates on signature change and enforces
    drop-until-authed exactly like the full step."""
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
        endpoint_selector=EndpointSelector.from_labels(app="pay"),
        ingress=(IngressRule(
            from_endpoints=(EndpointSelector.from_labels(app="cart"),),
            auth_mode="required",
            to_ports=(PortRule(
                ports=(PortProtocol(8443, Protocol.TCP),)),)),),
    )]
    alloc = IdentityAllocator()
    pay = alloc.allocate(LabelSet.from_dict({"app": "pay"}))
    cart = alloc.allocate(LabelSet.from_dict({"app": "cart"}))
    cache = SelectorCache(alloc)
    repo = Repository()
    repo.add(rules, sanitize=False)
    per_identity = {pay: PolicyResolver(repo, cache).resolve(
        alloc.lookup(pay))}
    cfg = Config()
    cfg.enable_tpu_offload = True
    engine = Loader(cfg).regenerate(per_identity, revision=1)
    flows = [Flow(src_identity=cart, dst_identity=pay, dport=8443)]
    replay, cols = _replay_for(engine, cfg, flows)
    authed = np.array([[cart, pay]], dtype=np.int32)
    out_closed = replay.verdict_chunk(cols.rec, cols.l7,
                                      authed_pairs=None)
    assert int(out_closed["verdict"][0]) == 2  # fail closed
    inv0 = replay.memo.invalidations
    out_authed = replay.verdict_chunk(cols.rec, cols.l7,
                                      authed_pairs=authed)
    assert int(out_authed["verdict"][0]) == 1  # authed forwards
    assert replay.memo.invalidations == inv0 + 1


def test_prefetched_id_chunks_replay_identically():
    """Sequential chunked replay (which auto-prefetches chunk N+1's
    id stream) must equal the unchunked truth."""
    cfg, engine, scenario = _engine_for("fqdn", n_rules=6,
                                        n_flows=180)
    replay, cols = _replay_for(engine, cfg, scenario.flows)
    want = engine.verdict_flows(scenario.flows)["verdict"]
    got = []
    for s in range(0, len(cols.rec), 48):
        got.extend(replay.verdict_chunk(
            cols.rec[s:s + 48], cols.l7[s:s + 48],
            start=s)["verdict"].tolist())
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# golden replay + hypothesis differential


@pytest.mark.slow
def test_golden_5000_flow_replay_bit_equal():
    """The acceptance differential at size: a 5000-flow replay
    through the full columnar pipeline (columnar encode → staged
    tables → hash dedup → memo gather) is bit-equal to the per-record
    featurize path."""
    cfg, engine, scenario = _engine_for("http", n_rules=100,
                                        n_flows=5000)
    replay, cols = _replay_for(engine, cfg, scenario.flows)
    want = engine.verdict_flows(scenario.flows)["verdict"]
    got = list(itertools.chain.from_iterable(
        replay.verdict_chunk(cols.rec[s:s + 512], cols.l7[s:s + 512],
                             start=s)["verdict"].tolist()
        for s in range(0, len(cols.rec), 512)))
    np.testing.assert_array_equal(got, want)
    m = replay.memo
    assert m.hits / (m.hits + m.misses) > 0.9
    assert len(set(got)) > 1


# the baked CI image may not carry hypothesis; only the property test
# below skips when it is absent — the rest of this module must run
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - depends on the image
    given = None

if given is not None:
    _ident = st.integers(min_value=1, max_value=5)
    _text = st.text(
        alphabet=st.characters(min_codepoint=33, max_codepoint=126),
        max_size=8)

    @st.composite
    def _flows(draw):
        from cilium_tpu.core.flow import (
            DNSInfo,
            GenericL7Info,
            HTTPInfo,
            KafkaInfo,
            L7Type,
        )

        out = []
        for _ in range(draw(st.integers(min_value=1, max_value=12))):
            kind = draw(st.sampled_from(
                ["none", "http", "kafka", "dns", "generic"]))
            f = Flow(
                src_identity=draw(_ident),
                dst_identity=draw(_ident),
                dport=draw(st.integers(min_value=1, max_value=9000)),
                sport=draw(st.integers(min_value=0, max_value=9000)),
                direction=draw(st.sampled_from(
                    [TrafficDirection.INGRESS,
                     TrafficDirection.EGRESS])))
            if kind == "http":
                f.l7 = L7Type.HTTP
                f.http = HTTPInfo(
                    method=draw(_text), path="/" + draw(_text),
                    host=draw(_text),
                    headers=tuple(
                        (draw(_text) or "k", draw(_text))
                        for _ in range(draw(st.integers(0, 2)))))
            elif kind == "kafka":
                f.l7 = L7Type.KAFKA
                f.kafka = KafkaInfo(
                    api_key=draw(st.integers(0, 3)), api_version=1,
                    client_id=draw(_text), topic=draw(_text))
            elif kind == "dns":
                f.l7 = L7Type.DNS
                f.dns = DNSInfo(query=draw(st.sampled_from(
                    ["", "a.example.com", "x.y.z", "*.bad"])))
            elif kind == "generic":
                f.l7 = L7Type.GENERIC
                f.generic = GenericL7Info(
                    proto=draw(st.sampled_from(
                        ["", "r2d2", "memcache"])),
                    fields={draw(_text): draw(_text)
                            for _ in range(draw(st.integers(0, 3)))})
            out.append(f)
        return out

    @given(flows=_flows())
    @settings(max_examples=40, deadline=None)
    def test_hypothesis_columnar_encoder_differential(flows):
        """Property: for ANY flow batch, the columnar encoder
        resolves to the same capture content as the per-record
        reference writer."""
        rec, l7, offsets, blob, gen, fmax = \
            binary.flows_to_capture_l7(flows)
        want = binary.records_to_flows_l7(rec, l7, offsets, blob,
                                          gen=gen)
        cols = flows_to_columns(flows)
        got = binary.records_to_flows_l7(
            cols.rec, cols.l7, cols.offsets, cols.blob, gen=cols.gen)
        assert got == want
