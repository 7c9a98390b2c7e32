"""v2 binary captures (L7 sidecar): roundtrip, vectorized-encode
parity, verdict parity vs the object path, validation.

VERDICT r2 item 2 / north star "replaying a Hubble capture": the
binary format now carries HTTP/Kafka/DNS payloads via a string table +
fixed 32B L7 records, and featurization is pure numpy gathers
(``engine.verdict.encode_l7_records``) — these tests pin that the
zero-Python path verdicts bit-identically to the per-flow object path.
"""

import numpy as np
import pytest

from cilium_tpu.core.config import Config
from cilium_tpu.core.flow import (
    DNSInfo,
    Flow,
    HTTPInfo,
    KafkaInfo,
    L7Type,
    Protocol,
    TrafficDirection,
)
from cilium_tpu.engine.verdict import (
    encode_flows,
    encode_l7_records,
    flowbatch_to_host_dict,
)
from cilium_tpu.ingest import binary, synth
from cilium_tpu.runtime.loader import Loader


def l7_flows():
    return [
        Flow(src_identity=1001, dst_identity=2002, dport=80,
             l7=L7Type.HTTP,
             http=HTTPInfo(method="GET", path="/api/v1/items/7",
                           host="SVC.Local",
                           headers=(("X-Role", "admin"),
                                    ("Accept", "json")))),
        Flow(src_identity=1001, dst_identity=2002, dport=9092,
             l7=L7Type.KAFKA,
             kafka=KafkaInfo(api_key=0, api_version=3,
                             client_id="producer-1", topic="orders")),
        Flow(src_identity=1001, dst_identity=2002, dport=53,
             protocol=Protocol.UDP, direction=TrafficDirection.EGRESS,
             l7=L7Type.DNS, dns=DNSInfo(query="API.Example.COM.")),
        Flow(src_identity=1001, dst_identity=2002, dport=443),
    ]


def test_v2_roundtrip_object_path(tmp_path):
    path = str(tmp_path / "cap2.bin")
    assert binary.write_capture_l7(path, l7_flows()) == 4
    assert binary.capture_count(path) == 4
    assert binary.capture_version(path) == binary.VERSION_L7
    back = binary.read_capture_flows_l7(path)
    assert back[0].http.path == "/api/v1/items/7"
    assert back[0].http.host == "svc.local"          # write-time lowercase
    assert dict(back[0].http.headers) == {"x-role": "admin",
                                          "accept": "json"}
    assert back[1].kafka.topic == "orders"
    assert back[1].kafka.api_version == 3
    # write-time sanitize (matchpattern.sanitize_name: lowercased,
    # FQDN trailing dot preserved — same form encode_flows feeds the
    # DNS automaton)
    assert back[2].dns.query == "api.example.com."
    assert back[3].l7 == L7Type.NONE


def test_v3_generic_flows_roundtrip(tmp_path):
    """Generic l7proto payloads ride the v3 GENERIC section (VERDICT
    r3 item 3): proto + (key, value) pairs roundtrip through the
    shared string table; a payload-less GENERIC flow still flattens
    to its L4 tuple (it could never match a rule)."""
    from cilium_tpu.core.flow import GenericL7Info

    path = str(tmp_path / "gen.bin")
    binary.write_capture_l7(path, [
        Flow(src_identity=1, dst_identity=2, dport=6379,
             l7=L7Type.GENERIC,
             generic=GenericL7Info(proto="r2d2",
                                   fields={"cmd": "GET",
                                           "file": "x.txt"})),
        Flow(src_identity=3, dst_identity=4, dport=6379,
             l7=L7Type.GENERIC),  # no payload → uncarriable
    ])
    assert binary.capture_version(path) == binary.VERSION_L7G
    assert binary.capture_count(path) == 2
    back = binary.read_capture_flows_l7(path)
    assert back[0].l7 == L7Type.GENERIC
    assert back[0].generic.proto == "r2d2"
    assert back[0].generic.fields == {"cmd": "GET", "file": "x.txt"}
    assert back[1].l7 == L7Type.NONE
    assert back[1].generic is None
    # a proto-only generic flow (zero field pairs) still forces the
    # GENERIC section — written as v2 it would re-verdict against an
    # absent payload on replay
    p2 = str(tmp_path / "protoonly.bin")
    binary.write_capture_l7(p2, [
        Flow(src_identity=1, dst_identity=2, dport=6379,
             l7=L7Type.GENERIC,
             generic=GenericL7Info(proto="r2d2", fields={}))])
    assert binary.capture_version(p2) == binary.VERSION_L7G
    (po,) = binary.read_capture_flows_l7(p2)
    assert po.l7 == L7Type.GENERIC
    assert po.generic.proto == "r2d2" and po.generic.fields == {}
    # truncating the GENERIC section is detected
    raw = open(path, "rb").read()
    trunc = tmp_path / "trunc.bin"
    trunc.write_bytes(raw[:-5])
    with pytest.raises(binary.CaptureError):
        binary.capture_count(str(trunc))


def test_v3_native_and_numpy_writers_agree(tmp_path, monkeypatch):
    if binary._native() is None:
        pytest.skip("native toolchain unavailable")
    from cilium_tpu.core.flow import GenericL7Info

    flows = l7_flows() + [
        Flow(src_identity=5, dst_identity=6, dport=4242,
             l7=L7Type.GENERIC,
             generic=GenericL7Info(proto="r2d2",
                                   fields={"cmd": "READ"}))]
    native_path = tmp_path / "native.bin"
    numpy_path = tmp_path / "numpy.bin"
    binary.write_capture_l7(str(native_path), flows)
    monkeypatch.setattr(binary, "_lib", None)
    monkeypatch.setattr(binary, "_lib_tried", True)
    binary.write_capture_l7(str(numpy_path), flows)
    assert native_path.read_bytes() == numpy_path.read_bytes()
    assert binary.capture_version(str(native_path)) == binary.VERSION_L7G
    # the numpy fallback validates + reads the native-written v3 file
    assert binary.capture_count(str(native_path)) == len(flows)
    gen = binary.read_gen_sidecar(str(native_path))
    assert gen is not None and int(gen["proto"][-1]) != 0


def test_v2_native_and_numpy_writers_agree(tmp_path, monkeypatch):
    if binary._native() is None:
        pytest.skip("native toolchain unavailable")
    native_path = tmp_path / "native.bin"
    numpy_path = tmp_path / "numpy.bin"
    binary.write_capture_l7(str(native_path), l7_flows())
    monkeypatch.setattr(binary, "_lib", None)
    monkeypatch.setattr(binary, "_lib_tried", True)
    binary.write_capture_l7(str(numpy_path), l7_flows())
    assert native_path.read_bytes() == numpy_path.read_bytes()
    # and the fallback validates/reads the native-written file
    assert binary.capture_count(str(native_path)) == 4
    l7, offsets, blob = binary.read_l7_sidecar(str(native_path))
    assert len(l7) == 4 and offsets[0] == 0
    assert int(offsets[-1]) == blob.size


def test_v2_validation(tmp_path):
    path = tmp_path / "cap2.bin"
    binary.write_capture_l7(str(path), l7_flows())
    raw = path.read_bytes()
    truncated = tmp_path / "trunc.bin"
    truncated.write_bytes(raw[:-7])
    with pytest.raises(binary.CaptureError):
        binary.capture_count(str(truncated))
    # a v1 capture has no sidecar to read
    v1 = tmp_path / "v1.bin"
    binary.write_capture(str(v1), l7_flows())
    with pytest.raises(binary.CaptureError):
        binary.read_l7_sidecar(str(v1))


def _scenario(which, n=300):
    if which == "http":
        return synth.synth_http_scenario(n_rules=25, n_flows=n)
    if which == "fqdn":
        return synth.synth_fqdn_scenario(n_names=20, n_rules=8,
                                         n_flows=n)
    if which == "kafka":
        return synth.synth_kafka_scenario(n_rules=15, n_records=n)
    return synth.synth_generic_scenario(n_rules=12, n_flows=n)


@pytest.mark.parametrize("which", ["http", "fqdn", "kafka", "generic"])
def test_v2_verdict_parity_with_flows_path(tmp_path, which):
    """The whole point: capture→gather→device verdicts == per-flow
    object-path verdicts, for every L7 family the capture carries
    (generic rides the v3 section)."""
    per_identity, scenario = synth.realize_scenario(_scenario(which))
    cfg = Config()
    cfg.enable_tpu_offload = True
    engine = Loader(cfg).regenerate(per_identity, revision=1)

    path = str(tmp_path / "cap2.bin")
    binary.write_capture_l7(path, scenario.flows)
    rec = binary.map_capture(path)
    l7, offsets, blob = binary.read_l7_sidecar(path)
    gen = binary.read_gen_sidecar(path)
    assert (gen is not None) == (which == "generic")

    via_capture = engine.verdict_l7_records(rec, l7, offsets, blob,
                                            gen=gen)
    via_flows = engine.verdict_flows(scenario.flows)
    np.testing.assert_array_equal(via_capture["verdict"],
                                  via_flows["verdict"])
    # flows must actually exercise both outcomes
    assert len(set(via_flows["verdict"].tolist())) > 1


def test_cli_v2_convert_info_fast_replay(tmp_path, capsys):
    """CLI plumbing: JSONL with L7 payloads converts to a v2 capture,
    `capture info` reports the sidecar, and --fast replay (columnar,
    sidecar-gathering) agrees with the object path on the same file."""
    import json

    from cilium_tpu import cli
    from cilium_tpu.ingest.hubble import flow_to_dict

    jsonl = tmp_path / "cap.jsonl"
    jsonl.write_text("\n".join(
        json.dumps(flow_to_dict(f)) for f in l7_flows()) + "\n")
    bin_path = tmp_path / "cap2.bin"
    assert cli.main(["capture", "convert", str(jsonl),
                     str(bin_path)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out == {"records": 4, "version": 2, "l7_payloads": 3}
    assert cli.main(["capture", "info", str(bin_path)]) == 0
    info = json.loads(capsys.readouterr().out)
    assert info["version"] == 2 and info["strings"] > 1

    cnp = tmp_path / "p.yaml"
    cnp.write_text("""
apiVersion: cilium.io/v2
kind: CiliumNetworkPolicy
metadata: {name: t}
spec:
  endpointSelector: {matchLabels: {app: svc}}
  ingress:
  - toPorts: [{ports: [{port: "80", protocol: TCP}],
               rules: {http: [{method: GET, path: "/api/.*"}]}}]
""")
    base = ["--policy", str(cnp), "--endpoint", "app=svc"]
    assert cli.main(["replay", str(bin_path)] + base) == 0
    slow = json.loads(capsys.readouterr().out)
    assert cli.main(["replay", str(bin_path), "--fast"] + base) == 0
    fast = json.loads(capsys.readouterr().out)
    assert fast == slow
    assert slow["flows"] == 4


@pytest.mark.parametrize("which", ["http", "fqdn", "kafka", "generic"])
def test_capture_replay_staged_tables_parity(tmp_path, which):
    """The staged-table replay path (string tables DFA-scanned once on
    device, chunks verdicted from row indices — verdict_step_capture)
    must agree bit-for-bit with verdict_flows, including across chunk
    boundaries."""
    from cilium_tpu.engine.verdict import CaptureReplay

    per_identity, scenario = synth.realize_scenario(_scenario(which))
    cfg = Config()
    cfg.enable_tpu_offload = True
    engine = Loader(cfg).regenerate(per_identity, revision=1)

    path = str(tmp_path / "cap2.bin")
    binary.write_capture_l7(path, scenario.flows)
    rec = binary.map_capture(path)
    l7, offsets, blob = binary.read_l7_sidecar(path)
    gen = binary.read_gen_sidecar(path)

    replay = CaptureReplay(engine, l7, offsets, blob, cfg.engine,
                           gen=gen)
    got = []
    for s in range(0, len(rec), 100):  # three chunks
        out = replay.verdict_chunk(rec[s:s + 100], l7[s:s + 100],
                                   start=s)
        got.extend(out["verdict"].tolist())
    want = engine.verdict_flows(scenario.flows)["verdict"]
    np.testing.assert_array_equal(got, want)
    assert len(set(want.tolist())) > 1


def test_cli_fast_tpu_uses_staged_replay_and_agrees(tmp_path, capsys):
    """--fast --tpu routes v2 captures through the CaptureReplay
    session (staged string tables); the summary must equal the object
    path's, chunked across the stream."""
    import json

    from cilium_tpu import cli
    from cilium_tpu.ingest.hubble import flow_to_dict

    scenario = synth.synth_http_scenario(n_rules=12, n_flows=120)
    _, scenario = synth.realize_scenario(scenario)
    for f in scenario.flows:
        f.src_labels = ()
        f.dst_labels = ()
    jsonl = tmp_path / "cap.jsonl"
    jsonl.write_text("\n".join(
        json.dumps(flow_to_dict(f)) for f in scenario.flows) + "\n")
    bin_path = tmp_path / "cap2.bin"
    assert cli.main(["capture", "convert", str(jsonl),
                     str(bin_path)]) == 0
    capsys.readouterr()
    cnp = tmp_path / "p.yaml"
    cnp.write_text("""
apiVersion: cilium.io/v2
kind: CiliumNetworkPolicy
metadata: {name: t}
spec:
  endpointSelector: {matchLabels: {app: svc}}
  ingress:
  - toPorts: [{ports: [{port: "80", protocol: TCP}],
               rules: {http: [{method: GET, path: "/api/.*"}]}}]
""")
    base = ["--policy", str(cnp), "--endpoint", "app=svc", "--tpu"]
    assert cli.main(["replay", str(bin_path)] + base) == 0
    slow = json.loads(capsys.readouterr().out)
    assert cli.main(["replay", str(bin_path), "--fast"] + base) == 0
    fast = json.loads(capsys.readouterr().out)
    assert fast == slow
    assert slow["flows"] == 120


def test_generic_capture_hypothesis_differential(tmp_path):
    """Generative sweep over the v3 generic lane: random l7proto
    rules × random generic payloads must verdict identically on the
    oracle, the TPU-gated object path, the columnar capture path, and
    the staged-table replay — including presence-only constraints,
    unknown protos, and Fmax-overflow field maps."""
    import itertools
    import random

    from cilium_tpu.core.flow import (
        Flow,
        GenericL7Info,
        L7Type,
        TrafficDirection,
    )
    from cilium_tpu.engine.verdict import CaptureReplay
    from cilium_tpu.policy.api import (
        EndpointSelector,
        IngressRule,
        L7Rules,
        PortProtocol,
        PortRule,
        Rule,
    )
    from cilium_tpu.core.identity import IdentityAllocator
    from cilium_tpu.core.labels import LabelSet
    from cilium_tpu.policy.mapstate import PolicyResolver
    from cilium_tpu.policy.oracle import OracleVerdictEngine
    from cilium_tpu.policy.repository import Repository
    from cilium_tpu.policy.selectorcache import SelectorCache

    from cilium_tpu.policy.compiler import frontends

    rng = random.Random(77)
    keys = ["cmd", "file", "op", "mode", "extra1", "extra2"]
    vals = ["GET", "PUT", "x.txt", "y.txt", "on", ""]
    # proxy-only protos (no engine frontend): the sweep exercises the
    # generic PAIR path, whose key/value universe is open — frontend
    # protos like r2d2 now validate rule keys at compile and route to
    # the l7g automaton instead (tests/test_frontends.py covers them).
    # Registration is required since ISSUE 15: an unknown l7proto
    # fails the compile loudly.
    protos = ["test.lineparser", "custom", "memq"]
    for p in ("custom", "memq"):
        frontends.register_proxy_parser(p)
    seen_verdicts: set = set()

    for trial in range(6):
        n_rules = rng.randint(1, 5)
        gen_rules = []
        for _ in range(n_rules):
            constraint = {
                k: rng.choice(vals)
                for k in rng.sample(keys, rng.randint(0, 3))
            }
            gen_rules.append(constraint)
        proto = rng.choice(protos)
        rules = [Rule(
            endpoint_selector=EndpointSelector.from_labels(app="svc"),
            ingress=(IngressRule(to_ports=(PortRule(
                ports=(PortProtocol(4242, Protocol.TCP),),
                rules=L7Rules(l7proto=proto, l7=tuple(gen_rules)),
            ),)),),
            labels=(f"trial={trial}",),
        )]
        alloc = IdentityAllocator()
        svc = alloc.allocate(LabelSet.from_dict({"app": "svc"}))
        cache = SelectorCache(alloc)
        repo = Repository()
        repo.add(rules, sanitize=False)
        per_identity = {
            svc: PolicyResolver(repo, cache).resolve(alloc.lookup(svc))}

        flows = []
        for i in range(40):
            fp = rng.choice(protos + [proto, proto])  # bias to match
            nf = rng.randint(0, 6)  # up to 6 fields: Fmax overflow
            fields = {k: rng.choice(vals[:-1])
                      for k in rng.sample(keys, nf)}
            flows.append(Flow(
                src_identity=9, dst_identity=svc, dport=4242,
                protocol=Protocol.TCP,
                direction=TrafficDirection.INGRESS,
                l7=L7Type.GENERIC,
                generic=GenericL7Info(proto=fp, fields=fields)))

        oracle = OracleVerdictEngine(per_identity)
        want = oracle.verdict_flows(flows)["verdict"]

        cfg = Config()
        cfg.enable_tpu_offload = True
        engine = Loader(cfg).regenerate(per_identity, revision=1)
        got_obj = engine.verdict_flows(flows)["verdict"]
        np.testing.assert_array_equal(
            got_obj, want, err_msg=f"object path trial {trial}")

        path = str(tmp_path / f"gen{trial}.bin")
        binary.write_capture_l7(path, flows)
        rec = binary.map_capture(path)
        l7, offsets, blob = binary.read_l7_sidecar(path)
        gen = binary.read_gen_sidecar(path)
        got_col = engine.verdict_l7_records(
            rec, l7, offsets, blob, gen=gen)["verdict"]
        np.testing.assert_array_equal(
            got_col, want, err_msg=f"columnar path trial {trial}")

        replay = CaptureReplay(engine, l7, offsets, blob, cfg.engine,
                               gen=gen)
        replay.stage_rows(rec, l7)
        got_staged = list(itertools.chain.from_iterable(
            replay.verdict_chunk(rec[s:s + 16], l7[s:s + 16],
                                 start=s)["verdict"].tolist()
            for s in range(0, len(rec), 16)))
        np.testing.assert_array_equal(
            got_staged, want, err_msg=f"staged path trial {trial}")
        # dedup replay (unique-row table + id stream) is lossless:
        # chunked verdicts through verdict_idx equal every other path
        ratio = replay.stage_unique()
        assert 0 < ratio <= 1.0
        got_dedup = list(itertools.chain.from_iterable(
            np.asarray(replay.verdict_idx(
                replay.row_idx[s:s + 16])["verdict"]).tolist()
            for s in range(0, len(rec), 16)))
        np.testing.assert_array_equal(
            got_dedup, want, err_msg=f"dedup path trial {trial}")
        seen_verdicts |= set(int(v) for v in want)

    # the sweep exercised allow AND deny, not one degenerate outcome
    assert {2, 5} <= seen_verdicts, seen_verdicts


def test_cli_generic_capture_replays_like_jsonl_twin(tmp_path, capsys):
    """VERDICT r3 item 3 'done' criterion: a generic-rule capture
    (v3 binary) replays file→verdict with verdicts identical to its
    JSONL twin, through BOTH the columnar and the --fast staged-table
    paths."""
    import json

    from cilium_tpu import cli
    from cilium_tpu.ingest.hubble import flow_to_dict

    scenario = synth.synth_generic_scenario(n_rules=9, n_flows=120)
    _, scenario = synth.realize_scenario(scenario)
    for f in scenario.flows:
        f.src_labels = ()
        f.dst_labels = ()
    jsonl = tmp_path / "cap.jsonl"
    jsonl.write_text("\n".join(
        json.dumps(flow_to_dict(f)) for f in scenario.flows) + "\n")
    bin_path = tmp_path / "cap3.bin"
    assert cli.main(["capture", "convert", str(jsonl),
                     str(bin_path)]) == 0
    conv = json.loads(capsys.readouterr().out)
    assert conv["version"] == binary.VERSION_L7G
    cnp = tmp_path / "p.yaml"
    cnp.write_text("""
apiVersion: cilium.io/v2
kind: CiliumNetworkPolicy
metadata: {name: t}
spec:
  endpointSelector: {matchLabels: {app: r2d2}}
  ingress:
  - toPorts: [{ports: [{port: "4242", protocol: TCP}],
               rules: {l7proto: r2d2,
                       l7: [{cmd: READ, file: f0.txt},
                            {cmd: HALT}]}}]
""")
    base = ["--policy", str(cnp), "--endpoint", "app=r2d2", "--tpu"]
    assert cli.main(["replay", str(jsonl)] + base) == 0
    twin = json.loads(capsys.readouterr().out)
    assert cli.main(["replay", str(bin_path)] + base) == 0
    slow = json.loads(capsys.readouterr().out)
    assert cli.main(["replay", str(bin_path), "--fast"] + base) == 0
    fast = json.loads(capsys.readouterr().out)
    assert slow["verdicts"] == twin["verdicts"]
    assert fast["verdicts"] == twin["verdicts"]
    assert twin["flows"] == 120
    assert len(twin["verdicts"]) > 1  # both outcomes exercised


def test_capture_replay_enforces_auth_pairs(tmp_path):
    """Drop-until-authed rides the capture path too: the same
    authed-pairs table drives verdict_step_capture and verdict_flows
    to identical verdicts (fail-closed without the handshake, forward
    with it)."""
    from cilium_tpu.core.identity import IdentityAllocator
    from cilium_tpu.core.labels import LabelSet
    from cilium_tpu.engine.verdict import CaptureReplay
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
    resolver = PolicyResolver(repo, cache)
    per_identity = {pay: resolver.resolve(alloc.lookup(pay))}
    cfg = Config()
    cfg.enable_tpu_offload = True
    engine = Loader(cfg).regenerate(per_identity, revision=1)

    flows = [Flow(src_identity=cart, dst_identity=pay, dport=8443)]
    path = str(tmp_path / "auth.bin")
    binary.write_capture_l7(path, flows)
    rec = binary.map_capture(path)
    l7, offsets, blob = binary.read_l7_sidecar(path)
    replay = CaptureReplay(engine, l7, offsets, blob, cfg.engine)

    replay.stage_rows(rec, l7)
    replay.stage_unique()
    for pairs, want in (
            (None, 2),                                    # fail closed
            (np.array([[cart, pay]], dtype=np.int32), 1),  # authed
    ):
        via_cap = replay.verdict_chunk(rec, l7, authed_pairs=pairs)
        via_flows = engine.verdict_flows(flows, authed_pairs=pairs)
        # the dedup id stream enforces identically (regression: its
        # first cut skipped _stage_auth, silently forwarding unauthed
        # auth-demanding flows on this path only)
        via_idx = replay.verdict_idx(replay.row_idx,
                                     authed_pairs=pairs)
        assert int(via_cap["verdict"][0]) == want
        assert int(via_flows["verdict"][0]) == want
        assert int(np.asarray(via_idx["verdict"])[0]) == want
        assert bool(via_cap["auth_required"][0])


def test_encode_l7_matches_encode_flows(tmp_path):
    """Array-level parity: the vectorized gather featurizer produces
    the SAME FlowBatch tensors as the per-flow encoder."""
    scenario = synth.synth_http_scenario(n_rules=10, n_flows=120)
    per_identity, scenario = synth.realize_scenario(scenario)
    cfg = Config()
    cfg.enable_tpu_offload = True
    engine = Loader(cfg).regenerate(per_identity, revision=1)
    interns = engine.policy.kafka_interns

    path = str(tmp_path / "cap2.bin")
    binary.write_capture_l7(path, scenario.flows)
    rec = binary.map_capture(path)
    l7, offsets, blob = binary.read_l7_sidecar(path)

    a = flowbatch_to_host_dict(encode_flows(scenario.flows, interns,
                                            cfg.engine))
    b = flowbatch_to_host_dict(encode_l7_records(rec, l7, offsets, blob,
                                                 interns, cfg.engine))
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_cli_capture_synth_is_reproducible(tmp_path, capsys):
    import json

    from cilium_tpu import cli

    a, b = str(tmp_path / "a.bin"), str(tmp_path / "b.bin")
    for out in (a, b):
        assert cli.main(["capture", "synth", out, "--scenario", "http",
                         "--rules", "10", "--flows", "200",
                         "--seed", "7"]) == 0
        rec = json.loads(capsys.readouterr().out)
        assert rec["records"] == 200 and rec["version"] == 2
    assert open(a, "rb").read() == open(b, "rb").read()  # same seed


def test_replay_chunk_readback_counts_one_host_sync(tmp_path):
    from cilium_tpu.engine.verdict import CaptureReplay
    from cilium_tpu.runtime.metrics import ENGINE_HOST_SYNCS, METRICS

    per_identity, scenario = synth.realize_scenario(_scenario("http"))
    cfg = Config()
    cfg.enable_tpu_offload = True
    engine = Loader(cfg).regenerate(per_identity, revision=1)
    path = str(tmp_path / "cap.bin")
    binary.write_capture_l7(path, scenario.flows)
    rec = binary.map_capture(path)
    l7, offsets, blob = binary.read_l7_sidecar(path)
    replay = CaptureReplay(engine, l7, offsets, blob, cfg.engine)
    labels = {"site": "capture.readback"}
    before = METRICS.get(ENGINE_HOST_SYNCS, labels)
    for s in range(0, 200, 100):
        replay.verdict_chunk(rec[s:s + 100], l7[s:s + 100], start=s)
    assert METRICS.get(ENGINE_HOST_SYNCS, labels) == before + 2
