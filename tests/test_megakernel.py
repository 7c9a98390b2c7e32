"""Megakernel differential suite (ISSUE 9 acceptance).

The fused verdict step (`engine/megakernel.py`) must be BIT-EQUAL to
the unfused `verdict_step` on every output lane, for both scan arms a
field can be planned onto — the rule's plan and the explicit NFA plan
(`megakernel.nfa_plan`) — over the golden 5000-flow corpus, curated
edge policies (LOG header matches, dead secret-backed rules,
multi-ruleset membership), and hypothesis-random rule banks/payloads.
Plus the bitset-NFA arm's word-level equality with the dense DFA, the
Pallas kernel in interpret mode, and the rule's per-field plan.
"""

import numpy as np
import pytest

from cilium_tpu.core.config import Config
from cilium_tpu.core.flow import (
    Flow,
    HTTPInfo,
    L7Type,
    Protocol,
    TrafficDirection,
)
from cilium_tpu.ingest import synth
from cilium_tpu.policy.api import (
    EndpointSelector,
    IngressRule,
    L7Rules,
    PortProtocol,
    PortRule,
    PortRuleHTTP,
    Rule,
)
from cilium_tpu.policy.api.l7 import HeaderMatch
from cilium_tpu.runtime.loader import Loader

OUTPUT_LANES = ("verdict", "allowed", "l3l4_allowed", "redirect",
                "l7_ok", "l7_log", "match_spec", "ruleset",
                "auth_required")


def _cfg(**engine_kw):
    cfg = Config.from_env()
    cfg.enable_tpu_offload = True
    for k, v in engine_kw.items():
        setattr(cfg.engine, k, v)
    return cfg


def _engine(per_identity, cfg):
    return Loader(cfg).regenerate(per_identity, revision=1), cfg


def _nfa_engine(per_identity, cfg):
    """An engine restaged on the explicit NFA plan: every field whose
    banks fit the arm's position budget scans through it."""
    from cilium_tpu.engine import megakernel

    engine, cfg = _engine(per_identity, cfg)
    engine.stage_plan(megakernel.nfa_plan(engine.policy, cfg.engine))
    return engine, cfg


def _plan_on_tpu(engine, cfg, monkeypatch):
    """The rule's plan for ``engine``'s policy as a TPU backend would
    stage it. Only the planning sees the "tpu" reading: the step then
    traces on the CPU, where the NFA arm runs in plain XLA."""
    import jax

    from cilium_tpu.engine import megakernel

    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        return megakernel.plan_for_engine(engine.policy, cfg.engine)


def _assert_fused_equals_legacy(engine, flows, cfg):
    """Engine's staged (fused) step vs the legacy verdict_step, all
    output lanes."""
    import jax

    from cilium_tpu.engine.verdict import (
        encode_flows,
        flowbatch_to_host_dict,
        verdict_step,
    )

    host = flowbatch_to_host_dict(encode_flows(
        flows, engine.policy.kafka_interns, cfg.engine))
    batch = {k: jax.device_put(v) for k, v in host.items()}
    want = jax.jit(verdict_step)(engine._arrays, batch)
    got = engine.verdict_batch_arrays(batch)
    assert set(want) == set(got)
    for k in OUTPUT_LANES:
        np.testing.assert_array_equal(np.asarray(want[k]),
                                      np.asarray(got[k]), err_msg=k)


@pytest.mark.parametrize("config,n_rules", [
    ("http", 300), ("fqdn", 200), ("kafka", 100), ("generic", 50)])
def test_fused_bit_equal_per_config(config, n_rules):
    per_identity, scenario = synth.realize_scenario(
        synth.scenario_by_name(config, n_rules, 512))
    engine, cfg = _engine(per_identity, _cfg())
    assert engine.impl_plan, "fused step should be staged by default"
    _assert_fused_equals_legacy(engine, scenario.flows, cfg)


# ---------------------------------------------------------- edge policies
def _http_policy(http_rules, secrets=None, n_selectors=1):
    """Realize a policy whose http rules split across ``n_selectors``
    endpoint selectors — multi-ruleset membership for the group
    factoring to chew on."""
    sel = EndpointSelector.from_labels
    rules = []
    chunk = max(1, len(http_rules) // n_selectors)
    for i in range(n_selectors):
        sub = http_rules[i * chunk:(i + 1) * chunk] or http_rules[:1]
        rules.append(Rule(
            endpoint_selector=sel(app=f"server{i}"),
            ingress=(IngressRule(
                from_endpoints=(sel(app="client"),),
                to_ports=(PortRule(
                    ports=(PortProtocol(80, Protocol.TCP),),
                    rules=L7Rules(http=tuple(sub))),)),),
            labels=(f"mk={i}",)))
    endpoints = {f"server{i}": {"app": f"server{i}"}
                 for i in range(n_selectors)}
    endpoints["client"] = {"app": "client"}
    scenario = synth.SynthScenario(name="mk", rules=rules,
                                   endpoints=endpoints, flows=[])
    return synth.realize_scenario(scenario)


def _flows(ids, paths, headers=(), n_servers=1):
    out = []
    for i, p in enumerate(paths):
        for s in range(n_servers):
            out.append(Flow(
                src_identity=ids["client"],
                dst_identity=ids[f"server{s}"],
                dport=80, direction=TrafficDirection.INGRESS,
                l7=L7Type.HTTP,
                http=HTTPInfo(method=("GET", "POST")[i % 2], path=p,
                              host="svc.local",
                              headers=tuple(headers))))
    return out


def test_fused_log_lanes_and_dead_rules():
    """LOG-action header matches (the l7_log lane) and a dead rule
    (unresolvable FAIL secret) ride the group signature exactly."""
    http = [
        PortRuleHTTP(path="/log/.*", header_matches=(
            HeaderMatch(name="X-Trace", value="on",
                        mismatch_action="LOG"),)),
        PortRuleHTTP(path="/fail/.*", header_matches=(
            HeaderMatch(name="X-Tok", mismatch_action="",
                        secret=("ns", "missing")),)),
        PortRuleHTTP(path="/open/.*"),
        PortRuleHTTP(method="GET"),  # path-unconstrained group
    ]
    per_identity, scenario = _http_policy(http)
    engine, cfg = _engine(per_identity, _cfg())
    ids = scenario.ids
    flows = _flows(ids, ["/log/a", "/log/b", "/fail/x", "/open/y",
                         "/none", "/log/c"],
                   headers=(("X-Trace", "off"),))
    flows += _flows(ids, ["/log/a"], headers=(("X-Trace", "on"),))
    _assert_fused_equals_legacy(engine, flows, cfg)
    # and the semantics are live: some l7_log set, dead rule denies
    out = engine.verdict_flows(flows)
    assert out["l7_log"].any()


def test_fused_multi_ruleset_membership():
    """The same rule signature under different ruleset memberships
    must stay in separate groups — a flow's ruleset must only see its
    own members' path lanes."""
    http = [PortRuleHTTP(method="GET", path=f"/svc{i}/[a-z]+")
            for i in range(12)]
    per_identity, scenario = _http_policy(http, n_selectors=3)
    engine, cfg = _engine(per_identity, _cfg())
    ids = scenario.ids
    flows = _flows(ids, [f"/svc{i}/abc" for i in range(12)],
                   n_servers=3)
    _assert_fused_equals_legacy(engine, flows, cfg)
    out = engine.verdict_flows(flows)
    # server0 serves rules 0-3 only: its flows for /svc8 must drop
    assert len(set(np.asarray(out["verdict"]).tolist())) > 1


def test_plan_degenerate_falls_back_to_legacy_resolve(monkeypatch):
    from cilium_tpu.engine import megakernel

    monkeypatch.setattr(megakernel, "GROUP_CAP", 1)
    per_identity, scenario = synth.realize_scenario(
        synth.synth_http_scenario(n_rules=60, n_flows=128))
    cfg = _cfg()
    # a cached artifact compiled under the real GROUP_CAP would carry
    # its plan regardless of the monkeypatch — force a fresh compile
    cfg.loader.enable_cache = False
    engine, cfg = _engine(per_identity, cfg)
    assert engine.policy.resolve_meta is None
    assert "rp_g_method" not in engine.policy.arrays
    _assert_fused_equals_legacy(engine, scenario.flows, cfg)


def test_no_http_rules_policy():
    per_identity, scenario = synth.realize_scenario(
        synth.scenario_by_name("fqdn", 20, 64))
    engine, cfg = _engine(per_identity, _cfg())
    _assert_fused_equals_legacy(engine, scenario.flows, cfg)


# ------------------------------------------------- bitset-NFA arm equality
PATTERNS = [
    "/api/v[0-9]+/users/.*", "GET|POST", "foo(bar)?baz", "a{2,4}b",
    "[a-c]+x", "(ab|cd)*", "x[^0-9]y", "h?ello+", "", ".*",
]


def _rand_payloads(n=300, L=32, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size=(n, L)).astype(np.uint8)
    for i, s in enumerate(["/api/v1/users/42", "GET", "foobarbaz",
                           "aab", "abab", "xay", "hello", "", "cd",
                           "aaab"]):
        b = s.encode()
        data[i, :len(b)] = np.frombuffer(b, np.uint8)
        data[i, len(b):] = 0
    lens = rng.integers(0, L + 1, size=(n,)).astype(np.int32)
    lens[:10] = [16, 3, 9, 3, 4, 3, 5, 0, 2, 4]
    return data, lens


def test_nfa_scan_words_equal_dense():
    import jax
    import jax.numpy as jnp

    from cilium_tpu.core.config import EngineConfig
    from cilium_tpu.engine import nfa_kernel
    from cilium_tpu.engine.dfa_kernel import dfa_scan_banked
    from cilium_tpu.policy.compiler.dfa import compile_patterns

    banked = compile_patterns(PATTERNS, bank_size=4)
    st = banked.stacked()
    data, lens = _rand_payloads()
    want = np.asarray(dfa_scan_banked(
        jnp.asarray(st["trans"]), jnp.asarray(st["byteclass"]),
        jnp.asarray(st["start"]), jnp.asarray(st["accept"]),
        jnp.asarray(data), jnp.asarray(lens)))
    banks = nfa_kernel.banks_from_dfa(banked, EngineConfig())
    assert banks is not None
    stacked = {k: jnp.asarray(v)
               for k, v in nfa_kernel.stack_nfa_banks(banks).items()}
    got = np.asarray(jax.jit(
        lambda s, d, l: nfa_kernel.nfa_scan_banked(s, d, l))(
        stacked, jnp.asarray(data), jnp.asarray(lens)))
    np.testing.assert_array_equal(want, got)


def test_pallas_nfa_interpret_equals_dense():
    import jax
    import jax.numpy as jnp

    from cilium_tpu.core.config import EngineConfig
    from cilium_tpu.engine import nfa_kernel
    from cilium_tpu.engine.dfa_kernel import dfa_scan_banked
    from cilium_tpu.policy.compiler.dfa import compile_patterns

    banked = compile_patterns(PATTERNS, bank_size=4)
    st = banked.stacked()
    data, lens = _rand_payloads(n=48, L=16, seed=3)
    want = np.asarray(dfa_scan_banked(
        jnp.asarray(st["trans"]), jnp.asarray(st["byteclass"]),
        jnp.asarray(st["start"]), jnp.asarray(st["accept"]),
        jnp.asarray(data), jnp.asarray(lens)))
    banks = nfa_kernel.banks_from_dfa(banked, EngineConfig())
    stacked = {k: jnp.asarray(v)
               for k, v in nfa_kernel.stack_nfa_banks(banks).items()}
    got = np.asarray(jax.jit(
        lambda s, d, l: nfa_kernel.nfa_scan_banked(
            s, d, l, interpret=True))(
        stacked, jnp.asarray(data), jnp.asarray(lens)))
    np.testing.assert_array_equal(want, got)


def test_forced_nfa_arm_full_engine_bit_equal():
    """The explicit NFA plan puts the arm engine-wide (bank_size small
    enough that every bank fits the position budget) — the full
    verdict must still be bit-equal."""
    per_identity, scenario = synth.realize_scenario(
        synth.synth_http_scenario(n_rules=24, n_flows=256))
    engine, cfg = _nfa_engine(per_identity, _cfg(bank_size=4))
    assert "nfa-bitset" in engine.impl_plan.values(), engine.kernel_report
    assert engine.policy.kernel_plan == engine.impl_plan
    _assert_fused_equals_legacy(engine, scenario.flows, cfg)


def test_forced_nfa_ineligible_falls_back_dense(monkeypatch):
    """An ineligible bank plans dense: the 200-rule path banks pass 128
    DFA states, but their positions bust the NFA arm's budget, so the
    rule keeps them dense even on a TPU, and the explicit NFA plan
    cannot take them either — verdicts unchanged."""
    per_identity, scenario = synth.realize_scenario(
        synth.synth_http_scenario(n_rules=200, n_flows=64))
    engine, cfg = _engine(per_identity, _cfg())
    plan, extra, report = _plan_on_tpu(engine, cfg, monkeypatch)
    assert report["path"]["dfa_states"] > 128
    assert report["path"]["nfa_positions"] is None
    assert plan["path"] == "dfa-dense"
    assert not any(k.startswith("path_") for k in extra)
    engine, cfg = _nfa_engine(per_identity, cfg)
    assert engine.impl_plan["path"] == "dfa-dense"
    _assert_fused_equals_legacy(engine, scenario.flows, cfg)


#: a path the DFA needs 2^8 states for (it remembers which of the last
#: eight bytes was an "x") and the NFA 12 positions: the NFA arm's regime
WIDE_PATH = "/a/.*x[a-z][a-z][a-z][a-z][a-z][a-z][a-z]"


def _wide_path_policy():
    per_identity, scenario = _http_policy([
        PortRuleHTTP(path=WIDE_PATH, method="GET"),
        PortRuleHTTP(path="/b/[0-9]+", method="POST")])
    flows = _flows(scenario.ids, ["/a/zzxabcdefg", "/a/xabcdef",
                                  "/a/qqxqqqqqqqq", "/b/42", "/b/x"])
    return per_identity, flows


def test_rule_plans_nfa_for_a_wide_eligible_stack_on_tpu(monkeypatch):
    """On a TPU the rule puts a stack past 128 DFA states whose NFA fits
    on the NFA arm, and keeps the small stacks dense; off it every
    stack stays dense. The TPU plan's verdicts are bit-equal."""
    per_identity, flows = _wide_path_policy()
    engine, cfg = _engine(per_identity, _cfg())
    assert set(engine.impl_plan.values()) == {"dfa-dense"}
    plan = _plan_on_tpu(engine, cfg, monkeypatch)
    impl_plan, extra, report = plan
    assert report["path"]["dfa_states"] > 128
    assert impl_plan["path"] == "nfa-bitset"
    assert "path_nfa_follow" in extra
    assert {impl_plan[f] for f in ("method", "host", "hdr", "dns")} \
        == {"dfa-dense"}
    engine.stage_plan(plan)
    _assert_fused_equals_legacy(engine, flows, cfg)


def test_quarantined_build_plans_dense(monkeypatch):
    """A build serving quarantined banks (stale covers the NFA cannot
    rebuild) keeps every stack dense, even where the rule would give
    the NFA arm the stack on a TPU."""
    import dataclasses

    per_identity, _ = _wide_path_policy()
    engine, cfg = _engine(per_identity, _cfg())
    engine.policy = dataclasses.replace(engine.policy,
                                        bank_quarantined=("stale",))
    impl_plan, extra, _ = _plan_on_tpu(engine, cfg, monkeypatch)
    assert set(impl_plan.values()) == {"dfa-dense"}
    assert not extra


# ------------------------------------------------ golden corpus (at size)
@pytest.mark.slow
def test_golden_5000_flow_fused_bit_equal_both_arms():
    """The acceptance differential at size: 5000 flows over a policy
    whose banks fit both arms; the fused step must be bit-equal to
    the unfused step on the rule's plan and on the explicit NFA plan,
    and through capture replay (the staged-table + group-word path)."""
    import itertools

    from cilium_tpu.engine.verdict import CaptureReplay
    from cilium_tpu.ingest import binary

    scen = synth.synth_http_scenario(n_rules=48, n_flows=5000)
    for stage in (_engine, _nfa_engine):
        per_identity, scenario = synth.realize_scenario(scen)
        engine, cfg = stage(per_identity, _cfg(bank_size=4))
        if stage is _nfa_engine:
            assert "nfa-bitset" in engine.impl_plan.values()
        _assert_fused_equals_legacy(engine, scenario.flows, cfg)

    # capture replay over the same corpus (dense arm), chunked
    import tempfile, os

    per_identity, scenario = synth.realize_scenario(scen)
    engine, cfg = _engine(per_identity, _cfg())
    cap = os.path.join(tempfile.mkdtemp(), "mk_golden.bin")
    binary.write_capture_l7(cap, scenario.flows)
    rec = binary.map_capture(cap)
    l7, offsets, blob = binary.read_l7_sidecar(cap)
    replay = CaptureReplay(engine, l7, offsets, blob, cfg.engine,
                           gen=binary.read_gen_sidecar(cap))
    assert "path_groups" in replay.table_words
    replay.stage_rows(rec, l7)
    replay.stage_unique(drop_if_ratio_at_least=0.9)
    got = list(itertools.chain.from_iterable(
        replay.verdict_chunk(rec[s:s + 512], l7[s:s + 512],
                             start=s)["verdict"].tolist()
        for s in range(0, len(rec), 512)))
    want = engine.verdict_flows(scenario.flows)["verdict"]
    np.testing.assert_array_equal(got, want)
    assert len(set(got)) > 1


# ------------------------------------------------------ hypothesis fuzzing
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - depends on the image
    given = None

if given is not None:
    _short = st.text(alphabet="abcx/", min_size=0, max_size=6)
    _pattern = st.one_of(
        _short.map(lambda s: s.replace("/", "") or "a"),
        st.sampled_from(["/a/[a-c]+", "x(y|z)*", "ab{1,3}c", ".*b",
                         "a?b+c", "[^x]y"]),
    )
    _method = st.sampled_from(["", "GET", "PUT|POST"])
    _hdr = st.sampled_from([(), ("X-A: 1",), ("X-A: 1", "X-B: 2")])

    @st.composite
    def _policies(draw):
        rules = []
        for _ in range(draw(st.integers(1, 8))):
            rules.append(PortRuleHTTP(
                path=draw(_pattern), method=draw(_method),
                headers=draw(_hdr)))
        return rules

    @given(rules=_policies(),
           payloads=st.lists(_short, min_size=1, max_size=16),
           n_sel=st.integers(1, 2),
           data=st.data())
    @settings(max_examples=25, deadline=None)
    def test_hypothesis_fused_equals_legacy(rules, payloads, n_sel,
                                            data):
        """Random rule banks + random payloads: the fused step (the
        rule's plan, or the NFA plan where eligible) is bit-equal to
        the unfused step."""
        per_identity, scenario = _http_policy(rules,
                                              n_selectors=n_sel)
        stage = data.draw(st.sampled_from([_engine, _nfa_engine]))
        engine, cfg = stage(per_identity, _cfg(bank_size=4))
        flows = _flows(scenario.ids,
                       ["/" + p if not p.startswith("/") else p
                        for p in payloads],
                       headers=(("X-A", "1"),), n_servers=n_sel)
        _assert_fused_equals_legacy(engine, flows, cfg)


# --------------------------------------- kafka/generic factored groups
def test_kafka_rides_the_factored_plan_with_predicate_dedup():
    """ISSUE 11 satellite: kafka resolves on the factored path —
    identical predicates across rulesets collapse to ONE group whose
    ruleset membership is the OR of its members', the rp_k_* tables
    stage to device, and the fused resolve stays bit-equal to the
    legacy per-rule formula."""
    from cilium_tpu.core.flow import KafkaInfo
    from cilium_tpu.policy.api.l7 import PortRuleKafka

    sel = EndpointSelector.from_labels
    shared = [PortRuleKafka(role="produce", topic="orders"),
              PortRuleKafka(role="consume", topic="orders",
                            client_id="etl"),
              PortRuleKafka(role="produce", topic="audit")]
    rules = []
    for i in range(4):   # 4 rulesets x 3 identical predicates = 12 rules
        rules.append(Rule(
            endpoint_selector=sel(app=f"broker{i}"),
            ingress=(IngressRule(
                from_endpoints=(sel(app="producer"),),
                to_ports=(PortRule(
                    ports=(PortProtocol(9092, Protocol.TCP),),
                    rules=L7Rules(kafka=tuple(shared))),)),),
            labels=(f"kf={i}",)))
    endpoints = {f"broker{i}": {"app": f"broker{i}"} for i in range(4)}
    endpoints["producer"] = {"app": "producer"}
    per_identity, scenario = synth.realize_scenario(
        synth.SynthScenario(name="kfgroups", rules=rules,
                            endpoints=endpoints, flows=[]))
    engine, cfg = _engine(per_identity, _cfg())
    meta = engine.policy.resolve_meta
    assert meta is not None
    # 12 rules but only 3 distinct predicates -> 3 groups
    assert meta["kafka_groups"] == 3
    assert "rp_rs_kmask" in engine.policy.arrays
    ids = scenario.ids
    flows = []
    for b in range(4):
        for api_key, topic, client in [
                (0, "orders", "x"), (1, "orders", "etl"),
                (0, "audit", "x"), (1, "audit", "etl"),
                (0, "other", "x"), (-1, "orders", "x")]:
            flows.append(Flow(
                src_identity=ids["producer"],
                dst_identity=ids[f"broker{b}"], dport=9092,
                protocol=Protocol.TCP,
                direction=TrafficDirection.INGRESS,
                l7=L7Type.KAFKA,
                kafka=KafkaInfo(api_key=api_key, api_version=1,
                                client_id=client, topic=topic)))
    _assert_fused_equals_legacy(engine, flows, cfg)
    out = engine.verdict_flows(flows)
    verdicts = set(np.asarray(out["verdict"]).tolist())
    assert len(verdicts) > 1   # allows and denies both exercised


def test_generic_rides_the_factored_plan_with_predicate_dedup():
    """Generic (l7proto) rules dedup to (proto, pair-set) groups —
    pair ORDER inside a rule is predicate-irrelevant, so permuted
    copies collapse; resolve stays bit-equal. Uses a PROXY-ONLY
    proto (test.lineparser): frontend protos like r2d2 route to the
    l7g automaton path since ISSUE 15 and are covered by
    tests/test_frontends.py."""
    from cilium_tpu.core.flow import GenericL7Info
    from cilium_tpu.policy.api.l7 import PortRuleL7

    sel = EndpointSelector.from_labels
    rules = []
    for i in range(3):
        gen = (PortRuleL7(fields=(("cmd", "get"), ("table", "t1"))),
               # permuted duplicate of the first predicate
               PortRuleL7(fields=(("table", "t1"), ("cmd", "get"))),
               PortRuleL7(fields=(("cmd", "put"),)))
        rules.append(Rule(
            endpoint_selector=sel(app=f"db{i}"),
            ingress=(IngressRule(
                from_endpoints=(sel(app="client"),),
                to_ports=(PortRule(
                    ports=(PortProtocol(6379, Protocol.TCP),),
                    rules=L7Rules(l7proto="test.lineparser",
                                  l7=gen)),)),),
            labels=(f"gen={i}",)))
    endpoints = {f"db{i}": {"app": f"db{i}"} for i in range(3)}
    endpoints["client"] = {"app": "client"}
    per_identity, scenario = synth.realize_scenario(
        synth.SynthScenario(name="gengroups", rules=rules,
                            endpoints=endpoints, flows=[]))
    engine, cfg = _engine(per_identity, _cfg())
    meta = engine.policy.resolve_meta
    assert meta is not None
    # 9 rules, permuted duplicates collapse -> 2 distinct predicates
    assert meta["gen_groups"] == 2
    assert "rp_rs_genmask" in engine.policy.arrays
    ids = scenario.ids
    flows = []
    for d in range(3):
        for fields in ([("cmd", "get"), ("table", "t1")],
                       [("cmd", "put")],
                       [("cmd", "del")],
                       [("cmd", "get")]):
            flows.append(Flow(
                src_identity=ids["client"],
                dst_identity=ids[f"db{d}"], dport=6379,
                protocol=Protocol.TCP,
                direction=TrafficDirection.INGRESS,
                l7=L7Type.GENERIC,
                generic=GenericL7Info(proto="test.lineparser",
                                      fields=dict(fields))))
    _assert_fused_equals_legacy(engine, flows, cfg)
    out = engine.verdict_flows(flows)
    verdicts = set(np.asarray(out["verdict"]).tolist())
    assert len(verdicts) > 1
