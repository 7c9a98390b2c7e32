"""End-to-end verdict pipeline: compile policy → tensors → jitted step.

This is the compile/execute split of SURVEY.md §7 in one place:

* :class:`CompiledPolicy` (host): per-identity MapStates + the L7 rule
  universe → packed tensors — the sorted L3/L4 key table, banked DFAs
  per HTTP field (path/method/host/headers) and for DNS patterns, Kafka
  ACL columns, and per-ruleset rule bitmaps.
* :class:`VerdictEngine` (device): one jitted function over those
  tensors computing, for a flow batch: L3/L4 precedence verdict →
  L7 automaton matches → per-rule conjunction → ruleset-any → final
  verdict codes. Mirrors the reference datapath stages ct→policy→L7
  (SURVEY.md §3.3/§3.4) as one fused batched program.

Verdict codes follow flowpb: FORWARDED=1, DROPPED=2, REDIRECTED=5
(L7-allowed flows report REDIRECTED — they traversed the proxy path).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cilium_tpu.core.config import EngineConfig
from cilium_tpu.core.flow import (
    Flow,
    L7Type,
    TrafficDirection,
    Verdict,
)
from cilium_tpu.policy.api.l7 import L7Rules, PortRuleDNS, PortRuleHTTP, PortRuleKafka
from cilium_tpu.policy.compiler import matchpattern
from cilium_tpu.policy.compiler.dfa import BankedDFA, DFABank, compile_patterns
from cilium_tpu.policy.mapstate import MapState
from cilium_tpu.engine.dfa_kernel import dfa_scan_banked, scan_arm
from cilium_tpu.engine.search import lower_bound
from cilium_tpu.engine.mapstate_kernel import PackedMapState, pack_mapstate, mapstate_lookup


# --------------------------------------------------------------- helpers --
def encode_strings(
    strings: Sequence[bytes], max_len: int, pad_multiple: int = 32
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Encode byte strings → (data [B, L] uint8, lengths [B] int32,
    valid [B] bool). Overlong strings are truncated and marked invalid —
    the engine zeroes their match words (no false accepts)."""
    B = len(strings)
    longest = max((len(s) for s in strings), default=1)
    L = min(max_len, max(pad_multiple, -(-max(longest, 1) // pad_multiple)
                         * pad_multiple))
    data = np.zeros((B, L), dtype=np.uint8)
    lengths = np.zeros((B,), dtype=np.int32)
    valid = np.ones((B,), dtype=bool)
    for i, s in enumerate(strings):
        if len(s) > L:
            valid[i] = False
            s = s[:L]
        data[i, : len(s)] = np.frombuffer(s, dtype=np.uint8)
        lengths[i] = len(s)
    return data, lengths, valid


def serialize_headers(headers: Sequence[Tuple[str, str]]) -> bytes:
    """Canonical header block: lowercase names, sorted, ``name:value``
    lines each newline-terminated. The header automatons match
    contains-regexes over this form."""
    lines = sorted(f"{k.strip().lower()}:{v.strip()}" for k, v in headers)
    return ("".join(line + "\n" for line in lines)).encode("utf-8")


def header_requirement_regex(name: str, value: str) -> str:
    """Regex (over the serialized header block) for one required header.
    Empty value = presence check."""
    import re as _re

    n = _re.escape(name.strip().lower())
    if value:
        v = _re.escape(value.strip())
        line = f"{n}:{v}"
    else:
        line = f"{n}:[^\\n]*"
    return f"(?:[^\\n]*\\n)*{line}\\n(?:[^\\n]*\\n)*"


def _empty_banked() -> BankedDFA:
    """A 1-bank, 0-pattern automaton (matches nothing) so tensor shapes
    stay non-degenerate when a protocol has no rules."""
    bank = DFABank(
        trans=np.zeros((2, 1), dtype=np.int32),
        byteclass=np.zeros(256, dtype=np.int32),
        accept=np.zeros((2, 1), dtype=np.uint32),
        start=1,
        n_patterns=0,
    )
    return BankedDFA(
        banks=[bank],
        pattern_bank=np.zeros(0, dtype=np.int32),
        pattern_lane=np.zeros(0, dtype=np.int32),
        patterns=(),
    )


@dataclasses.dataclass
class _FieldMatcher:
    """A deduped pattern universe for one string field + its stacked
    tensors; rules reference patterns by global lane."""

    banked: BankedDFA
    arrays: Dict[str, np.ndarray]
    pattern_index: Dict[str, int]
    #: bankplan.FieldBankStats when built through a BankRegistry (the
    #: content-addressed churn path); None on the positional path
    bank_stats: object = None

    @classmethod
    def build(cls, patterns: List[str], cfg: EngineConfig,
              case_insensitive: bool = False,
              bank_cache=None, bank_registry=None,
              field: str = "") -> "_FieldMatcher":
        uniq: List[str] = []
        index: Dict[str, int] = {}
        for p in patterns:
            if p not in index:
                index[p] = len(uniq)
                uniq.append(p)
        stats = None
        if not uniq:
            banked = _empty_banked()
        elif bank_registry is not None:
            # content-addressed bank path (policy/compiler/bankplan):
            # membership is a pure function of the pattern set, so a
            # CNP add/delete recompiles only its bank(s), and a failed
            # bank quarantines instead of aborting the build
            banked, stats = bank_registry.compile_field(
                field or "field", uniq, cfg,
                case_insensitive=case_insensitive)
        else:
            banked = compile_patterns(
                uniq,
                bank_size=cfg.bank_size,
                max_states=cfg.max_dfa_states,
                max_quantifier=cfg.max_quantifier,
                case_insensitive=case_insensitive,
                bank_cache=bank_cache,
            )
        return cls(banked=banked, arrays=banked.stacked(),
                   pattern_index=index, bank_stats=stats)

    def lane(self, pattern: str) -> int:
        """Global lane of ``pattern``; -1 for the empty pattern (=no
        constraint)."""
        if not pattern:
            return -1
        return int(self.arrays["lane_of"][self.pattern_index[pattern]])


def _rule_bit(words: jax.Array, lanes: jax.Array) -> jax.Array:
    """words [B, NW] uint32, lanes [R] int32 (-1 = unconstrained) →
    bool [B, R]."""
    word_idx = jnp.clip(lanes >> 5, 0, words.shape[1] - 1)
    bit_idx = (lanes & 31).astype(jnp.uint32)
    w = jnp.take(words, word_idx, axis=1)            # [B, R]
    bits = (w >> bit_idx[None, :]) & jnp.uint32(1)
    return jnp.where(lanes[None, :] < 0, True, bits.astype(bool))


def _masks_to_array(masks: List[List[int]], n_rules: int) -> np.ndarray:
    W = max(1, (max(n_rules, 1) + 31) // 32)
    out = np.zeros((max(1, len(masks)), W), dtype=np.uint32)
    for i, rule_ids in enumerate(masks):
        for r in rule_ids:
            out[i, r // 32] |= np.uint32(1 << (r % 32))
    return out


# ---------------------------------------------------------------- policy --


@dataclasses.dataclass
class CompiledPolicy:
    """Everything the device step needs, as host numpy arrays."""

    mapstate: PackedMapState
    arrays: Dict[str, np.ndarray]           # flat tensor dict
    http_rules: List[PortRuleHTTP]
    kafka_rules: List[PortRuleKafka]
    dns_rules: List[PortRuleDNS]
    gen_rules: List[Tuple[str, Tuple[Tuple[str, str], ...]]]
    kafka_interns: Dict[str, Dict]          # intern tables (kafka + generic)
    path_matcher: _FieldMatcher
    method_matcher: _FieldMatcher
    host_matcher: _FieldMatcher
    header_matcher: _FieldMatcher
    dns_matcher: _FieldMatcher
    revision: int = 0
    #: protocol-frontend rules (policy/compiler/frontends/):
    #: (l7proto, sorted (key, value) pairs) per rule, compiled onto
    #: the ``l7g`` banked automaton instead of the generic pair path
    fe_rules: List[Tuple[str, Tuple[Tuple[str, str], ...]]] = \
        dataclasses.field(default_factory=list)
    #: the ``l7g`` field matcher over the frontend pattern universe;
    #: None when no frontend rules exist (the l7g_* arrays are then
    #: absent and every l7g code path is statically skipped)
    l7g_matcher: Optional[_FieldMatcher] = None
    #: per-HTTP-rule proxy-side header rewrites from ADD/DELETE/REPLACE
    #: mismatch actions: [(action, header-name, value), ...] — the
    #: shim/Envoy layer owns applying them; the verdict engine only
    #: carries them (reference: cilium.l7policy filter does the bytes)
    header_rewrites: List[List[Tuple[str, str, str]]] = \
        dataclasses.field(default_factory=list)
    #: content-addressed bank plan (field → serving bank-key tuple)
    #: when built through a BankRegistry — the loader diffs plans
    #: across commits to derive the bank-scoped invalidation delta
    bank_plan: Dict[str, Tuple[str, ...]] = \
        dataclasses.field(default_factory=dict)
    #: bank keys quarantined during this build (stale covers serving);
    #: non-empty marks the policy DEGRADED: never cached, never warm-
    #: snapshotted, commits a full invalidation delta
    bank_quarantined: Tuple[str, ...] = ()
    #: host-side metadata of the factored resolve plan
    #: (engine/megakernel.py): group count + the path-lane → group
    #: mapping the NFA arm's group plane derives from. None when the
    #: grouping degenerated (fused step falls back to legacy resolve).
    resolve_meta: Optional[Dict] = None
    #: field → scan-impl pick ("dfa-dense" / "nfa-bitset"), written at
    #: engine staging by the per-bank-shape autotuner; rides the
    #: policy object into bank_status and the bench lines
    kernel_plan: Dict[str, str] = dataclasses.field(default_factory=dict)

    @classmethod
    def build(
        cls,
        per_identity: Dict[int, MapState],
        cfg: Optional[EngineConfig] = None,
        revision: int = 0,
        secret_lookup=None,
        bank_cache=None,
        bank_registry=None,
        audit: bool = False,
    ) -> "CompiledPolicy":
        """``bank_cache`` (compiler.dfa.BankCache): reuse compiled DFA
        banks across builds — incremental rule updates recompile only
        banks whose pattern membership changed. ``bank_registry``
        (compiler.bankplan.BankRegistry) supersedes it with the
        content-addressed partition + per-bank quarantine. ``audit`` =
        policy_audit_mode: would-be denials verdict AUDIT, not DROPPED
        (staged as a device scalar so the jitted step needs no
        recompile-per-mode)."""
        cfg = cfg or EngineConfig()

        # -- collect the L7 rule universe (deduped) and rulesets --------
        http_rules: List[PortRuleHTTP] = []
        http_index: Dict[PortRuleHTTP, int] = {}
        kafka_rules: List[PortRuleKafka] = []
        kafka_index: Dict[PortRuleKafka, int] = {}
        dns_rules: List[PortRuleDNS] = []
        dns_index: Dict[PortRuleDNS, int] = {}

        # generic (l7proto) rules: (proto, sorted (key, value) pairs);
        # an l7proto with no l7 constraints is the 0-pair allow-all rule
        gen_rules: List[Tuple[str, Tuple[Tuple[str, str], ...]]] = []
        gen_index: Dict[Tuple, int] = {}
        # protocol-FRONTEND rules: same (proto, pairs) shape, routed
        # to the l7g banked automaton (policy/compiler/frontends/) —
        # a proto with a registered frontend never compiles onto the
        # generic pair path, and an UNKNOWN proto (neither frontend
        # nor registered proxy parser) fails loudly right here
        from cilium_tpu.policy.compiler import frontends as _frontends

        fe_rules: List[Tuple[str, Tuple[Tuple[str, str], ...]]] = []
        fe_index: Dict[Tuple, int] = {}

        ruleset_key_to_id: Dict[Tuple, int] = {}
        # per ruleset: member rule ids in each protocol family's space —
        # a merged entry can carry several families (the oracle checks
        # all of them), so no single "dominant protocol" is picked
        ruleset_http: List[List[int]] = []
        ruleset_kafka: List[List[int]] = []
        ruleset_dns: List[List[int]] = []
        ruleset_gen: List[List[int]] = []
        ruleset_fe: List[List[int]] = []

        def intern_rule(table, index, rule):
            if rule not in index:
                index[rule] = len(table)
                table.append(rule)
            return index[rule]

        def ruleset_of(l7_rules_tuple: Tuple[L7Rules, ...]) -> int:
            http_ids, kafka_ids, dns_ids = [], [], []
            gen_ids, fe_ids = [], []
            for lr in l7_rules_tuple:
                for h in lr.http:
                    http_ids.append(intern_rule(http_rules, http_index, h))
                for k in lr.kafka:
                    kafka_ids.append(intern_rule(kafka_rules, kafka_index, k))
                for d in lr.dns:
                    dns_ids.append(intern_rule(dns_rules, dns_index, d))
                if lr.l7proto:
                    # the unified-registry check (ISSUE 15 satellite):
                    # an l7proto that is neither an engine frontend
                    # nor a registered proxy parser fails the COMPILE
                    # loudly instead of compiling to unmatched rules
                    _frontends.validate_l7proto(lr.l7proto)
                    fe = _frontends.get(lr.l7proto)
                    table, index, ids = (
                        (fe_rules, fe_index, fe_ids) if fe is not None
                        else (gen_rules, gen_index, gen_ids))
                    if not lr.l7:
                        ids.append(intern_rule(
                            table, index, (lr.l7proto, ())))
                    for g in lr.l7:
                        pairs = tuple(sorted(g.items()))
                        if fe is not None:
                            fe.validate_rule(pairs)
                        ids.append(intern_rule(
                            table, index, (lr.l7proto, pairs)))
            if not (http_ids or kafka_ids or dns_ids or gen_ids
                    or fe_ids):
                return -1
            key = (tuple(sorted(set(http_ids))),
                   tuple(sorted(set(kafka_ids))),
                   tuple(sorted(set(dns_ids))),
                   tuple(sorted(set(gen_ids))),
                   tuple(sorted(set(fe_ids))))
            rid = ruleset_key_to_id.get(key)
            if rid is None:
                rid = len(ruleset_http)
                ruleset_key_to_id[key] = rid
                ruleset_http.append(list(key[0]))
                ruleset_kafka.append(list(key[1]))
                ruleset_dns.append(list(key[2]))
                ruleset_gen.append(list(key[3]))
                ruleset_fe.append(list(key[4]))
            return rid

        # per-build memo keyed by the l7-rules tuple's OBJECT identity:
        # at fleet scale (10k identities over ~hundreds of shared
        # resolved MapStates) the same tuple reaches ruleset_of once
        # per identity — walking its rules every time is the dominant
        # per-update cost. The tuples stay alive for the whole build
        # (their entries hold them), so id() keys cannot be recycled.
        _ruleset_memo: Dict[int, int] = {}

        def ruleset_of_entry(ep, key, entry):
            rid = _ruleset_memo.get(id(entry.l7_rules))
            if rid is None:
                rid = ruleset_of(entry.l7_rules)
                _ruleset_memo[id(entry.l7_rules)] = rid
            return rid

        packed = pack_mapstate(
            per_identity,
            ruleset_of_entry=ruleset_of_entry,
        )

        # -- compile field matchers -------------------------------------
        path_matcher = _FieldMatcher.build(
            [h.path for h in http_rules if h.path], cfg,
            bank_cache=bank_cache, bank_registry=bank_registry,
            field="path")
        method_matcher = _FieldMatcher.build(
            [h.method for h in http_rules if h.method], cfg,
            bank_cache=bank_cache, bank_registry=bank_registry,
            field="method")
        host_matcher = _FieldMatcher.build(
            [h.host for h in http_rules if h.host], cfg,
            case_insensitive=True, bank_cache=bank_cache,
            bank_registry=bank_registry, field="host")
        from cilium_tpu.secrets import resolve_header_value

        header_pats: List[str] = []
        rule_header_lanes: List[List[str]] = []   # FAIL: gate the rule
        rule_log_lanes: List[List[str]] = []      # LOG: raise l7_log
        rule_dead: List[bool] = []   # FAIL w/ unresolvable secret
        header_rewrites: List[List[Tuple[str, str, str]]] = []
        for h in http_rules:
            pats = []
            log_pats = []
            rewrites: List[Tuple[str, str, str]] = []
            dead = False
            for hdr in h.headers:
                if ":" in hdr:
                    name, value = hdr.split(":", 1)
                else:
                    name, value = hdr, ""
                pats.append(header_requirement_regex(name, value))
            for hm in h.header_matches:
                action = hm.mismatch_action
                value = resolve_header_value(hm, secret_lookup)
                if action == "":
                    # FAIL: mismatch denies; an unresolvable secret
                    # kills the rule outright (fail closed)
                    if value is None:
                        dead = True
                    else:
                        pats.append(header_requirement_regex(
                            hm.name, value))
                elif action == "LOG":
                    if value is not None:
                        log_pats.append(header_requirement_regex(
                            hm.name, value))
                else:
                    # ADD/DELETE/REPLACE: never gate; the rewrite is
                    # proxy-side (exposed for the shim/Envoy layer)
                    rewrites.append((action, hm.name, value or ""))
            header_pats.extend(pats)
            header_pats.extend(log_pats)
            rule_header_lanes.append(pats)
            rule_log_lanes.append(log_pats)
            rule_dead.append(dead)
            header_rewrites.append(rewrites)
        header_matcher = _FieldMatcher.build(header_pats, cfg,
                                             bank_cache=bank_cache,
                                             bank_registry=bank_registry,
                                             field="hdr")

        dns_pats = []
        for d in dns_rules:
            if d.match_name:
                dns_pats.append(matchpattern.name_to_regex(d.match_name))
            else:
                dns_pats.append(matchpattern.to_regex(d.match_pattern))
        dns_matcher = _FieldMatcher.build(dns_pats, cfg,
                                          bank_cache=bank_cache,
                                          bank_registry=bank_registry,
                                          field="dns")

        # -- per-rule lane arrays ---------------------------------------
        # Rule-table row counts BUCKET past 64 (next multiple of 64):
        # every staged array sized by a rule count keeps its shape
        # across ±63 net rule adds, so incremental policy updates at
        # fleet scale reuse the jitted step's compiled executable
        # instead of paying an XLA recompile per update. Padded rows
        # are inert three ways over: lanes are -1, membership masks
        # never select them, and (for HTTP) the dead flag is set.
        # Small policies (≤64 rules) keep exact shapes.
        def _rbucket(n: int) -> int:
            return max(1, n) if n <= 64 else -(-n // 64) * 64

        Rh = _rbucket(len(http_rules))
        max_hdrs = max([len(p) for p in rule_header_lanes] + [1])
        max_logs = max([len(p) for p in rule_log_lanes] + [1])
        http_path_lane = np.full(Rh, -1, dtype=np.int32)
        http_method_lane = np.full(Rh, -1, dtype=np.int32)
        http_host_lane = np.full(Rh, -1, dtype=np.int32)
        http_header_lanes = np.full((Rh, max_hdrs), -1, dtype=np.int32)
        http_log_lanes = np.full((Rh, max_logs), -1, dtype=np.int32)
        http_rule_dead = np.zeros(Rh, dtype=bool)
        for i, h in enumerate(http_rules):
            if h.path:
                http_path_lane[i] = path_matcher.lane(h.path)
            if h.method:
                http_method_lane[i] = method_matcher.lane(h.method)
            if h.host:
                http_host_lane[i] = host_matcher.lane(h.host)
            for j, pat in enumerate(rule_header_lanes[i]):
                http_header_lanes[i, j] = header_matcher.lane(pat)
            for j, pat in enumerate(rule_log_lanes[i]):
                http_log_lanes[i, j] = header_matcher.lane(pat)
            http_rule_dead[i] = rule_dead[i]
        http_rule_dead[len(http_rules):] = True   # padding is inert

        Rk = _rbucket(len(kafka_rules))
        kafka_apikey_mask = np.zeros(Rk, dtype=np.uint32)   # 0 = any
        kafka_version = np.full(Rk, -1, dtype=np.int32)
        kafka_client = np.full(Rk, -1, dtype=np.int32)
        kafka_topic = np.full(Rk, -1, dtype=np.int32)
        client_intern: Dict[str, int] = {}
        topic_intern: Dict[str, int] = {}
        for i, k in enumerate(kafka_rules):
            for ak in k.allowed_api_keys():
                kafka_apikey_mask[i] |= np.uint32(1 << ak)
            if k.api_version:
                kafka_version[i] = int(k.api_version)
            if k.client_id:
                kafka_client[i] = client_intern.setdefault(
                    k.client_id, len(client_intern))
            if k.topic:
                kafka_topic[i] = topic_intern.setdefault(
                    k.topic, len(topic_intern))

        Rd = _rbucket(len(dns_rules))
        dns_lane = np.full(Rd, -1, dtype=np.int32)
        for i in range(len(dns_rules)):
            dns_lane[i] = dns_matcher.lane(dns_pats[i])

        # -- generic l7proto rules: proto + (key,value)-pair interning --
        # A rule matches a record when the record's pair-id set contains
        # every required pair id. Flows emit (proto,key,value) ids plus
        # (proto,key,"") presence ids; an empty rule value requires only
        # presence. Exact-value semantics, matching the oracle.
        gen_proto_intern: Dict[str, int] = {}
        gen_pair_intern: Dict[Tuple[str, str, str], int] = {}
        for proto, pairs in gen_rules:
            gen_proto_intern.setdefault(proto, len(gen_proto_intern))
            for k, v in pairs:
                gen_pair_intern.setdefault((proto, k, v),
                                           len(gen_pair_intern))
        Rg = _rbucket(len(gen_rules))
        gen_max_pairs = max([len(p) for _, p in gen_rules] + [1])
        gen_rule_proto = np.full(Rg, -1, dtype=np.int32)
        gen_rule_pairs = np.full((Rg, gen_max_pairs), -1, dtype=np.int32)
        for i, (proto, pairs) in enumerate(gen_rules):
            gen_rule_proto[i] = gen_proto_intern[proto]
            for j, (k, v) in enumerate(pairs):
                gen_rule_pairs[i, j] = gen_pair_intern[(proto, k, v)]

        # -- protocol-frontend rules: scan-field patterns + predicates --
        # Each frontend rule lowers (frontends.lower_rule) into (a)
        # one full-match pattern over its protocol's SCAN FIELD value
        # — compiled through the same content-defined bank pipeline
        # as the HTTP/DNS fields (bankplan partition → CompileQueue →
        # quarantine/artifacts), read off the l7g scan as a lane —
        # and (b) interned enum/presence predicates matched by the
        # generic pair-subset check. Exact-value patterns keep the
        # bank subset construction trie-shaped, so the universe
        # compiles in time linear in total literal length. An
        # unsatisfiable rule (two exact scan values — the oracle can
        # never match it either) compiles DEAD.
        l7g_matcher: Optional[_FieldMatcher] = None
        fe_lane = np.full(max(1, _rbucket(len(fe_rules))), -1,
                          dtype=np.int32)
        fe_family = np.full(len(fe_lane), -1, dtype=np.int32)
        fe_dead = np.zeros(len(fe_lane), dtype=bool)
        fe_dead[len(fe_rules):] = True       # padding is inert
        fe_max_pairs = 1
        fe_pairs = np.full((len(fe_lane), fe_max_pairs), -1,
                           dtype=np.int32)
        if fe_rules:
            lowered = [_frontends.get(proto).lower_rule(pairs)
                       for proto, pairs in fe_rules]
            for lo in lowered:
                for t in lo.pairs:
                    gen_pair_intern.setdefault(t,
                                               len(gen_pair_intern))
            fe_max_pairs = max([len(lo.pairs) for lo in lowered] + [1])
            fe_pairs = np.full((len(fe_lane), fe_max_pairs), -1,
                               dtype=np.int32)
            l7g_matcher = _FieldMatcher.build(
                [lo.pattern for lo in lowered
                 if lo.pattern is not None], cfg,
                bank_cache=bank_cache, bank_registry=bank_registry,
                field="l7g")
            for i, ((proto, _pairs), lo) in enumerate(
                    zip(fe_rules, lowered)):
                fe_family[i] = _frontends.family_of(proto)
                if lo.dead:
                    fe_dead[i] = True
                    continue
                if lo.pattern is not None:
                    fe_lane[i] = l7g_matcher.lane(lo.pattern)
                for j, t in enumerate(lo.pairs):
                    fe_pairs[i, j] = gen_pair_intern[t]

        # -- ruleset masks ----------------------------------------------
        http_members = ruleset_http
        kafka_members = ruleset_kafka
        dns_members = ruleset_dns

        arrays: Dict[str, np.ndarray] = {
            "audit_mode": np.array(audit, dtype=bool),
            "ms_key_w0": packed.key_w0,
            "ms_key_w1": packed.key_w1,
            "ms_key_w2": packed.key_w2,
            "ms_deny": packed.is_deny,
            "ms_ruleset": packed.ruleset_id,
            "ms_auth": packed.auth,
            "ms_enf_ids": packed.enf_ids,
            "ms_enf_flags": packed.enf_flags,
            "ms_plens": packed.port_plens,
            "ms_tmpl_ids": packed.tmpl_ids,
            # mask widths follow the BUCKETED rule counts so they
            # shape-stabilize with the lane arrays (padded bits stay 0)
            "rs_http_mask": _masks_to_array(http_members or [[]], Rh),
            "rs_kafka_mask": _masks_to_array(kafka_members or [[]],
                                             Rk),
            "rs_dns_mask": _masks_to_array(dns_members or [[]], Rd),
            "rs_gen_mask": _masks_to_array(ruleset_gen or [[]], Rg),
            "gen_rule_proto": gen_rule_proto,
            "gen_rule_pairs": gen_rule_pairs,
            "http_path_lane": http_path_lane,
            "http_method_lane": http_method_lane,
            "http_host_lane": http_host_lane,
            "http_header_lanes": http_header_lanes,
            "http_log_lanes": http_log_lanes,
            "http_rule_dead": http_rule_dead,
            "kafka_apikey_mask": kafka_apikey_mask,
            "kafka_version": kafka_version,
            "kafka_client": kafka_client,
            "kafka_topic": kafka_topic,
            "dns_lane": dns_lane,
        }
        matcher_stacks = [
            ("path", path_matcher),
            ("method", method_matcher),
            ("host", host_matcher),
            ("hdr", header_matcher),
            ("dns", dns_matcher),
        ]
        if l7g_matcher is not None:
            # the l7g stack + fe rule arrays exist ONLY when frontend
            # rules do: policies without them stage byte-identical
            # arrays (and every l7g code path is statically skipped
            # under jit — "l7g_trans" is the one gate)
            matcher_stacks.append(("l7g", l7g_matcher))
            arrays["rs_fe_mask"] = _masks_to_array(
                ruleset_fe or [[]], len(fe_lane))
            arrays["fe_lane"] = fe_lane
            arrays["fe_family"] = fe_family
            arrays["fe_dead"] = fe_dead
            arrays["fe_pairs"] = fe_pairs
        for prefix, m in matcher_stacks:
            for k, v in m.arrays.items():
                if k != "lane_of":
                    arrays[f"{prefix}_{k}"] = v

        # fixed per-flow pair-slot width: a flow can emit at most two ids
        # per field (value + presence) and never more than the interned
        # universe; deriving it from the POLICY keeps verdict_step's jit
        # shape static across batches (no data-driven recompiles)
        gen_fmax = max(4, min(len(gen_pair_intern),
                              2 * cfg.max_generic_fields))
        gen_fmax = -(-gen_fmax // 4) * 4

        bank_plan: Dict[str, Tuple[str, ...]] = {}
        bank_quarantined: List[str] = []
        for _prefix, m in matcher_stacks:
            st = m.bank_stats
            if st is not None:
                bank_plan[st.field] = st.bank_keys
                bank_quarantined.extend(st.quarantined)

        # factored resolve plan (engine/megakernel.py): rule-signature
        # groups + group-accept planes over the path automaton — the
        # rp_* arrays stage to device with everything else; the fused
        # step falls back to the legacy per-rule resolve when absent
        from cilium_tpu.engine import megakernel as _mk

        resolve_meta = None
        plan = _mk.build_resolve_plan(arrays, len(http_rules),
                                      len(dns_rules),
                                      n_kafka=len(kafka_rules),
                                      n_gen=len(gen_rules),
                                      n_fe=len(fe_rules))
        if plan is not None:
            rp_arrays, resolve_meta = plan
            arrays.update(rp_arrays)

        return cls(
            mapstate=packed,
            arrays=arrays,
            http_rules=http_rules,
            kafka_rules=kafka_rules,
            dns_rules=dns_rules,
            gen_rules=gen_rules,
            kafka_interns={"client_id": client_intern, "topic": topic_intern,
                           "gen_protos": gen_proto_intern,
                           "gen_pairs": gen_pair_intern,
                           "gen_fmax": gen_fmax},
            path_matcher=path_matcher,
            method_matcher=method_matcher,
            host_matcher=host_matcher,
            header_matcher=header_matcher,
            dns_matcher=dns_matcher,
            revision=revision,
            header_rewrites=header_rewrites,
            bank_plan=bank_plan,
            bank_quarantined=tuple(bank_quarantined),
            resolve_meta=resolve_meta,
            fe_rules=fe_rules,
            l7g_matcher=l7g_matcher,
        )


# ----------------------------------------------------------------- engine --
@dataclasses.dataclass
class FlowBatch:
    """Host-encoded flow tensors (all numpy; shapes static per bucket)."""

    ep_ids: np.ndarray
    peer_ids: np.ndarray
    dports: np.ndarray
    protos: np.ndarray
    directions: np.ndarray
    l7_types: np.ndarray
    path: Tuple[np.ndarray, np.ndarray, np.ndarray]
    method: Tuple[np.ndarray, np.ndarray, np.ndarray]
    host: Tuple[np.ndarray, np.ndarray, np.ndarray]
    headers: Tuple[np.ndarray, np.ndarray, np.ndarray]
    qname: Tuple[np.ndarray, np.ndarray, np.ndarray]
    kafka_api_key: np.ndarray
    kafka_api_version: np.ndarray
    kafka_client: np.ndarray
    kafka_topic: np.ndarray
    gen_proto: np.ndarray     # [B] interned l7proto id, -2 = none/unknown
    gen_pairs: np.ndarray     # [B, F] interned (proto,key,value) ids, -2 pad
    #: canonical serialized frontend record bytes (the l7g automaton's
    #: input; empty for non-frontend flows) — (data, len, valid)
    l7g: Tuple[np.ndarray, np.ndarray, np.ndarray] = None

    @property
    def size(self) -> int:
        return len(self.ep_ids)


def encode_flows(
    flows: Sequence[Flow],
    interns: Dict[str, Dict[str, int]],
    cfg: Optional[EngineConfig] = None,
) -> FlowBatch:
    """Featurize flows → FlowBatch (the host half of ingest; mirrors the
    reference's parse step feeding the verdict lookup)."""
    cfg = cfg or EngineConfig()
    B = len(flows)
    ep = np.zeros(B, dtype=np.int32)
    peer = np.zeros(B, dtype=np.int32)
    dport = np.zeros(B, dtype=np.int32)
    proto = np.zeros(B, dtype=np.int32)
    dirs = np.zeros(B, dtype=np.int32)
    l7t = np.zeros(B, dtype=np.int32)
    paths: List[bytes] = []
    methods: List[bytes] = []
    hosts: List[bytes] = []
    headerblocks: List[bytes] = []
    qnames: List[bytes] = []
    k_api = np.zeros(B, dtype=np.int32)
    k_ver = np.zeros(B, dtype=np.int32)
    k_cli = np.full(B, -2, dtype=np.int32)
    k_top = np.full(B, -2, dtype=np.int32)
    cintern = interns.get("client_id", {})
    tintern = interns.get("topic", {})
    gproto_intern = interns.get("gen_protos", {})
    gpair_intern = interns.get("gen_pairs", {})
    g_proto = np.full(B, -2, dtype=np.int32)
    g_pair_lists: List[List[int]] = [[] for _ in range(B)]
    from cilium_tpu.policy.compiler import frontends as _frontends

    l7g_strings: List[bytes] = []
    for i, f in enumerate(flows):
        ingress = f.direction == TrafficDirection.INGRESS
        ep[i] = f.dst_identity if ingress else f.src_identity
        peer[i] = f.src_identity if ingress else f.dst_identity
        dport[i] = f.dport
        proto[i] = int(f.protocol)
        dirs[i] = int(f.direction)
        l7t[i] = int(f.l7)
        h = f.http
        paths.append((h.path if h else "").encode("utf-8"))
        methods.append((h.method if h else "").encode("utf-8"))
        hosts.append((h.host.lower() if h else "").encode("utf-8"))
        headerblocks.append(serialize_headers(h.headers) if h else b"")
        d = f.dns
        qnames.append(
            matchpattern.sanitize_name(d.query).encode("utf-8")
            if d and d.query else b"")
        k = f.kafka
        if k:
            k_api[i] = k.api_key
            k_ver[i] = k.api_version
            k_cli[i] = cintern.get(k.client_id, -2)
            k_top[i] = tintern.get(k.topic, -2)
        g = f.generic
        fam = _frontends.family_of(g.proto) if g is not None else 0
        if fam:
            # frontend-routed record: the l7-type lane NORMALIZES to
            # the frontend family (memo row mirror + per-family
            # invalidation + the fe family gate key on it) and the
            # SCAN FIELD's value feeds the l7g automaton; the enum
            # predicates ride the shared pair-id probing below
            # (gen_proto stays -2 so generic rules never see it)
            l7t[i] = fam
            l7g_strings.append(_frontends.scan_value(g.proto,
                                                     g.fields))
        else:
            l7g_strings.append(b"")
        if g is not None:
            if not fam:
                g_proto[i] = gproto_intern.get(g.proto, -2)
            # only interned ids matter — pairs no rule references can
            # never satisfy a requirement (deduped: a field emits at
            # most one value id + one presence id). Sorted key order:
            # the capture path (_gen_intern_rows) reproduces this
            # exact id sequence, so Fmax truncation selects the SAME
            # subset live and on replay. Frontend records probe the
            # same table: their enum/presence predicates intern there.
            seen: set = set()
            for key, val in sorted(g.fields.items()):
                for probe in ((g.proto, key, val), (g.proto, key, "")):
                    pid = gpair_intern.get(probe)
                    if pid is not None and pid not in seen:
                        seen.add(pid)
                        g_pair_lists[i].append(pid)
    Fmax = int(interns.get("gen_fmax", 4))
    g_pairs = np.full((B, Fmax), -2, dtype=np.int32)
    for i, pl in enumerate(g_pair_lists):
        g_pairs[i, :min(len(pl), Fmax)] = pl[:Fmax]
    bucket = max(cfg.http_path_buckets)
    return FlowBatch(
        ep_ids=ep, peer_ids=peer, dports=dport, protos=proto,
        directions=dirs, l7_types=l7t,
        path=encode_strings(paths, bucket),
        method=encode_strings(methods, cfg.http_method_len),
        host=encode_strings(hosts, cfg.http_host_len),
        headers=encode_strings(headerblocks, 1024),
        qname=encode_strings(qnames, cfg.dns_name_len),
        kafka_api_key=k_api, kafka_api_version=k_ver,
        kafka_client=k_cli, kafka_topic=k_top,
        gen_proto=g_proto, gen_pairs=g_pairs,
        l7g=encode_strings(l7g_strings, cfg.l7g_len),
    )


def encode_records(rec, cfg: Optional[EngineConfig] = None,
                   fmax: int = 4) -> FlowBatch:
    """Vectorized FlowBatch straight from binary capture records
    (``ingest/binary.py`` structured arrays) — no per-flow Python
    objects anywhere between disk and device. Records are L3/L4
    tuples by format (L7 payloads ride JSONL), so every string field
    encodes empty and L7 interning is skipped wholesale.
    """
    cfg = cfg or EngineConfig()
    B = len(rec)
    ingress = rec["direction"] == int(TrafficDirection.INGRESS)
    ep = np.where(ingress, rec["dst_identity"],
                  rec["src_identity"]).astype(np.int32)
    peer = np.where(ingress, rec["src_identity"],
                    rec["dst_identity"]).astype(np.int32)

    def empty_field(width: int):
        # same width an all-empty batch gets from encode_strings
        # (min(max_len, one 32-byte pad block)): record batches then
        # share the flows path's jit cache entry instead of compiling
        # their own, and the empty buffers transfer 8-32x less
        width = min(width, 32)
        return (np.zeros((B, width), dtype=np.uint8),
                np.zeros(B, dtype=np.int32),
                np.ones(B, dtype=bool))

    return FlowBatch(
        ep_ids=ep, peer_ids=peer,
        dports=rec["dport"].astype(np.int32),
        protos=rec["proto"].astype(np.int32),
        directions=rec["direction"].astype(np.int32),
        l7_types=rec["l7_type"].astype(np.int32),
        path=empty_field(max(cfg.http_path_buckets)),
        method=empty_field(cfg.http_method_len),
        host=empty_field(cfg.http_host_len),
        headers=empty_field(1024),
        qname=empty_field(cfg.dns_name_len),
        kafka_api_key=np.zeros(B, dtype=np.int32),
        kafka_api_version=np.zeros(B, dtype=np.int32),
        kafka_client=np.full(B, -2, dtype=np.int32),
        kafka_topic=np.full(B, -2, dtype=np.int32),
        gen_proto=np.full(B, -2, dtype=np.int32),
        # fmax mirrors encode_flows' interned width so record batches
        # share the flows path's jit cache entry
        gen_pairs=np.full((B, fmax), -2, dtype=np.int32),
        l7g=empty_field(cfg.l7g_len),
    )


def _gather_table_field(blob: np.ndarray, offsets: np.ndarray,
                        idx: np.ndarray, max_len: int,
                        pad_multiple: int = 32,
                        fixed_len: Optional[int] = None):
    """Vectorized :func:`encode_strings` over a capture string table:
    ``idx`` [B] references strings in (offsets, blob); returns the same
    (data [B, L] u8, lengths, valid) triple — built entirely from numpy
    gathers (unique → fill → scatter back), no per-flow Python.
    ``fixed_len`` pins the padded width (chunked replay: every chunk
    must produce identical shapes so the jitted step compiles once)."""
    uniq, inv = np.unique(idx, return_inverse=True)
    starts = offsets[uniq].astype(np.int64)
    lens = offsets[uniq + 1].astype(np.int64) - starts
    if fixed_len is not None:
        L = fixed_len
    else:
        longest = int(lens.max()) if len(lens) else 1
        L = min(max_len,
                max(pad_multiple, -(-max(longest, 1) // pad_multiple)
                    * pad_multiple))
    valid_u = lens <= L
    lens_u = np.minimum(lens, L)
    pos = np.arange(L, dtype=np.int64)
    gidx = starts[:, None] + pos[None, :]
    mask = pos[None, :] < lens_u[:, None]
    if blob.size:
        data_u = np.where(mask, blob[np.minimum(gidx, blob.size - 1)], 0)
    else:
        data_u = np.zeros((len(uniq), L), dtype=np.uint8)
    return (data_u.astype(np.uint8, copy=False)[inv],
            lens_u.astype(np.int32)[inv], valid_u[inv])


def _intern_lut(offsets: np.ndarray, blob: np.ndarray, idx: np.ndarray,
                intern: Dict[str, int]) -> np.ndarray:
    """Map string-table indices → engine intern ids (-2 = unknown),
    resolving each UNIQUE string once."""
    uniq, inv = np.unique(idx, return_inverse=True)
    lut = np.full(len(uniq), -2, dtype=np.int32)
    for j, u in enumerate(uniq):
        s = blob[int(offsets[u]):int(offsets[u + 1])].tobytes()
        lut[j] = intern.get(s.decode("utf-8", "replace"), -2)
    return lut[inv]


def _gen_intern_rows(gen, offsets: np.ndarray, blob: np.ndarray,
                     interns: Dict[str, Dict]) -> np.ndarray:
    """v3 GENERIC section → row-aligned engine columns: one
    ``[N, 1 + gen_fmax]`` int32 block (col 0 = interned l7proto id,
    rest = interned pair ids, -2 pad). The (proto, key, value) triple
    resolution runs once per UNIQUE triple; per-row assembly is
    vectorized (dedup + left-pack), mirroring ``encode_flows``'s
    value-id + presence-id probing — set semantics, so slot order
    doesn't matter to the engine's membership check."""
    N = len(gen)
    Fe = int(interns.get("gen_fmax", 4))
    out = np.full((N, 1 + Fe), -2, dtype=np.int32)
    if N == 0:
        return out
    gproto = interns.get("gen_protos", {})
    gpair = interns.get("gen_pairs", {})
    proto_idx = np.asarray(gen["proto"], dtype=np.int64)
    out[:, 0] = _intern_lut(offsets, blob, proto_idx, gproto)
    pairs = np.asarray(gen["pairs"], dtype=np.int64)     # [N, F, 2]
    F = pairs.shape[1]
    triples = np.concatenate(
        [np.repeat(proto_idx, F)[:, None], pairs.reshape(-1, 2)],
        axis=1)                                          # [N*F, 3]
    uniq, inv = np.unique(triples, axis=0, return_inverse=True)

    def s(i: int) -> str:
        return blob[int(offsets[i]):int(offsets[i + 1])] \
            .tobytes().decode("utf-8", "replace")

    vid = np.full(len(uniq), -2, dtype=np.int32)
    pid = np.full(len(uniq), -2, dtype=np.int32)
    for j, (p, k, v) in enumerate(uniq):
        if k == 0:
            continue  # string 0 = "" = unused pair slot
        ps, ks, vs = s(int(p)), s(int(k)), s(int(v))
        vid[j] = gpair.get((ps, ks, vs), -2)
        pid[j] = gpair.get((ps, ks, ""), -2)
    # interleave value-id then presence-id per pair slot — the capture
    # writes pairs in sorted-key order and encode_flows probes
    # (value, presence) per sorted key, so this candidate sequence is
    # the SAME id sequence the live path builds; first-occurrence
    # dedup + left-pack + Fe cap therefore select an identical subset
    # (live/replay verdict parity even under Fmax truncation)
    cand = np.empty((N, 2 * F), dtype=np.int32)
    cand[:, 0::2] = vid[inv].reshape(N, F)
    cand[:, 1::2] = pid[inv].reshape(N, F)
    dup = np.zeros_like(cand, dtype=bool)
    for j in range(1, 2 * F):  # F is small (pair slots per flow)
        dup[:, j] = (cand[:, :j] == cand[:, j:j + 1]).any(axis=1)
    c = np.where(dup, -2, cand)
    order = np.argsort(c == -2, axis=1, kind="stable")
    packed = np.take_along_axis(c, order, axis=1)
    if packed.shape[1] < Fe:
        packed = np.pad(packed, ((0, 0), (0, Fe - packed.shape[1])),
                        constant_values=-2)
    out[:, 1:] = packed[:, :Fe]
    return out



def _gen_l7g_cols(gen, offsets: np.ndarray, blob: np.ndarray):
    """v3 GENERIC section → the frontend columns every capture path
    shares: ``(fam [N] int32, uniq_scan List[bytes], row [N] int32)``
    where ``fam`` is the frontend family id (0 = not a frontend
    record), ``uniq_scan`` the deduped SCAN-FIELD values
    (frontends.scan_value; index 0 is always empty), and ``row[i]``
    indexes a record's scan bytes in that list. The (proto,
    pair-row) → scan-value work runs once per UNIQUE section row —
    capture traffic repeats its records heavily, which is the same
    dedup the string tables ride."""
    from cilium_tpu.policy.compiler import frontends as _frontends

    N = len(gen)
    fam = np.zeros(N, dtype=np.int32)
    row = np.zeros(N, dtype=np.int32)
    uniq_serialized: List[bytes] = [b""]
    if N == 0:
        return fam, uniq_serialized, row
    proto_idx = np.asarray(gen["proto"], dtype=np.int64)
    pairs = np.asarray(gen["pairs"], dtype=np.int64)    # [N, F, 2]
    whole = np.concatenate(
        [proto_idx[:, None], pairs.reshape(N, -1)], axis=1)
    uniq, inv = np.unique(whole, axis=0, return_inverse=True)

    def s(i: int) -> str:
        return blob[int(offsets[i]):int(offsets[i + 1])] \
            .tobytes().decode("utf-8", "replace")

    ser_of = np.zeros(len(uniq), dtype=np.int32)
    fam_of = np.zeros(len(uniq), dtype=np.int32)
    index: Dict[bytes, int] = {b"": 0}
    for j, u in enumerate(uniq):
        proto = s(int(u[0]))
        f = _frontends.family_of(proto)
        if not f:
            continue
        fields = {}
        for k_idx, v_idx in u[1:].reshape(-1, 2):
            if k_idx:           # string 0 = "" = unused pair slot
                fields[s(int(k_idx))] = s(int(v_idx))
        ser = _frontends.scan_value(proto, fields)
        rid = index.get(ser)
        if rid is None:
            rid = index[ser] = len(uniq_serialized)
            uniq_serialized.append(ser)
        ser_of[j] = rid
        fam_of[j] = f
    fam[:] = fam_of[inv]
    row[:] = ser_of[inv]
    return fam, uniq_serialized, row


def _pow2_rows(n: int) -> int:
    """The power-of-two row bucket that holds ``n`` rows (at least 1)."""
    return 1 << max(0, (max(1, n) - 1)).bit_length()


def _pad_rows_pow2(*arrays):
    """Pad each array's FIRST axis (same length across arrays) with
    zeros up to the next power of two — shape buckets so the jitted
    scans/gathers hit the persistent XLA cache across captures instead
    of compiling per-file exact sizes. Padded rows must never be
    referenced (valid-masked or absent from every id stream)."""
    n = len(arrays[0])
    S_pad = _pow2_rows(n)
    if S_pad == n:
        return arrays if len(arrays) > 1 else arrays[0]
    out = tuple(
        np.concatenate(
            [a, np.zeros((S_pad - n,) + a.shape[1:], dtype=a.dtype)])
        for a in arrays)
    return out if len(out) > 1 else out[0]


class CaptureFeaturizer:
    """Chunked-replay featurizer over one v2 capture: pays the string
    work ONCE per file, then each chunk is pure row gathers.

    Every string each field references is encoded into a padded
    per-field table ([S_used, L] u8 + lengths + valid), kafka strings
    resolve to engine intern ids, and a string-table → row LUT is
    built per field. Construction builds the LUTs and allocates the
    tables; their rows are built in order as they are first read
    (:meth:`table_rows`, :attr:`tables`), so :func:`stage_capture_tables`
    can scan one row piece on the device while the host builds the
    next. ``encode(rec, l7)`` then featurizes a chunk with ~8 numpy
    row-gathers — this is what lets file→verdict replay keep pace with
    the device (north star "replaying a Hubble capture"; the
    reference's per-request parse has no analog of this because its
    datapath consumes one packet at a time)."""

    _FIELD_CAPS = (("path", "http_path_buckets"),
                   ("method", "http_method_len"),
                   ("host", "http_host_len"),
                   ("headers", None),      # fixed 1024 cap
                   ("qname", "dns_name_len"))

    def __init__(self, l7, offsets, blob, interns: Dict[str, Dict],
                 cfg: Optional[EngineConfig] = None, gen=None):
        cfg = cfg or EngineConfig()
        self.cfg = cfg
        self.interns = interns
        self.fmax = int(interns.get("gen_fmax", 4))
        self.widths = capture_field_widths(l7, offsets, cfg)
        #: v3 captures: whole-capture generic columns, row-aligned
        #: ([N, 3+fmax] int32: interned proto id, frontend family id
        #: (0 = not a frontend record), row into the staged l7g
        #: string table, then the interned pair ids); chunk callers
        #: pass the slice matching their record slice to
        #: :meth:`encode_rows`
        self.gen_rows = None
        self._l7g_uniq = None
        if gen is not None:
            gen_block = _gen_intern_rows(gen, offsets, blob, interns)
            fam, uniq_ser, l7g_row = _gen_l7g_cols(gen, offsets, blob)
            self._l7g_uniq = uniq_ser
            self.gen_rows = np.concatenate(
                [gen_block[:, :1], fam[:, None].astype(np.int32),
                 l7g_row[:, None].astype(np.int32), gen_block[:, 1:]],
                axis=1)
        n_strings = len(offsets) - 1
        self._offsets, self._blob = offsets, blob
        self._tables: Dict[str, tuple] = {}
        #: per string field, the string ids of its table rows, and how
        #: many of those rows are built
        self._used: Dict[str, np.ndarray] = {}
        self._built: Dict[str, int] = {}
        self.luts: Dict[str, np.ndarray] = {}
        for field, _ in self._FIELD_CAPS:
            used = np.unique(l7[field])
            # shape-bucket the string count (pow2, as _pad_rows_pow2):
            # the staged table scan (stage_capture_tables) then hits
            # the persistent XLA cache across captures instead of
            # paying a fresh compile per shape; the padded rows stay
            # zero and invalid
            S_pad, L = _pow2_rows(len(used)), self.widths[field]
            self._tables[field] = (np.zeros((S_pad, L), dtype=np.uint8),
                                   np.zeros(S_pad, dtype=np.int32),
                                   np.zeros(S_pad, dtype=bool))
            self._used[field] = used
            self._built[field] = 0
            lut = np.zeros(n_strings, dtype=np.int32)
            lut[used] = np.arange(len(used), dtype=np.int32)
            self.luts[field] = lut
        if self._l7g_uniq is not None:
            # frontend record serializations as one more staged string
            # table (scanned through the l7g automaton when the policy
            # carries frontend rules); no LUT — l7g_rows already
            # indexes this table directly
            self._tables["l7g"] = _pad_rows_pow2(
                *encode_strings(self._l7g_uniq, cfg.l7g_len))
        for col, key in (("kafka_client", "client_id"),
                         ("kafka_topic", "topic")):
            used = np.unique(l7[col])
            ids = _intern_lut(offsets, blob, used, interns.get(key, {}))
            lut = np.full(n_strings, -2, dtype=np.int32)
            lut[used] = ids
            self.luts[col] = lut

    @property
    def tables(self) -> Dict[str, tuple]:
        """Each field's whole padded table ``(data [S_pad, L] u8,
        lengths, valid)``, its unbuilt rows built first."""
        for field, used in self._used.items():
            self._build_rows(field, len(used))
        return self._tables

    def padded_rows(self) -> Dict[str, int]:
        """Each table's padded row count, building nothing."""
        return {field: len(t[0]) for field, t in self._tables.items()}

    def table_rows(self, field: str, lo: int, hi: int) -> tuple:
        """Rows ``[lo, hi)`` of ``field``'s padded table (views), every
        row before ``hi`` built first."""
        self._build_rows(field, hi)
        return tuple(a[lo:hi] for a in self._tables[field])

    def _build_rows(self, field: str, hi: int) -> None:
        used = self._used.get(field)
        if used is None:  # built whole at construction (l7g)
            return
        lo, hi = self._built[field], min(hi, len(used))
        if hi <= lo:
            return
        L = self.widths[field]
        data, lens, valid = self._tables[field]
        data[lo:hi], lens[lo:hi], valid[lo:hi] = _gather_table_field(
            self._blob, self._offsets, used[lo:hi], L, fixed_len=L)
        # ctlint: disable=unbounded-registry  # keyed by the five string fields, all set at construction
        self._built[field] = hi

    def _field(self, name: str, idx: np.ndarray):
        data, lens, valid = self.tables[name]
        rows = self.luts[name][idx]
        return data[rows], lens[rows], valid[rows]

    def encode_rows(self, rec, l7, gen_rows=None) -> np.ndarray:
        """Chunk → ONE [B, 15] int32 block for
        :func:`verdict_step_capture`: per-flow scalars plus per-field
        ROW indices into the staged table match-words — the string
        bytes themselves never leave the string table (scanned once
        per file on device). ~0.3ms per 10k flows. ``gen_rows`` (the
        chunk's slice of :attr:`gen_rows`, v3 captures) appends the
        generic proto/pair columns → [B, 16 + gen_fmax]."""
        rec = np.asarray(rec)
        B = len(rec)
        out = np.empty((B, len(_ROW_COLS)), dtype=np.int32)
        col = {c: i for i, c in enumerate(_ROW_COLS)}
        ingress = rec["direction"] == int(TrafficDirection.INGRESS)
        out[:, col["ep_ids"]] = np.where(
            ingress, rec["dst_identity"], rec["src_identity"])
        out[:, col["peer_ids"]] = np.where(
            ingress, rec["src_identity"], rec["dst_identity"])
        out[:, col["dports"]] = rec["dport"]
        out[:, col["protos"]] = rec["proto"]
        out[:, col["directions"]] = rec["direction"]
        out[:, col["l7_types"]] = rec["l7_type"]
        out[:, col["kafka_api_key"]] = l7["kafka_api_key"]
        out[:, col["kafka_api_version"]] = l7["kafka_api_version"]
        out[:, col["kafka_client"]] = \
            self.luts["kafka_client"][l7["kafka_client"]]
        out[:, col["kafka_topic"]] = \
            self.luts["kafka_topic"][l7["kafka_topic"]]
        for name, _ in self._FIELD_CAPS:
            out[:, col[f"{name}_row"]] = self.luts[name][l7[name]]
        if gen_rows is not None:
            gen_rows = np.asarray(gen_rows, dtype=np.int32)
            # frontend records normalize the l7-type lane to their
            # family (gen col 1) — what keys the fe lane on device
            # and the (ep, l7type, dport) memo mirror host-side;
            # identical to encode_flows' live normalization
            fam = gen_rows[:, 1]
            out[:, col["l7_types"]] = np.where(
                fam > 0, fam, out[:, col["l7_types"]])
            out = np.concatenate([out, gen_rows], axis=1)
        return out

    def encode(self, rec, l7) -> FlowBatch:
        ingress = rec["direction"] == int(TrafficDirection.INGRESS)
        ep = np.where(ingress, rec["dst_identity"],
                      rec["src_identity"]).astype(np.int32)
        peer = np.where(ingress, rec["src_identity"],
                        rec["dst_identity"]).astype(np.int32)
        B = len(rec)
        return FlowBatch(
            ep_ids=ep, peer_ids=peer,
            dports=rec["dport"].astype(np.int32),
            protos=rec["proto"].astype(np.int32),
            directions=rec["direction"].astype(np.int32),
            l7_types=rec["l7_type"].astype(np.int32),
            path=self._field("path", l7["path"]),
            method=self._field("method", l7["method"]),
            host=self._field("host", l7["host"]),
            headers=self._field("headers", l7["headers"]),
            qname=self._field("qname", l7["qname"]),
            kafka_api_key=l7["kafka_api_key"].astype(np.int32),
            kafka_api_version=l7["kafka_api_version"].astype(np.int32),
            kafka_client=self.luts["kafka_client"][l7["kafka_client"]],
            kafka_topic=self.luts["kafka_topic"][l7["kafka_topic"]],
            gen_proto=np.full(B, -2, dtype=np.int32),
            gen_pairs=np.full((B, self.fmax), -2, dtype=np.int32),
            l7g=(np.zeros((B, 32), dtype=np.uint8),
                 np.zeros(B, dtype=np.int32),
                 np.ones(B, dtype=bool)),
        )


#: Column order of the [B, 15] "rows" block verdict_step_capture
#: consumes (see CaptureFeaturizer.encode_rows).
_ROW_COLS = (
    "ep_ids", "peer_ids", "dports", "protos", "directions", "l7_types",
    "kafka_api_key", "kafka_api_version", "kafka_client", "kafka_topic",
    "path_row", "method_row", "host_row", "headers_row", "qname_row",
)


#: (field, policy-array prefix) pairs of the staged string tables
_TABLE_FIELDS = (("path", "path"), ("method", "method"),
                 ("host", "host"), ("headers", "hdr"),
                 ("qname", "dns"))


def _stage_tables_step(arrays: Dict[str, jax.Array],
                       tables: Dict[str, tuple],
                       earlier: Dict[str, tuple],
                       impl: str = "gather"
                       ) -> Dict[str, Dict[str, jax.Array]]:
    """One row piece of the per-field table scans as ONE traced
    program (:func:`stage_capture_tables`): each field in ``tables``
    is scanned, and a field whose ``earlier`` pieces' outputs are
    passed is joined to them here, so the whole table's words leave
    the program that scans its last piece. Fusing the fields matters
    twice over: one dispatch instead of ~40 eager ops per staging (the
    eager per-field loop cost ~0.3s of pure dispatch on CPU), and one
    XLA executable big enough to clear the persistent compilation
    cache's min-compile-time bar — a fresh process restages a repeat
    capture shape from disk in milliseconds instead of recompiling
    five sub-threshold programs (~2s, the dominant stage_ms phase of
    the tier-1 CPU config).

    Returns ``{field: {key: words}}``. With a factored resolve plan
    staged (``rp_path_gaccept``, engine/megakernel.py) the path table
    also emits per-row GROUP words — a second accept read off the same
    final states, bank-ORed into the ``"path_groups"`` table the fused
    capture resolve gathers. The frontend l7g table (present only when
    the policy carries its automaton) scans exactly like the five
    string fields. ``impl`` is trace-static (the engine resolves it at
    staging; see dfa_kernel.resolve_impl)."""
    out: Dict[str, Dict[str, jax.Array]] = {}
    for field, prefix in _TABLE_FIELDS + (("l7g", "l7g"),):
        if field not in tables:
            continue
        data, lens, valid = tables[field]
        want_groups = field == "path" and "rp_path_gaccept" in arrays
        res = dfa_scan_banked(
            arrays[f"{prefix}_trans"], arrays[f"{prefix}_byteclass"],
            arrays[f"{prefix}_start"], arrays[f"{prefix}_accept"],
            data, lens, impl=impl,
            extra_accept=(arrays["rp_path_gaccept"] if want_groups
                          else None))
        tw: Dict[str, jax.Array] = {}
        if want_groups:
            words, gw3 = res
            gwords = jax.lax.reduce(gw3, jnp.uint32(0),
                                    jax.lax.bitwise_or, (1,))
            tw["path_groups"] = jnp.where(valid[:, None], gwords, 0)
        else:
            words = res
        flat = words.reshape(data.shape[0], -1)
        tw[field] = jnp.where(valid[:, None], flat, 0)
        prior = earlier.get(field, ())
        out[field] = {key: jnp.concatenate([p[key] for p in prior] + [w])
                      if prior else w for key, w in tw.items()}
    return out


@functools.lru_cache(maxsize=8)
def _stage_tables_jit(impl: str):
    """One jitted staging program per static impl — the env pick
    resolves on the host, never under trace. Its module is named
    ``jit__unknown`` (a partial has no name), which the benchmark's
    ``megakernel_roofline`` counts as the scan's device time."""
    return jax.jit(functools.partial(_stage_tables_step, impl=impl))

from cilium_tpu.engine.memo import memo_pack as _memo_pack  # noqa: E402

#: jitted verdict-output → [N, 9] int32 packer (memo fill path)
_MEMO_PACK_STEP = jax.jit(_memo_pack)


#: rows of a capture string table built and scanned as one piece by
#: :func:`stage_capture_tables`. A power of two, so every pow2-padded
#: table splits evenly. Small enough that the first piece's build,
#: which no scan hides, is short; large enough that a piece's dispatch
#: costs little beside its scan.
TABLE_PIECE_ROWS = 8192


def stage_capture_tables(engine: "VerdictEngine",
                         feat: CaptureFeaturizer) -> Dict[str, jax.Array]:
    """Scan each per-field string table through its banked DFA ONCE and
    keep the match words on device ([S_used, NW] per field, invalid
    rows zeroed). The reference memoizes per-string regex results in an
    LRU (``pkg/fqdn/re``); here the whole capture string table is the
    cache, computed in one batched scan — per-chunk replay then only
    GATHERS match words by row index (:func:`verdict_step_capture`),
    so the DFA cost scales with UNIQUE strings, not flows.

    The tables go to the device in pieces of :data:`TABLE_PIECE_ROWS`
    rows: piece k holds rows ``[k*P, (k+1)*P)`` of every table that
    long, is built on the host (:meth:`CaptureFeaturizer.table_rows`),
    uploaded, and its fields scanned in one fused jitted program
    (:func:`_stage_tables_step`), asynchronously — the device scans
    piece k while the host builds piece k+1, and the host's later
    staging runs behind the last scans. A table's pieces are joined in
    the program that scans its last one, so the returned words are
    the whole tables', bit-identical to one scan of each. Tables of at
    most one piece stage in a single dispatch."""
    rows = feat.padded_rows()
    fields = [field for field, _ in _TABLE_FIELDS]
    if "l7g" in rows and "l7g_trans" in engine._arrays:
        fields.append("l7g")
    impl = getattr(engine, "_dfa_impl", "gather")
    step = _stage_tables_jit(impl)
    prefix_of = dict(_TABLE_FIELDS + (("l7g", "l7g"),))
    P = TABLE_PIECE_ROWS
    table_words: Dict[str, jax.Array] = {}
    held: Dict[str, list] = {}  # field → its earlier pieces' words
    for lo in range(0, max(rows[f] for f in fields), P):
        with _span("capture.stage.table_piece"):
            piece = {f: feat.table_rows(f, lo, lo + P)
                     for f in fields if lo < rows[f]}
            last = {f for f in piece if lo + P >= rows[f]}
            earlier = {f: tuple(held.pop(f)) for f in last if f in held}
            # ctlint: disable=hot-loop-h2d  # piece k's rows exist only once the host has built them, and its upload and scan overlap the build of piece k+1: that overlap is the pipeline
            dev = jax.device_put(piece, engine.device)
            out = step(engine._arrays, dev, earlier)
        for f in piece:
            arm = scan_arm(impl, engine._arrays[f"{prefix_of[f]}_trans"].shape,
                           len(piece[f][0]))
            _METRICS.inc(_CAPTURE_TABLE_PIECES,
                         labels={"field": f, "arm": arm})
            if f in last:
                table_words.update(out[f])
            else:
                held.setdefault(f, []).append(out[f])
    return table_words


def verdict_step_capture(arrays: Dict[str, jax.Array],
                         table_words: Dict[str, jax.Array],
                         batch: Dict[str, jax.Array]
                         ) -> Dict[str, jax.Array]:
    """:func:`verdict_step` specialized for v2/v3-capture replay:
    string match words come from the staged per-file tables (gathered
    by row index) instead of per-flow DFA scans, then the shared
    :func:`_verdict_core` assembles the verdict — capture replay and
    live verdicts share one implementation of the semantics. A v3
    capture's generic columns ride the SAME row block (cols 15+:
    interned proto id + pair ids), so generic traffic costs no extra
    device argument; v2 row blocks are [B, 15] and skip the family.

    With ``batch["idx"]`` present (deduplicated replay,
    :meth:`CaptureReplay.stage_unique`), ``rows`` is the capture's
    UNIQUE-row table and ``idx`` the per-flow row ids: flows expand by
    an on-device gather, so the host→device stream carries 2–4 bytes
    per flow instead of 60+ — the same unique-then-gather shape the
    string tables use, one level up. Every flow is still verdicted
    individually after the gather."""
    rows = batch["rows"]
    idx = batch.get("idx")
    if idx is not None:
        rows = rows[idx.astype(jnp.int32)]
    col = {c: i for i, c in enumerate(_ROW_COLS)}

    def c(name):
        return rows[:, col[name]]

    ms = mapstate_lookup(
        arrays["ms_key_w0"], arrays["ms_key_w1"], arrays["ms_key_w2"],
        arrays["ms_deny"], arrays["ms_ruleset"],
        arrays["ms_enf_ids"], arrays["ms_enf_flags"],
        c("ep_ids"), c("peer_ids"), c("dports"),
        c("protos"), c("directions"),
        auth=arrays.get("ms_auth"),
        port_plens=arrays.get("ms_plens"),
        tmpl_ids=arrays.get("ms_tmpl_ids"),
    )
    words = (table_words["path"][c("path_row")],
             table_words["method"][c("method_row")],
             table_words["host"][c("host_row")],
             table_words["headers"][c("headers_row")],
             table_words["qname"][c("qname_row")])
    ingress = c("directions") == int(TrafficDirection.INGRESS)
    src = jnp.where(ingress, c("peer_ids"), c("ep_ids"))
    dst = jnp.where(ingress, c("ep_ids"), c("peer_ids"))
    n = len(_ROW_COLS)
    gen_cols = None
    # ctlint: disable=recompile-hazard  # row width is static per capture layout: one compile per layout, by design
    if rows.shape[1] > n:
        # gen block layout (CaptureFeaturizer / IncrementalSession):
        # [proto id, frontend family, l7g table row, pair ids...]
        gen_cols = (rows[:, n], rows[:, n + 3:])
        if "l7g_trans" in arrays and "l7g" in table_words:
            words = words + (
                table_words["l7g"][rows[:, n + 2]],)
    kafka_cols = (c("kafka_api_key"), c("kafka_api_version"),
                  c("kafka_client"), c("kafka_topic"))
    if "rp_g_method" in arrays and "path_groups" in table_words:
        # factored resolve (megakernel): the staged path table carries
        # per-row GROUP words; replay gathers them like any match word
        from cilium_tpu.engine import megakernel as _mk

        gwords = table_words["path_groups"][c("path_row")]
        return _mk.fused_verdict_core(
            arrays, ms, c("l7_types"), words, gwords, kafka_cols,
            (src, dst), batch, gen_cols=gen_cols)
    return _verdict_core(
        arrays, ms, c("l7_types"), words, kafka_cols,
        (src, dst), batch, gen_cols=gen_cols)


# canonical implementation lives in ingest.binary (pure numpy, usable
# by the replay cursor without jax); re-exported here for engine users
from cilium_tpu.ingest.binary import capture_field_widths  # noqa: E402


def encode_l7_records(rec, l7, offsets, blob,
                      interns: Dict[str, Dict],
                      cfg: Optional[EngineConfig] = None,
                      widths: Optional[Dict[str, int]] = None,
                      gen=None) -> FlowBatch:
    """Vectorized FlowBatch straight from a v2 binary capture
    (``ingest/binary.py`` base records + L7 sidecar): string fields
    gather from the capture's string table, kafka strings resolve to
    engine intern ids via a unique-string LUT — no per-flow Python
    objects between disk and device (VERDICT r2 item 2; north star
    "replaying a Hubble capture"). Strings were normalized at capture
    write time (see ``ingest.binary.flows_to_capture_l7``)."""
    cfg = cfg or EngineConfig()
    B = len(rec)
    ingress = rec["direction"] == int(TrafficDirection.INGRESS)
    ep = np.where(ingress, rec["dst_identity"],
                  rec["src_identity"]).astype(np.int32)
    peer = np.where(ingress, rec["src_identity"],
                    rec["dst_identity"]).astype(np.int32)
    fmax = int(interns.get("gen_fmax", 4))
    w = widths or {}
    gen_rows = (_gen_intern_rows(gen, offsets, blob, interns)
                if gen is not None else None)
    l7_types = rec["l7_type"].astype(np.int32)
    if gen is not None:
        fam, uniq_ser, l7g_row = _gen_l7g_cols(gen, offsets, blob)
        # frontend records: normalize the l7-type lane to the family
        # and encode the serialized records (same invariants as
        # encode_flows — a chunked caller's fixed widths come from
        # capture_field_widths, but l7g serializations are derived,
        # so the cap itself is the fixed width)
        l7_types = np.where(fam > 0, fam, l7_types)
        ser = [uniq_ser[r] for r in l7g_row]
        l7g_field = encode_strings(
            ser, cfg.l7g_len,
            pad_multiple=cfg.l7g_len if w else 32)
    else:
        l7g_field = (np.zeros((B, 32), dtype=np.uint8),
                     np.zeros(B, dtype=np.int32),
                     np.ones(B, dtype=bool))

    def field(name: str, cap: int):
        return _gather_table_field(blob, offsets, l7[name], cap,
                                   fixed_len=w.get(name))

    return FlowBatch(
        ep_ids=ep, peer_ids=peer,
        dports=rec["dport"].astype(np.int32),
        protos=rec["proto"].astype(np.int32),
        directions=rec["direction"].astype(np.int32),
        l7_types=l7_types,
        path=field("path", max(cfg.http_path_buckets)),
        method=field("method", cfg.http_method_len),
        host=field("host", cfg.http_host_len),
        headers=field("headers", 1024),
        qname=field("qname", cfg.dns_name_len),
        kafka_api_key=l7["kafka_api_key"].astype(np.int32),
        kafka_api_version=l7["kafka_api_version"].astype(np.int32),
        kafka_client=_intern_lut(offsets, blob, l7["kafka_client"],
                                 interns.get("client_id", {})),
        kafka_topic=_intern_lut(offsets, blob, l7["kafka_topic"],
                                interns.get("topic", {})),
        gen_proto=(gen_rows[:, 0] if gen_rows is not None
                   else np.full(B, -2, dtype=np.int32)),
        gen_pairs=(gen_rows[:, 1:] if gen_rows is not None
                   else np.full((B, fmax), -2, dtype=np.int32)),
        l7g=l7g_field,
    )


#: Column order of the packed int32 "scalars" array. Packing the 21
#: per-flow scalar/flag columns into ONE device argument (plus the five
#: byte buckets and gen_pairs: 7 arrays total instead of 27) cuts
#: per-dispatch overhead where argument count — not bytes — dominates
#: small-batch dispatch latency.
_SCALAR_COLS = (
    "ep_ids", "peer_ids", "dports", "protos", "directions", "l7_types",
    "kafka_api_key", "kafka_api_version", "kafka_client", "kafka_topic",
    "gen_proto",
    "path_len", "path_valid", "method_len", "method_valid",
    "host_len", "host_valid", "headers_len", "headers_valid",
    "qname_len", "qname_valid", "l7g_len", "l7g_valid",
)


def pack_batch(d: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """27-key flat layout → 7-array packed layout (host side). The five
    byte buckets stay separate: concatenating them into one blob was
    tried and benched SLOWER (the in-kernel slices deny the DFA scans a
    clean [B, L] layout and the host-side concat taxes every batch
    copy) — argument-count savings beyond the scalar block don't pay."""
    scalars = np.stack(
        [d[c].astype(np.int32) for c in _SCALAR_COLS], axis=1)
    out = {"scalars": np.ascontiguousarray(scalars)}
    for name in ("path", "method", "host", "headers", "qname", "l7g"):
        out[f"{name}_data"] = d[f"{name}_data"]
    out["gen_pairs"] = d["gen_pairs"]
    return out


def unpack_batch(packed: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Packed layout → flat names (inside jit: slices fuse for free).
    ``*_valid`` columns come back as bool."""
    scalars = packed["scalars"]
    out = {}
    for i, col in enumerate(_SCALAR_COLS):
        v = scalars[:, i]
        out[col] = (v != 0) if col.endswith("_valid") else v
    for name in ("path", "method", "host", "headers", "qname", "l7g"):
        out[f"{name}_data"] = packed[f"{name}_data"]
    out["gen_pairs"] = packed["gen_pairs"]
    if "auth_pairs" in packed:  # staged auth table rides alongside
        out["auth_pairs"] = packed["auth_pairs"]
    return out


#: masked-min sentinel for the attribution winners (any value past
#: every legal lane/group/rule index). A plain int, NOT a jnp
#: constant: a module-level jax array would initialize the backend at
#: import time, before tests/conftest.py can force the virtual mesh.
_ATTR_NONE = 0x7FFFFFFF


def _first_lane(words: "jax.Array") -> "jax.Array":
    """[B, W] uint32 masked match words → the lowest set LANE index
    per row (int32; -1 when no bit is set). The device half of the
    attribution lane: a lane here is a group index (group-accept
    words), a DNS pattern lane, or a kafka/generic predicate-group
    bit, depending on which words the caller masked."""
    nz = words != 0
    any_ = jnp.any(nz, axis=1)
    i0 = jnp.argmax(nz, axis=1).astype(jnp.int32)   # first nonzero word
    w = jnp.take_along_axis(words, i0[:, None], axis=1)[:, 0]
    lsb = w & (~w + jnp.uint32(1))
    bit = jax.lax.population_count(lsb - jnp.uint32(1)).astype(jnp.int32)
    return jnp.where(any_, i0 * 32 + bit, -1)


def _masked_min(matched: "jax.Array", values: "jax.Array"
                ) -> "jax.Array":
    """min over ``values[r]`` where ``matched[b, r]`` (and the value
    is non-negative) → [B] int32, -1 when nothing matched. The legacy
    per-rule face of the attribution winner — with ``values`` a
    rule→group map it equals the fused path's lowest matched group
    (a group matches iff one of its member rules does)."""
    v = values[None, :].astype(jnp.int32)
    big = jnp.where(matched & (v >= 0), v, _ATTR_NONE)
    m = jnp.min(big, axis=1)
    return jnp.where(m == _ATTR_NONE, -1, m)


def _combine_l7_match(http, kafka, dns, gen=None,
                      fe=None) -> "jax.Array":
    """Per-family (ok, win) pairs → ONE [B] int32 attribution lane.
    Families are mutually exclusive per flow (every family's ``ok``
    is gated on its own ``l7t``; frontend families are distinct
    l7-type values), so the combine is a select, not a priority."""
    http_ok, http_win = http
    kafka_ok, kafka_win = kafka
    dns_ok, dns_win = dns
    out = jnp.where(http_ok, http_win,
                    jnp.where(kafka_ok, kafka_win,
                              jnp.where(dns_ok, dns_win, -1)))
    if gen is not None:
        gen_ok, gen_win = gen
        out = jnp.where((out < 0) & gen_ok, gen_win, out)
    if fe is not None:
        fe_ok, fe_win = fe
        out = jnp.where((out < 0) & fe_ok, fe_win, out)
    return out.astype(jnp.int32)


def _l7_kafka(arrays, ruleset, kafka_cols, l7t):
    """Kafka columnar exact/set matching → ``(ruleset-any [B] bool,
    attribution winner [B] int32)``. Shared verbatim by the legacy
    and fused (megakernel) resolves; the winner is reported in GROUP
    space when the resolve plan staged ``rp_k_rule_group`` (bit-equal
    to the fused arm's lowest matched group), else in rule space."""
    k_api, k_ver, k_cli, k_top = kafka_cols
    ak = jnp.clip(k_api, 0, 31).astype(jnp.uint32)
    am = arrays["kafka_apikey_mask"][None, :]        # [1, Rk]
    # api_key < 0 is the unknown-role sentinel (flowpb decode): it
    # matches only api-key-unconstrained rules — the clip alone would
    # collapse it onto 0/produce and falsely match produce ACLs
    k_ok = (
        ((am == 0) | (((am >> ak[:, None]) & jnp.uint32(1)).astype(bool)
                      & (k_api >= 0)[:, None]))
        & ((arrays["kafka_version"][None, :] < 0)
           | (arrays["kafka_version"][None, :] == k_ver[:, None]))
        & ((arrays["kafka_client"][None, :] < 0)
           | (arrays["kafka_client"][None, :] == k_cli[:, None]))
        & ((arrays["kafka_topic"][None, :] < 0)
           | (arrays["kafka_topic"][None, :] == k_top[:, None]))
    )
    kafka_mask = arrays["rs_kafka_mask"][ruleset]
    k_words = _bools_to_words(k_ok, kafka_mask.shape[1])
    ok = (jnp.any((k_words & kafka_mask) != 0, axis=1)
          & (l7t == int(L7Type.KAFKA)))
    Rk = k_ok.shape[1]
    r_idx = jnp.arange(Rk)
    in_set = ((kafka_mask[:, r_idx >> 5]
               >> (r_idx & 31).astype(jnp.uint32)) & 1).astype(bool)
    values = (arrays["rp_k_rule_group"]
              if "rp_k_rule_group" in arrays
              else jnp.arange(Rk, dtype=jnp.int32))
    return ok, _masked_min(k_ok & in_set, values)


def _l7_generic(arrays, ruleset, gen_cols, l7t):
    """Generic l7proto pair-subset matching → ``(ruleset-any [B]
    bool, attribution winner [B] int32)``. Shared verbatim by the
    legacy and fused resolves (winner space: see ``_l7_kafka``)."""
    gen_proto, gen_pairs = gen_cols
    grp = arrays["gen_rule_pairs"]              # [Rg, Km]
    have = jnp.any(
        gen_pairs[:, None, None, :] == grp[None, :, :, None],
        axis=-1)                                # [B, Rg, Km]
    pair_ok = jnp.all(jnp.where(grp[None, :, :] < 0, True, have),
                      axis=-1)
    proto_ok = (arrays["gen_rule_proto"][None, :]
                == gen_proto[:, None])          # [B, Rg]
    g_ok = pair_ok & proto_ok & (arrays["gen_rule_proto"] >= 0)[None, :]
    gen_mask = arrays["rs_gen_mask"][ruleset]
    g_words = _bools_to_words(g_ok, gen_mask.shape[1])
    ok = (jnp.any((g_words & gen_mask) != 0, axis=1)
          & (l7t == int(L7Type.GENERIC)))
    Rg = g_ok.shape[1]
    r_idx = jnp.arange(Rg)
    in_set = ((gen_mask[:, r_idx >> 5]
               >> (r_idx & 31).astype(jnp.uint32)) & 1).astype(bool)
    values = (arrays["rp_gen_rule_group"]
              if "rp_gen_rule_group" in arrays
              else jnp.arange(Rg, dtype=jnp.int32))
    return ok, _masked_min(g_ok & in_set, values)


def _l7_frontend(arrays, ruleset, l7g_w, gen_pairs, l7t):
    """Protocol-frontend rule matching → ``(ruleset-any [B] bool,
    attribution winner [B] int32)``. Per rule: one automaton lane bit
    over the protocol's SCAN-FIELD value (``fe_lane``; -1 =
    unconstrained) AND a pair-subset check of the rule's interned
    enum/presence predicates (``fe_pairs``, same id space and same
    subset semantics as the generic path's ``gen_pairs`` column),
    gated on the rule's family matching the flow's normalized l7-type
    lane; dead rules (unsatisfiable / padding) never match. Shared
    verbatim by the legacy and fused resolves (winner space: see
    ``_l7_kafka``)."""
    lane_ok = _rule_bit(l7g_w, arrays["fe_lane"])
    grp = arrays["fe_pairs"]                    # [Rf, Km]
    have = jnp.any(
        gen_pairs[:, None, None, :] == grp[None, :, :, None],
        axis=-1)                                # [B, Rf, Km]
    pair_ok = jnp.all(jnp.where(grp[None, :, :] < 0, True, have),
                      axis=-1)
    fam = arrays["fe_family"]
    f_ok = (lane_ok & pair_ok
            & (fam[None, :] == l7t[:, None])
            & (fam >= 0)[None, :]
            & ~arrays["fe_dead"][None, :])
    fe_mask = arrays["rs_fe_mask"][ruleset]
    f_words = _bools_to_words(f_ok, fe_mask.shape[1])
    ok = jnp.any((f_words & fe_mask) != 0, axis=1)
    Rf = f_ok.shape[1]
    r_idx = jnp.arange(Rf)
    in_set = ((fe_mask[:, r_idx >> 5]
               >> (r_idx & 31).astype(jnp.uint32)) & 1).astype(bool)
    values = (arrays["rp_fe_rule_group"]
              if "rp_fe_rule_group" in arrays
              else jnp.arange(Rf, dtype=jnp.int32))
    return ok, _masked_min(f_ok & in_set, values)


def _assemble_verdict(arrays, ms, l7_ok, l7_log_http, auth_src_dst,
                      batch, l7_match=None):
    """Precedence + auth + audit assembly → the output dict. ONE
    implementation for every resolve path (legacy, fused, capture) so
    none can drift on the verdict-code semantics.

    ``l7_match`` is the attribution lane ([B] int32): the winning
    L7 rule-signature group (group space, the fused plan) or rule
    index (rule space, plan-less policies) of the family that
    matched; -1 = no L7 winner. The host side maps it to rule id +
    bank key through ``engine/attribution.AttributionMap``."""
    allowed = ms["allowed"] & (l7_ok | ~ms["redirect"])
    auth_required = ms["auth_required"]
    if "auth_pairs" in batch:  # static key check: enforcement staged
        # drop-until-authed (the reference's auth map): a winning allow
        # that demands auth forwards only if (src, dst) completed the
        # handshake. Pairs ride a lex-sorted [P, 2] int32 table
        # (two words, not a packed int64 — x64 is disabled under jax).
        src, dst = auth_src_dst
        pairs = batch["auth_pairs"]
        _, authed = lower_bound((pairs[:, 0], pairs[:, 1]), (src, dst))
        allowed = allowed & (~auth_required | authed)
    # policy_audit_mode: a would-be denial forwards with verdict AUDIT.
    # Per FLOW: the global scalar (device-staged — no recompile when
    # the mode flips) ORs with the owning endpoint's audit bit from
    # the enforcement table (reference: per-endpoint PolicyAuditMode —
    # one namespace can audit a new policy while the fleet enforces)
    audit = ms.get("audit", jnp.zeros_like(ms["allowed"]))
    if "audit_mode" in arrays:
        audit = audit | arrays["audit_mode"]
    deny_code = jnp.where(audit, int(Verdict.AUDIT),
                          int(Verdict.DROPPED)).astype(jnp.int32)
    verdict = jnp.where(
        allowed,
        jnp.where(ms["redirect"], int(Verdict.REDIRECTED),
                  int(Verdict.FORWARDED)),
        deny_code,
    ).astype(jnp.int32)
    if l7_match is None:
        l7_match = jnp.full(l7_ok.shape, -1, jnp.int32)
    return {
        "verdict": verdict,
        "allowed": allowed,
        "l3l4_allowed": ms["allowed"],
        "redirect": ms["redirect"],
        "l7_ok": l7_ok,
        "l7_log": l7_log_http & allowed & ms["redirect"],
        "match_spec": ms["match_spec"],
        "ruleset": ms["ruleset"],
        "auth_required": ms["auth_required"],
        "l7_match": l7_match.astype(jnp.int32),
    }


def _verdict_core(arrays, ms, l7t, words, kafka_cols, auth_src_dst,
                  batch, gen_cols=None):
    """Shared back half of :func:`verdict_step` and
    :func:`verdict_step_capture`: per-family rule conjunctions →
    ruleset-any → precedence + auth + audit assembly. Keeping it in
    ONE place is what guarantees capture replay and live verdicts
    cannot drift. (The megakernel's factored resolve
    (``engine/megakernel.py``) replaces only the HTTP/DNS conjunction
    halves; kafka/generic and the assembly are these same helpers.)

    ``words`` = (path_w, method_w, host_w, hdr_w, dns_w) match-word
    tensors; ``kafka_cols`` = (api_key, api_version, client, topic)
    int32 columns; ``auth_src_dst`` = (src, dst) identity columns for
    the authed-pairs check; ``gen_cols`` = (gen_proto, gen_pairs) or
    None when the caller's format cannot carry generic records (v2
    captures — a -2 proto could never match anyway). A sixth entry in
    ``words`` is the l7g (protocol-frontend) match words — present
    exactly when the policy staged an l7g automaton and the caller's
    format carries serialized frontend records."""
    ruleset = jnp.clip(ms["ruleset"], 0, arrays["rs_http_mask"].shape[0] - 1)
    path_w, method_w, host_w, hdr_w, dns_w = words[:5]
    l7g_w = words[5] if len(words) > 5 else None

    # HTTP: conjunction of per-field pattern bits per rule
    rule_ok = (
        _rule_bit(path_w, arrays["http_path_lane"])
        & _rule_bit(method_w, arrays["http_method_lane"])
        & _rule_bit(host_w, arrays["http_host_lane"])
    )
    hdr_lanes = arrays["http_header_lanes"]          # [R, H]
    hdr_ok = jax.vmap(lambda lanes: _rule_bit(hdr_w, lanes),
                      in_axes=1, out_axes=2)(hdr_lanes)  # [B, R, H]
    rule_ok = rule_ok & jnp.all(hdr_ok, axis=2)
    # a FAIL header match whose secret is unresolvable kills the rule
    # (fail closed — compiler marks it dead)
    if "http_rule_dead" in arrays:
        rule_ok = rule_ok & ~arrays["http_rule_dead"][None, :]

    http_mask = arrays["rs_http_mask"][ruleset]      # [B, Wh]
    rule_words = _bools_to_words(rule_ok, http_mask.shape[1])
    # a rule family only matches flows carrying that L7 record (oracle:
    # flow.http is None → no HTTP rule matches)
    http_ok = (jnp.any((rule_words & http_mask) != 0, axis=1)
               & (l7t == int(L7Type.HTTP)))
    r_idx = jnp.arange(rule_ok.shape[1])
    in_set = ((http_mask[:, r_idx >> 5]
               >> (r_idx & 31).astype(jnp.uint32)) & 1).astype(bool)
    # attribution winner: in GROUP space when the plan staged the
    # rule→group map (equals the fused arm's lowest matched group —
    # a group matches iff one of its member rules does), else the
    # lowest matched rule index
    http_win = _masked_min(
        rule_ok & in_set,
        (arrays["rp_rule_group"] if "rp_rule_group" in arrays
         else jnp.arange(rule_ok.shape[1], dtype=jnp.int32)))

    # LOG-action header matches: a matching rule whose LOG lane
    # mismatched raises the flow's l7_log lane (allow + log, the
    # reference's access-log annotation)
    if "http_log_lanes" in arrays:
        log_lanes = arrays["http_log_lanes"]         # [R, G]
        log_bits = jax.vmap(lambda lanes: _rule_bit(hdr_w, lanes),
                            in_axes=1, out_axes=2)(log_lanes)
        # padding lanes (-1) read True via _rule_bit → ~bits masks them
        log_fail = jnp.any(~log_bits, axis=2)        # [B, R]
        l7_log_http = jnp.any(rule_ok & in_set & log_fail, axis=1) \
            & http_ok
    else:
        l7_log_http = jnp.zeros_like(http_ok)

    kafka_ok, kafka_win = _l7_kafka(arrays, ruleset, kafka_cols, l7t)

    # DNS: qname automaton
    d_ok = (_rule_bit(dns_w, arrays["dns_lane"])
            & (arrays["dns_lane"] >= 0)[None, :])
    dns_mask = arrays["rs_dns_mask"][ruleset]
    d_words = _bools_to_words(d_ok, dns_mask.shape[1])
    dns_ok = (jnp.any((d_words & dns_mask) != 0, axis=1)
              & (l7t == int(L7Type.DNS)))
    # DNS attribution is always LANE space (the fused arm reads the
    # same lanes off its ruleset lane-mask)
    dr_idx = jnp.arange(d_ok.shape[1])
    dns_in_set = ((dns_mask[:, dr_idx >> 5]
                   >> (dr_idx & 31).astype(jnp.uint32)) & 1
                  ).astype(bool)
    dns_win = _masked_min(d_ok & dns_in_set, arrays["dns_lane"])

    # allow-list over the union of the ruleset's families (a merged
    # entry can carry several protocol families; oracle checks all)
    l7_ok = http_ok | kafka_ok | dns_ok

    gen_pair = None
    if gen_cols is not None:
        # generic l7proto records: pair-subset matching
        gen_ok, gen_win = _l7_generic(arrays, ruleset, gen_cols, l7t)
        l7_ok = l7_ok | gen_ok
        gen_pair = (gen_ok, gen_win)

    fe_pair = None
    if l7g_w is not None and gen_cols is not None \
            and "fe_lane" in arrays:
        # protocol-frontend records: scan-field automaton lane +
        # enum pair subset + family equality
        fe_ok, fe_win = _l7_frontend(arrays, ruleset, l7g_w,
                                     gen_cols[1], l7t)
        l7_ok = l7_ok | fe_ok
        fe_pair = (fe_ok, fe_win)

    l7_match = _combine_l7_match((http_ok, http_win),
                                 (kafka_ok, kafka_win),
                                 (dns_ok, dns_win), gen_pair,
                                 fe=fe_pair)
    return _assemble_verdict(arrays, ms, l7_ok, l7_log_http,
                             auth_src_dst, batch, l7_match=l7_match)


#: transfer order of the single-blob service transport (pack_blob_host
#: / unpack_blob): every per-batch array, one H2D
_BLOB_KEYS = ("scalars", "path_data", "method_data", "host_data",
              "headers_data", "qname_data", "l7g_data", "gen_pairs")


def pack_blob_host(host: Dict[str, np.ndarray]):
    """Packed 7-array layout → ONE contiguous u8 blob ([B, W]) plus a
    static layout tuple for :func:`unpack_blob`.

    The 27→7 packing note above stops at the byte buckets because
    in-KERNEL slicing hurt the DFA scans — but the SERVICE path's cost
    is different: at batch ≤ 256 each of the 7 device_puts is a
    host↔device round trip that dwarfs the device work. One blob = one
    transfer; the on-device split/bitcast back to clean [B, L] arrays
    is an HBM copy XLA fuses into the step."""
    parts, layout = [], []
    for k in _BLOB_KEYS:
        a = host[k]
        if a.dtype == np.int32:
            u8 = np.ascontiguousarray(a).view(np.uint8).reshape(
                len(a), -1)
            layout.append((k, "i32", int(a.shape[1])))
        else:
            u8 = np.ascontiguousarray(a, dtype=np.uint8)
            layout.append((k, "u8", int(a.shape[1])))
        parts.append(u8)
    return np.concatenate(parts, axis=1), tuple(layout)


def unpack_blob(batch: Dict[str, jax.Array], layout) -> Dict[str, jax.Array]:
    """Inverse of :func:`pack_blob_host` inside jit: slices +
    bitcasts rebuild the packed 7-array dict (auth table passes
    through untouched)."""
    blob = batch["blob"]
    out: Dict[str, jax.Array] = {}
    off = 0
    for k, kind, ncols in layout:
        if kind == "i32":
            w = ncols * 4
            part = blob[:, off:off + w]
            out[k] = jax.lax.bitcast_convert_type(
                part.reshape(part.shape[0], ncols, 4), jnp.int32)
        else:
            w = ncols
            out[k] = blob[:, off:off + w]
        off += w
    if "auth_pairs" in batch:
        out["auth_pairs"] = batch["auth_pairs"]
    return out


def verdict_step(arrays: Dict[str, jax.Array], batch: Dict[str, jax.Array]
                 ) -> Dict[str, jax.Array]:
    """The pure device function: full verdict for one batch.

    ``arrays`` = CompiledPolicy.arrays staged on device;
    ``batch`` = FlowBatch fields as device arrays, either packed
    (:func:`pack_batch`) or flat — the dict-key check is static under
    jit, so both layouts trace cleanly.
    """
    if "scalars" in batch:
        batch = unpack_batch(batch)
    # ICMP key encoding (marker bit in the port slot) happens inside
    # mapstate_lookup so the kernel matches its golden model for every
    # caller, not just this one
    ms = mapstate_lookup(
        arrays["ms_key_w0"], arrays["ms_key_w1"], arrays["ms_key_w2"],
        arrays["ms_deny"], arrays["ms_ruleset"],
        arrays["ms_enf_ids"], arrays["ms_enf_flags"],
        batch["ep_ids"], batch["peer_ids"], batch["dports"],
        batch["protos"], batch["directions"],
        auth=arrays.get("ms_auth"),
        port_plens=arrays.get("ms_plens"),
        tmpl_ids=arrays.get("ms_tmpl_ids"),
    )

    def scan_field(prefix: str, data, lengths, valid):
        words = dfa_scan_banked(
            arrays[f"{prefix}_trans"], arrays[f"{prefix}_byteclass"],
            arrays[f"{prefix}_start"], arrays[f"{prefix}_accept"],
            data, lengths,
        )
        B = words.shape[0]
        flat = words.reshape(B, -1)
        return jnp.where(valid[:, None], flat, 0)

    words = (scan_field("path", *batch_field(batch, "path")),
             scan_field("method", *batch_field(batch, "method")),
             scan_field("host", *batch_field(batch, "host")),
             scan_field("hdr", *batch_field(batch, "headers")),
             scan_field("dns", *batch_field(batch, "qname")))
    if "l7g_trans" in arrays:   # frontend rules staged (static)
        words = words + (
            scan_field("l7g", *batch_field(batch, "l7g")),)
    # flows rebuild (src, dst) from (ep, peer) by direction
    ingress = batch["directions"] == int(TrafficDirection.INGRESS)
    src = jnp.where(ingress, batch["peer_ids"], batch["ep_ids"])
    dst = jnp.where(ingress, batch["ep_ids"], batch["peer_ids"])
    return _verdict_core(
        arrays, ms, batch["l7_types"], words,
        (batch["kafka_api_key"], batch["kafka_api_version"],
         batch["kafka_client"], batch["kafka_topic"]),
        (src, dst), batch,
        gen_cols=(batch["gen_proto"], batch["gen_pairs"]))


def batch_field(batch: Dict[str, jax.Array], name: str):
    return (batch[f"{name}_data"], batch[f"{name}_len"],
            batch[f"{name}_valid"])


def _bools_to_words(bools: jax.Array, n_words: int) -> jax.Array:
    """[B, R] bool → [B, n_words] uint32 bitmap (R ≤ 32*n_words)."""
    B, R = bools.shape
    pad = n_words * 32 - R
    if pad:
        bools = jnp.pad(bools, ((0, 0), (0, pad)))
    b = bools.reshape(B, n_words, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts[None, None, :], axis=2, dtype=jnp.uint32)


import time as _time

from cilium_tpu.runtime import faults as _faults
from cilium_tpu.runtime.metrics import (
    CAPTURE_STAGE_SECONDS as _CAPTURE_STAGE_SECONDS,
    CAPTURE_TABLE_PIECES as _CAPTURE_TABLE_PIECES,
    METRICS as _METRICS,
)
from cilium_tpu.runtime.tracing import (
    PHASE_DEVICE as _PH_DEVICE,
    PHASE_HOST as _PH_HOST,
    TRACER as _TRACER,
    host_sync as _host_sync,
    span as _span,
)

#: fires at every device dispatch of the jitted engine (the oracle is
#: never injected — it is the fallback the breaker trips TO)
DISPATCH_POINT = _faults.register_point(
    "engine.dispatch", "device dispatch in VerdictEngine")


class _StagePhase:
    """Capture-staging phase timer (perf ledger): seconds into
    ``cilium_tpu_capture_stage_seconds{phase=...}`` (benches read
    ``histo_sum`` deltas) inside the span ``capture.stage.<phase>``
    (the profiler always, the recorder when a trace is active)."""

    __slots__ = ("phase", "_t0", "_span")

    def __init__(self, phase: str):
        self.phase = phase

    def __enter__(self) -> "_StagePhase":
        self._span = _TRACER.span(f"capture.stage.{self.phase}",
                                  phase=_PH_HOST)
        self._span.__enter__()
        self._t0 = _time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        _METRICS.observe(_CAPTURE_STAGE_SECONDS,
                         _time.perf_counter() - self._t0,
                         labels={"phase": self.phase})
        self._span.__exit__(*exc)


class VerdictEngine:
    """Jitted wrapper around the verdict step for a CompiledPolicy.

    By default the step is the fused megakernel
    (``engine/megakernel.fused_verdict_step``): one device dispatch
    for mapstate gather + byte-scans + factored priority resolve,
    with the scan impl picked per bank shape at staging and recorded
    on ``policy.kernel_plan``. ``cfg.kernel_impl="legacy"`` (or a
    policy whose resolve plan degenerated) reverts to the unfused
    :func:`verdict_step` — bit-equal either way."""

    def __init__(self, policy: CompiledPolicy, device=None,
                 cfg: Optional[EngineConfig] = None):
        from cilium_tpu.engine import megakernel as _mk
        from cilium_tpu.engine.dfa_kernel import resolve_impl

        self.policy = policy
        self.device = device
        self.cfg = cfg or EngineConfig()
        #: trace-static scan choices, resolved ONCE here on the host
        #: (never under trace — the ctlint jit-purity contract)
        self._dfa_impl = resolve_impl()
        #: Pallas arms compile for the TPU only: on any other backend
        #: the engine picks none (no silent interpreter)
        self._pallas = jax.default_backend() == "tpu"
        self._arrays = {
            k: jax.device_put(v, device) for k, v in policy.arrays.items()
        }
        #: True when some staged entry demands authentication — when
        #: False, callers skip staging the authed-pairs table
        self.needs_auth = bool(np.any(policy.arrays["ms_auth"]))
        #: field → scan impl of the staged step ({} on the legacy path)
        self.impl_plan: Dict[str, str] = {}
        #: per-field autotune report (impl, timings, shapes)
        self.kernel_report: Dict[str, Dict] = {}
        mode = getattr(self.cfg, "kernel_impl", "auto")
        if mode != "legacy":
            impl_plan, extra, report = _mk.plan_for_engine(
                policy, self.cfg)
            for k, v in extra.items():
                self._arrays[k] = jax.device_put(v, device)
            self.impl_plan = impl_plan
            self.kernel_report = report
            policy.kernel_plan = dict(impl_plan)
            self._step = jax.jit(functools.partial(
                _mk.fused_verdict_step,
                impl_plan=tuple(sorted(impl_plan.items())),
                dfa_impl=self._dfa_impl,
                use_pallas_nfa=self._pallas))
        else:
            self._step = jax.jit(verdict_step)
        #: layout-tuple → jitted blob step (the layout is static per
        #: config; distinct layouts are distinct compiles)
        self._blob_steps: Dict[tuple, object] = {}
        #: lazily-built host-side attribution decoder (provenance)
        self._attribution = None

    @property
    def attribution(self):
        """Host-side :class:`~cilium_tpu.engine.attribution.
        AttributionMap` over this engine's policy — decodes the
        ``l7_match`` output lane to rule ids + bank keys. Built once
        per engine (the policy is immutable per revision)."""
        if self._attribution is None:
            from cilium_tpu.engine.attribution import AttributionMap

            self._attribution = AttributionMap.from_policy(self.policy)
        return self._attribution

    def verdict_batch_arrays(self, batch: Dict[str, jax.Array]):
        _faults.maybe_fail(DISPATCH_POINT)
        return self._step(self._arrays, batch)

    def _blob_step(self, layout):
        fn = self._blob_steps.get(layout)
        if fn is None:
            inner = self._step  # jitted-in-jitted inlines under trace

            def step(arrays, batch):
                return inner(arrays, unpack_blob(batch, layout))

            fn = jax.jit(step)
            # ctlint: disable=unbounded-registry  # keyed by bucketed blob layout (finite shape universe)
            self._blob_steps[layout] = fn
        return fn

    def verdict_flows_blob(self, flows: Sequence[Flow],
                           cfg: Optional[EngineConfig] = None,
                           authed_pairs: Optional[np.ndarray] = None,
                           outputs: Optional[Sequence[str]] = None):
        """:meth:`verdict_flows` over the single-blob transport: ONE
        host→device transfer per batch instead of seven (see
        :func:`pack_blob_host`) — the service path's per-batch wall is
        transport RTTs, not device work. Bit-identical verdicts to
        :meth:`verdict_flows` (pinned by differential test)."""
        _faults.maybe_fail(DISPATCH_POINT)
        # phase attribution (runtime/tracing.py): featurize/pack is
        # host-prep; transfer + jitted step + readback is
        # device-dispatch. Leaf spans — nothing else on this path
        # records a phase, so a request's phases sum to its latency.
        with _TRACER.span("engine.featurize", phase=_PH_HOST,
                          records=len(flows)):
            fb = encode_flows(flows, self.policy.kafka_interns, cfg)
            blob, layout = pack_blob_host(flowbatch_to_host_dict(fb))
        with _TRACER.span("engine.dispatch", phase=_PH_DEVICE,
                          records=len(flows)):
            batch = {"blob": jax.device_put(blob, self.device)}
            self._stage_auth(batch, authed_pairs)
            out = self._blob_step(layout)(self._arrays, batch)
            if outputs is not None:
                out = {k: out[k] for k in outputs}
            return jax.device_get(out)


    def _stage_auth(self, batch: Dict[str, jax.Array],
                    authed_pairs) -> None:
        """Stage the authed-pairs table for drop-until-authed.

        Fail-closed default: when the staged policy demands auth and no
        table was supplied (``None``), an EMPTY sentinel table is
        staged so auth-demanding flows DROP — a verdict path built
        without an AuthManager backref must not forward traffic that
        policy says waits on a handshake. ``AUTH_UNENFORCED`` opts into
        demand-lane-only behavior explicitly."""
        from cilium_tpu.auth import AUTH_UNENFORCED

        if not self.needs_auth or authed_pairs is AUTH_UNENFORCED:
            return
        if authed_pairs is None:
            # sentinel row that never matches (identities are >= 0)
            authed_pairs = np.full((1, 2), -1, dtype=np.int32)
        batch["auth_pairs"] = jax.device_put(authed_pairs, self.device)

    def verdict_flows(self, flows: Sequence[Flow],
                      cfg: Optional[EngineConfig] = None,
                      authed_pairs: Optional[np.ndarray] = None,
                      outputs: Optional[Sequence[str]] = None):
        """``authed_pairs`` (lex-sorted [P, 2] int32 (src, dst) table,
        AuthManager.pairs_array): drop-until-authed enforcement for
        entries demanding authentication. See :meth:`_stage_auth` for
        the None / AUTH_UNENFORCED contract.

        ``outputs``: materialize only these lanes. Each np.asarray is
        its own device→host transfer, so a caller that only consumes
        verdicts (the MicroBatcher service path) pays one transfer
        instead of one per output key."""
        with _TRACER.span("engine.featurize", phase=_PH_HOST,
                          records=len(flows)):
            fb = encode_flows(flows, self.policy.kafka_interns, cfg)
        with _TRACER.span("engine.dispatch", phase=_PH_DEVICE,
                          records=len(flows)):
            batch = flowbatch_to_device(fb, self.device)
            self._stage_auth(batch, authed_pairs)
            out = self.verdict_batch_arrays(batch)
            if outputs is not None:
                out = {k: out[k] for k in outputs}
            return jax.device_get(out)

    def verdict_records(self, rec, cfg: Optional[EngineConfig] = None,
                        authed_pairs: Optional[np.ndarray] = None):
        """Columnar fast path: binary capture records → verdicts with
        no per-flow Python objects (ingest/binary.py → encode_records
        → device)."""
        fmax = int(self.policy.kafka_interns.get("gen_fmax", 4))
        with _TRACER.span("engine.featurize", phase=_PH_HOST,
                          records=len(rec)):
            fb = encode_records(rec, cfg, fmax=fmax)
        with _TRACER.span("engine.dispatch", phase=_PH_DEVICE,
                          records=len(rec)):
            batch = flowbatch_to_device(fb, self.device)
            self._stage_auth(batch, authed_pairs)
            out = self.verdict_batch_arrays(batch)
            return jax.device_get(out)

    def verdict_l7_records(self, rec, l7, offsets, blob,
                           cfg: Optional[EngineConfig] = None,
                           authed_pairs: Optional[np.ndarray] = None,
                           widths: Optional[Dict[str, int]] = None,
                           gen=None):
        """Columnar fast path over a v2/v3 capture (base records + L7
        sidecar, ``gen`` = v3 GENERIC section slice): full
        HTTP/Kafka/DNS/generic verdicts, zero per-flow Python
        (ingest/binary.py → encode_l7_records → device). Chunked
        callers MUST pass whole-capture ``widths``
        (:func:`capture_field_widths`) or every chunk whose longest
        string rounds differently re-jits the step."""
        with _TRACER.span("engine.featurize", phase=_PH_HOST,
                          records=len(rec)):
            fb = encode_l7_records(rec, l7, offsets, blob,
                                   self.policy.kafka_interns, cfg,
                                   widths=widths, gen=gen)
        with _TRACER.span("engine.dispatch", phase=_PH_DEVICE,
                          records=len(rec)):
            batch = flowbatch_to_device(fb, self.device)
            self._stage_auth(batch, authed_pairs)
            out = self.verdict_batch_arrays(batch)
            return jax.device_get(out)


class CaptureReplay:
    """Replay session over one v2/v3 capture: string tables scanned
    once on device (:func:`stage_capture_tables`), chunks verdicted
    via :func:`verdict_step_capture` from [B, 15(+gen)] row blocks.
    The file→verdict hot path for the north star's capture replay.
    ``gen`` (v3 GENERIC section, whole capture) converts to interned
    columns once; per-chunk callers pass their record range via
    ``start``.

    With the rows deduped (:meth:`stage_unique`), chunks ride the
    device-resident verdict memo (``engine/memo.py``): unique rows are
    verdicted ONCE per policy revision, every later chunk is a 2–4 B/
    flow id H2D plus one on-device gather. ``loader`` (optional) makes
    the session swap-safe: every verdict entry point checks the global
    policy generation, and a committed revision — swap, rollback, or
    warm restore — re-stages the session against the loader's current
    engine and drops the memo + unique device buffer, so a policy swap
    can never serve a stale verdict (tests/test_faults.py pins it)."""

    def __init__(self, engine: "VerdictEngine", l7, offsets, blob,
                 cfg: Optional[EngineConfig] = None, gen=None,
                 loader=None):
        from cilium_tpu.engine.memo import policy_generation

        self.engine = engine
        self.loader = loader
        self.cfg = cfg
        self._gen_epoch = policy_generation()
        # raw capture sections, kept so a policy swap can re-stage the
        # session (feat LUTs intern against the POLICY's vocabulary)
        self._sections = (l7, offsets, blob, gen)
        # stage-phase attribution (perf ledger): each once-per-file
        # staging step lands in cilium_tpu_capture_stage_seconds{phase}
        # so the 12.5s stage_ms has a machine-readable split
        with _StagePhase("tables"):
            self.feat = CaptureFeaturizer(l7, offsets, blob,
                                          engine.policy.kafka_interns,
                                          cfg, gen=gen)
            self.table_words = stage_capture_tables(engine, self.feat)
        self._step = jax.jit(verdict_step_capture)
        #: whole-capture row block ([N, 15(+gen)] int32) once
        #: :meth:`stage_rows` has run — per-chunk featurize then
        #: drops from ~0.5ms/10k to a contiguous slice (~1µs)
        self.rows_all: Optional[np.ndarray] = None
        #: the (rec, l7) references stage_rows featurized, for re-
        #: staging after a policy swap
        self._staged_records = None
        #: device-resident unique-row table + per-flow ids once
        #: :meth:`stage_unique` has run (dedup replay stream)
        self.unique_rows: Optional[jax.Array] = None
        self._uniq_host: Optional[np.ndarray] = None
        self.row_idx: Optional[np.ndarray] = None
        self._drop_ratio: Optional[float] = None
        #: verdict memo over the unique-row universe (slot == unique
        #: row id — ids are assigned by row hash in _stage_unique)
        self._memo = None
        self._memo_enabled = (cfg.verdict_memo
                              if cfg is not None else True)
        #: unique-row ids a bank-scoped commit touched, awaiting a
        #: scatter refill at the next memo staging
        self._memo_dirty: Optional[np.ndarray] = None
        #: double-buffer: (start, n) → device idx issued ahead of use
        self._prefetched: Dict[tuple, jax.Array] = {}

    # -- swap safety ------------------------------------------------------
    def _ensure_current(self) -> None:
        """Re-validate the session against the policy generation,
        consuming the committed revisions' :class:`PolicyDelta`\\ s
        (bank-scoped invalidation, ISSUE 8):

        * **no-change delta** (same artifact key: a no-op regenerate,
          a warm restore of the serving policy) — keep EVERYTHING:
          staged tables, unique device buffer, memo; just follow the
          loader's engine object.
        * **bank-scoped delta** (CNP/FQDN churn; interns unchanged) —
          row encodings are policy-independent, so the unique buffer
          and row ids stay; the string-table scan restages against the
          new arrays, and only memo rows whose enforcement identity
          changed are queued for a scatter refill.
        * **full delta** (rollback, gate/audit/secret change,
          quarantine involved, or no loader to rebind through) — the
          old conservative path: full re-stage, memo dropped."""
        from cilium_tpu.engine.memo import (
            POLICY_GENERATION,
            policy_generation,
        )

        gen_now = policy_generation()
        if gen_now == self._gen_epoch:
            return
        delta = POLICY_GENERATION.deltas_since(self._gen_epoch)
        self._gen_epoch = gen_now
        new_engine = self.engine
        if self.loader is not None:
            cand = self.loader.engine
            if isinstance(cand, VerdictEngine):
                new_engine = cand
        if delta.is_noop:
            # same compiled artifact recommitted: arrays bit-identical
            # by fingerprint, so staged tables/buffers/memo all remain
            # valid — the warm-restart hit ratio survives (regression-
            # pinned by tests/test_faults.py)
            self.engine = new_engine
            if self._memo is not None:
                self._memo.adopt()
            return
        partial = (not delta.full
                   and new_engine is not self.engine
                   and isinstance(new_engine, VerdictEngine)
                   and (new_engine.policy.kafka_interns
                        == self.engine.policy.kafka_interns))
        if partial:
            self.engine = new_engine
            # capture-side tables and LUTs are policy-independent
            # given equal interns: only the staged DFA scan restages
            with _StagePhase("tables"):
                self.table_words = stage_capture_tables(new_engine,
                                                        self.feat)
            if self._memo is not None and self._memo.filled:
                affected = self._affected_unique_ids(delta)
                if affected is None:
                    self._memo.invalidate(delta.reason)
                    self._memo_dirty = None
                else:
                    if len(affected):
                        self._memo.partial_invalidate(
                            len(affected), delta.reason)
                        prev = self._memo_dirty
                        self._memo_dirty = (
                            affected if prev is None else
                            np.union1d(prev, affected))
                    self._memo.adopt()
            elif self._memo is not None:
                self._memo.adopt()
            return
        self._prefetched.clear()
        self.unique_rows = None  # device buffer dropped on full delta
        self._memo_dirty = None
        if self._memo is not None:
            self._memo.invalidate(delta.reason if delta.full
                                  else "policy-swap")
        if new_engine is not self.engine:
            self.engine = new_engine
            l7, offsets, blob, gen = self._sections
            with _StagePhase("tables"):
                self.feat = CaptureFeaturizer(
                    l7, offsets, blob, new_engine.policy.kafka_interns,
                    self.cfg, gen=gen)
                self.table_words = stage_capture_tables(new_engine,
                                                        self.feat)
            if self._staged_records is not None:
                rec, l7s = self._staged_records
                self.stage_rows(rec, l7s)
                if self._drop_ratio is not None or \
                        self.row_idx is not None:
                    self.stage_unique(self._drop_ratio)

    def _affected_unique_ids(self, delta) -> Optional[np.ndarray]:
        """Unique-row ids whose verdict may have moved under a
        bank-scoped delta. Identity granularity subsumes rule/bank
        granularity for memo outputs (every rule change alters its
        identities' fingerprints); with family fingerprints on the
        delta it narrows further to bank-REFERENCE granularity — a row
        re-verdicts only when its own L7 family read a swapped bank
        (``PolicyDelta.affects``), so an HTTP-path bank swap keeps the
        identity's DNS/kafka rows serving. None = can't tell (no
        staged host rows) → caller must drop."""
        from cilium_tpu.engine.memo import affected_row_ids

        if self._uniq_host is None or self.rows_all is None:
            return None
        if not delta.changed_identities:
            return np.zeros(0, dtype=np.int32)
        return affected_row_ids(
            delta,
            self._uniq_host[:self.n_unique,
                            _ROW_COLS.index("ep_ids")],
            self._uniq_host[:self.n_unique,
                            _ROW_COLS.index("l7_types")],
            dports=self._uniq_host[:self.n_unique,
                                   _ROW_COLS.index("dports")])

    def stage_rows(self, rec, l7) -> np.ndarray:
        """Featurize the WHOLE capture once, as part of session
        staging (the same amortization as the string-table device
        scan: per-file work paid at open, not per chunk). At TPU
        device rates the per-chunk featurize (~19M rows/s host-side)
        is otherwise the e2e ceiling."""
        self._staged_records = (rec, l7)
        with _StagePhase("featurize"):
            self.rows_all = self.feat.encode_rows(
                np.asarray(rec), l7, gen_rows=self.feat.gen_rows)
        return self.rows_all

    def stage_unique(self, drop_if_ratio_at_least: Optional[float]
                     = None) -> float:
        """Deduplicate the staged row block (capture traffic repeats
        its 15-tuples heavily — identities × ports × L7 fields draw
        from small sets): the unique-row table goes to the device once,
        and chunks replay as per-flow u16/u32 row ids expanded by an
        on-device gather. Over a bandwidth-limited host↔device link
        this cuts the steady-state stream from 60+ to 2–4 bytes per
        flow, which is the difference between the transport capping
        e2e below the device rate and not. Lossless; returns the dedup ratio
        (unique/total) so callers can fall back to plain row streaming
        when a capture doesn't repeat (ratio ~1 would stream MORE
        bytes via table+ids than rows).

        Host-side only: call :meth:`stage_unique_device` (or just
        :meth:`verdict_idx`) to push the table — so a caller that
        inspects the ratio and falls back never pays the H2D for a
        table it won't use. The table is padded to a power-of-two row
        count (padded ids are never emitted in ``row_idx``), keeping
        the jitted step's shapes in buckets the persistent XLA cache
        can hit across captures.

        ``drop_if_ratio_at_least``: a capture that barely repeats makes
        the id stream a net loss AND the unique table ≈ a full copy of
        ``rows_all`` — past this ratio the table/ids are discarded
        immediately (``row_idx`` stays None) instead of pinning ~2× the
        capture in host memory for a session that will stream rows."""
        assert self.rows_all is not None, "stage_rows first"
        self._drop_ratio = drop_if_ratio_at_least
        with _StagePhase("dedup"):
            return self._stage_unique(drop_if_ratio_at_least)

    def _stage_unique(self, drop_if_ratio_at_least: Optional[float]
                      = None) -> float:
        # dedup by row HASH (engine/memo.hash_rows): a 1-D u64 unique
        # is ~10× cheaper than np.unique(axis=0)'s 15-column row sort
        # (0.77s → ~0.06s on the 200k tier-1 capture). Exact: every
        # row is verified against its hash representative; a collision
        # falls back to the row-sort path. Row ids are therefore
        # hash-assigned — the key the verdict memo rides.
        from cilium_tpu.engine.memo import hash_rows

        h = hash_rows(self.rows_all)
        _, first, inverse = np.unique(h, return_index=True,
                                      return_inverse=True)
        uniq = self.rows_all[first]
        if not np.array_equal(uniq[inverse], self.rows_all):
            uniq, inverse = np.unique(self.rows_all, axis=0,
                                      return_inverse=True)
        n_true = len(uniq)
        ratio = n_true / max(1, len(self.rows_all))
        if drop_if_ratio_at_least is not None \
                and ratio >= drop_if_ratio_at_least:
            self._uniq_host = None
            self.unique_rows = None
            self.row_idx = None
            self.n_unique = n_true
            return ratio
        uniq = _pad_rows_pow2(uniq)
        self._uniq_host = uniq
        self.unique_rows = None
        self.n_unique = n_true
        idx_dtype = np.uint16 if len(uniq) <= (1 << 16) else np.int32
        self.row_idx = inverse.astype(idx_dtype)
        return ratio

    def stage_unique_device(self) -> jax.Array:
        """Push the (padded) unique-row table to the device, once.
        The buffer is memoized on the session and dropped ONLY on a
        policy-generation change (:meth:`_ensure_current`) — repeated
        calls (every ``verdict_idx`` chunk, the phase probes) must
        never re-pay the full-table H2D."""
        if self.unique_rows is None:
            with _StagePhase("table-h2d"):
                self.unique_rows = jax.device_put(self._uniq_host,
                                                  self.engine.device)
                with _host_sync("capture.table_h2d"):
                    np.asarray(self.unique_rows[:2])  # completion-forced
        return self.unique_rows

    # -- verdict memo -----------------------------------------------------
    @property
    def memo(self):
        """The session's :class:`~cilium_tpu.engine.memo.VerdictMemo`
        (created lazily; None until the dedup stream is staged)."""
        return self._memo

    def stage_verdict_memo(self, authed_pairs=None):
        """Verdict every session-unique row ONCE (one batched capture
        step over the staged unique table) and keep the packed outputs
        on device — chunks then replay as pure id gathers. No-op when
        the memo is current for this auth view; re-fills after an
        invalidation. Returns the memo (None when dedup was dropped or
        the memo is disabled)."""
        from cilium_tpu.engine import memo as memo_mod

        if not self._memo_enabled or self.row_idx is None:
            return None
        sig = memo_mod.auth_signature(authed_pairs)
        if self._memo is None:
            self._memo = memo_mod.VerdictMemo(device=self.engine.device)
        m = self._memo
        if m.valid_for(sig) and m.filled >= self.n_unique:
            dirty = self._memo_dirty
            if dirty is not None and len(dirty) and m.table is not None:
                # bank-scoped refill: recompute ONLY the rows a
                # committed revision touched and scatter them over the
                # live table — the rest of the memo keeps serving
                with _StagePhase("memo-fill"):
                    D = max(32, 1 << (int(len(dirty)) - 1).bit_length())
                    idx = np.concatenate(
                        [dirty, np.full(D - len(dirty), dirty[0],
                                        dtype=dirty.dtype)]) \
                        if D > len(dirty) else dirty
                    batch = {"rows": self.stage_unique_device(),
                             "idx": jax.device_put(idx,
                                                   self.engine.device)}
                    self.engine._stage_auth(batch, authed_pairs)
                    out = self._step(self.engine._arrays,
                                     self.table_words, batch)
                    m.refill_scatter(idx, _MEMO_PACK_STEP(out),
                                     len(dirty))
            self._memo_dirty = None
            return m
        with _StagePhase("memo-fill"):
            self._memo_dirty = None  # full fill supersedes any refill
            batch = {"rows": self.stage_unique_device()}
            self.engine._stage_auth(batch, authed_pairs)
            out = self._step(self.engine._arrays, self.table_words,
                             batch)
            packed = _MEMO_PACK_STEP(out)
            m.fill(packed, 0, self.n_unique, sig)
        return m

    def prefetch_idx(self, idx: np.ndarray, start: int) -> None:
        """Issue the H2D for a coming chunk's id stream ahead of use
        (double buffering: chunk N+1's transfer overlaps chunk N's
        dispatch/readback — jax device_put is async, so this returns
        immediately)."""
        key = (start, len(idx))
        if key not in self._prefetched:
            if len(self._prefetched) > 2:  # bound the in-flight window
                self._prefetched.clear()
            self._prefetched[key] = jax.device_put(idx,
                                                   self.engine.device)

    def _idx_device(self, idx: np.ndarray, start: Optional[int]
                    ) -> jax.Array:
        if start is not None:
            dev = self._prefetched.pop((start, len(idx)), None)
            if dev is not None:
                return dev
        return jax.device_put(idx, self.engine.device)

    def verdict_idx(self, idx: np.ndarray, authed_pairs=None,
                    start: Optional[int] = None
                    ) -> Dict[str, jax.Array]:
        """Verdict a chunk given per-flow unique-row ids (the
        :meth:`stage_unique` stream). With the verdict memo staged and
        current, this is ONE tiny id H2D + one on-device gather of the
        memoized outputs; otherwise one id H2D + the shared capture
        step. Auth staging matches :meth:`verdict_rows` — the id
        stream must enforce drop-until-authed exactly like every other
        replay path (None is fail-closed when the policy demands
        auth); the memo keys on the auth signature so a different auth
        view can never read another view's verdicts."""
        self._ensure_current()
        m = self.stage_verdict_memo(authed_pairs)
        idx_dev = self._idx_device(idx, start)
        if m is not None:
            return m.gather(idx_dev)
        batch = {"rows": self.stage_unique_device(), "idx": idx_dev}
        self.engine._stage_auth(batch, authed_pairs)
        return self._step(self.engine._arrays, self.table_words, batch)

    def verdict_rows(self, rows: np.ndarray, authed_pairs=None
                     ) -> Dict[str, jax.Array]:
        self._ensure_current()
        batch = {"rows": jax.device_put(rows, self.engine.device)}
        self.engine._stage_auth(batch, authed_pairs)
        return self._step(self.engine._arrays, self.table_words, batch)

    def verdict_chunk(self, rec, l7, authed_pairs=None, start: int = 0
                      ) -> Dict[str, np.ndarray]:
        """``start`` is the chunk's GLOBAL record index — mandatory
        for non-initial chunks once :meth:`stage_rows` (or a v3
        capture's gen columns) is in play. With the dedup stream
        staged the chunk rides :meth:`verdict_idx` (memo gather) and
        the NEXT chunk's id H2D is issued before this one's outputs
        are read back — sequential callers get double-buffered
        transfers for free."""
        with _span("capture.verdict"):
            out = self._dispatch_chunk(rec, l7, authed_pairs, start)
            with _host_sync("capture.readback"):
                return jax.device_get(out)

    def _dispatch_chunk(self, rec, l7, authed_pairs, start: int
                        ) -> Dict[str, jax.Array]:
        """:meth:`verdict_chunk` up to the readback: the chunk's
        device outputs."""
        self._ensure_current()
        n = len(rec)
        if self.row_idx is not None and self.rows_all is not None:
            if start + n > len(self.rows_all):
                raise ValueError(
                    f"chunk [{start}:{start + n}] outside the "
                    f"staged capture ({len(self.rows_all)} rows) — "
                    f"wrong start, or staged from different records")
            idx = self.row_idx[start:start + n]
            out = self.verdict_idx(idx, authed_pairs, start=start)
            nxt = self.row_idx[start + n:start + 2 * n]
            if len(nxt):
                self.prefetch_idx(nxt, start + n)
            return out
        if self.rows_all is not None:
            rows = self.rows_all[start:start + n]
            if len(rows) != n:
                raise ValueError(
                    f"chunk [{start}:{start + n}] outside the "
                    f"staged capture ({len(self.rows_all)} rows) — "
                    f"wrong start, or staged from different records")
        else:
            gen_rows = (self.feat.gen_rows[start:start + n]
                        if self.feat.gen_rows is not None else None)
            rows = self.feat.encode_rows(rec, l7, gen_rows=gen_rows)
        return self.verdict_rows(rows, authed_pairs)


def flowbatch_to_host_dict(fb: FlowBatch) -> Dict[str, np.ndarray]:
    """FlowBatch → packed dict of HOST numpy arrays (same keys as
    :func:`flowbatch_to_device`): one int32 "scalars" block plus the
    five byte buckets and gen_pairs (see :func:`pack_batch` for why).
    Benchmarks build per-iteration device copies from this — staging
    from host keeps device→host round-trips out of the timed passes."""
    d: Dict[str, np.ndarray] = {
        "ep_ids": fb.ep_ids, "peer_ids": fb.peer_ids,
        "dports": fb.dports, "protos": fb.protos,
        "directions": fb.directions, "l7_types": fb.l7_types,
        "kafka_api_key": fb.kafka_api_key,
        "kafka_api_version": fb.kafka_api_version,
        "kafka_client": fb.kafka_client,
        "kafka_topic": fb.kafka_topic,
        "gen_proto": fb.gen_proto,
        "gen_pairs": fb.gen_pairs,
    }
    for name in ("path", "method", "host", "headers", "qname", "l7g"):
        data, lengths, valid = getattr(fb, name)
        d[f"{name}_data"] = data
        d[f"{name}_len"] = lengths
        d[f"{name}_valid"] = valid
    return pack_batch(d)


def flowbatch_to_device(fb: FlowBatch, device=None) -> Dict[str, jax.Array]:
    # one batched pytree transfer, not one device_put per column
    return jax.device_put(flowbatch_to_host_dict(fb), device)
