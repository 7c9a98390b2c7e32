"""Protocol-frontend compiler plane — ``l7proto`` rule specs as
banked-automaton compiler frontends.

The engine historically spoke exactly four L7 families (http / kafka /
dns / generic) while ``proxylib/`` carried cassandra, memcached, and
r2d2 as host-side ``OnData`` state machines whose policy decisions
never touched the banked byte-scan. Since the megakernel's factored
resolve, the per-bank-shape scan arm pick, and the bank-reference memo
invalidation are protocol-agnostic, a new protocol is a *compiler
frontend*, not an engine fork (SURVEY §2.2 calls the r2d2/testparsers
shape "the didactic template"; Hyperflex's SIMD-DFA framing says the
banked scan pays for any protocol whose predicates compile to
automata). A frontend owns exactly three things:

* **identity** — the ``l7proto`` name it claims, plus the engine
  family lane it verdicts on (an :class:`~cilium_tpu.core.flow.L7Type`
  value > GENERIC; the family id rides the verdict-memo row mirror
  ``(ep, l7type, dport)``, the bank-reference ``PolicyDelta`` family
  split, and the 3-bit family field of the packed provenance word —
  which caps engine frontends at family ids 5..7 until the word
  schema is bumped);
* **predicate extraction** — validating a rule's field keys/values at
  compile time (unknown keys fail LOUDLY — the silent-generic
  fallback this module retires) and lowering each rule into two
  predicate kinds (:meth:`ProtocolFrontend.lower_rule`): the
  protocol's ONE high-cardinality **scan field** (cassandra's
  query table, memcached's key, r2d2's file) becomes a full-match
  pattern over that field's value for the ``l7g`` banked automaton —
  the pattern universe rides the ordinary compile pipeline:
  content-defined banks via ``bankplan.py`` (→ CompileQueue,
  quarantine, bank artifacts), deduped rule-signature groups with
  ``rp_fe_*`` arrays on ``CompiledPolicy``, and the ``l7g`` field
  stack of the fused megakernel dispatch — while every
  small-cardinality **enum field** (query action / opcode name /
  command class) becomes interned ``(proto, key, value)`` pair
  requirements matched by the same pair-subset device check the
  generic path proved. Exact-value patterns keep each bank's subset
  construction trie-shaped (cost linear in total literal length), so
  a fleet-scale pattern universe bank-compiles inside the
  CompileQueue deadline;
* **nothing else** — framing stays in the proxylib parser, which
  becomes the differential CPU *oracle* for the family (its
  ``policy_check`` records route through the engine), not the
  verdict data path. The lowering is exactly the oracle's "every
  rule key present with the exact value; empty value = presence"
  semantics, pinned bit-equal by tests/test_frontends.py.

The module is also the ONE registry of the ``l7proto`` universe:
``proxylib.parser.register_parser`` feeds :func:`register_proxy_parser`
so the engine compiler and the proxy dispatch can no longer drift —
a policy naming an ``l7proto`` that is neither an engine frontend nor
a registered proxy parser raises :class:`UnknownL7ProtoError` at
compile time. The ``frontend-registry`` ctlint rule holds the static
halves of the contract (every ``register_parser`` name has a frontend
or a justified proxy-only pragma; every frontend family appears in
the memo/delta/attribution enums).

Adding a protocol is one file: subclass :class:`ProtocolFrontend`,
declare the spec, call :func:`register_frontend` at import time — see
``r2d2.py`` in this package for the worked didactic example.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from cilium_tpu.policy.api.l7 import SanitizeError


class UnknownL7ProtoError(SanitizeError):
    """A policy names an ``l7proto`` with neither an engine frontend
    nor a registered proxy parser — a typo would otherwise silently
    compile to an unmatched rule (the old generic fallback)."""


@dataclasses.dataclass(frozen=True)
class FrontendSpec:
    """What a protocol frontend declares about itself."""

    #: the ``l7proto`` / ``register_parser`` name (one registry)
    name: str
    #: engine family lane (an L7Type value > GENERIC, ≤ 7 — the
    #: packed provenance word carries the family in 3 bits)
    family: int
    #: family name in the memo/delta enums (memo.FAMILY_OF_L7TYPE,
    #: loader fingerprint split, attribution.FAMILY_NAMES)
    family_name: str
    #: legal rule field keys — anything else fails loudly at compile
    fields: Tuple[str, ...] = ()
    #: the ONE high-cardinality field whose value scans through the
    #: ``l7g`` banked automaton (query_table / key / file); every
    #: other field is a small-cardinality enum predicate matched by
    #: interned pair ids. "" = no scan field (all-enum protocol).
    scan_field: str = ""
    doc: str = ""


# -- the lowering ------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LoweredRule:
    """One frontend rule, lowered for the engine:

    * ``pattern`` — full-match regex over the record's SCAN-FIELD
      value for the ``l7g`` banked automaton (None = the rule leaves
      the scan field unconstrained);
    * ``pairs`` — required interned-predicate triples
      ``(proto, key, value)`` — value ``""`` is a presence
      requirement — matched by the same pair-subset machinery as the
      generic path (records emit value + presence ids per field);
    * ``dead`` — the rule is unsatisfiable (two distinct exact values
      for the scan field: the oracle can never match it either)."""

    pattern: Optional[str]
    pairs: Tuple[Tuple[str, str, str], ...]
    dead: bool = False


def scan_value(proto: str, fields: Dict[str, str]) -> bytes:
    """The bytes the ``l7g`` automaton scans for one record: the
    frontend's declared scan field's value (empty when absent —
    absence vs present-but-empty is distinguished by the presence
    pair id, never by the scan)."""
    fe = _FRONTENDS.get(proto)
    if fe is None or not fe.spec.scan_field:
        return b""
    return str(fields.get(fe.spec.scan_field, "")).encode("utf-8")


# -- the frontend contract ---------------------------------------------------


class ProtocolFrontend:
    """Base frontend: subclass, set :attr:`spec`, optionally override
    :meth:`validate_rule` (protocol-specific predicate checks) or
    :meth:`value_pattern` (non-exact scan-field predicates, e.g. a
    future glob lowering), and :func:`register_frontend` the instance
    at import time. The default lowering implements the oracle's
    exact-match semantics — most frontends only validate."""

    spec: FrontendSpec

    def validate_rule(self, pairs: Sequence[Tuple[str, str]]) -> None:
        """Raise :class:`~cilium_tpu.policy.api.l7.SanitizeError` on a
        rule no record of this protocol could ever produce. The base
        check is the field-key universe; subclasses add value
        predicates (command classes, opcode names)."""
        legal = set(self.spec.fields)
        for k, _v in pairs:
            if k not in legal:
                raise SanitizeError(
                    f"l7proto {self.spec.name!r}: unknown rule field "
                    f"{k!r} (known: {sorted(legal)})")

    def value_pattern(self, value: str) -> str:
        """Scan-field VALUE constraint → full-match regex over the
        scan bytes. Exact by default; plain literals keep the bank's
        subset construction trie-shaped (compile cost linear in total
        literal length — what lets a 5k-rule universe bank-compile
        inside the CompileQueue deadline)."""
        return re.escape(value)

    def lower_rule(self, pairs: Sequence[Tuple[str, str]]
                   ) -> LoweredRule:
        """Predicate extraction: split one rule's pairs into the
        scan-field automaton pattern and the interned enum/presence
        predicates. Exact-match semantics, bit-equal to the oracle
        (:func:`cilium_tpu.policy.oracle._generic_rule_matches`)."""
        proto = self.spec.name
        scan_key = self.spec.scan_field
        scan_vals: Set[str] = set()
        scan_presence = False
        enum: List[Tuple[str, str, str]] = []
        seen: Set[Tuple[str, str, str]] = set()
        for k, v in pairs:
            k, v = str(k), str(v)
            if k == scan_key:
                if v:
                    scan_vals.add(v)
                else:
                    scan_presence = True
                continue
            t = (proto, k, v)
            if t not in seen:
                seen.add(t)
                enum.append(t)
        if len(scan_vals) > 1:
            return LoweredRule(None, (), dead=True)
        pattern = (self.value_pattern(next(iter(scan_vals)))
                   if scan_vals else None)
        if scan_presence and not scan_vals:
            # presence-only scan-field constraint: the presence pair
            # id carries it (the scan can't see absent-vs-empty)
            enum.append((proto, scan_key, ""))
        return LoweredRule(pattern, tuple(enum))


# -- registry ----------------------------------------------------------------

#: name → engine frontend (import-time registrations; growth bounded
#: by the frontend modules in this package plus explicit test
#: registrations)
_FRONTENDS: Dict[str, ProtocolFrontend] = {}
#: family id → name (uniqueness check + reverse lookups)
_FAMILY_NAMES: Dict[int, str] = {}
#: parser names registered proxy-only (no engine frontend): the
#: proxylib ``register_parser`` seam feeds this, so the compiler
#: knows the full legal ``l7proto`` universe
_PROXY_PARSERS: Set[str] = set()

#: family ids the 3-bit provenance-word field can carry; also the
#: range the memo/attribution enums enumerate statically
MAX_FAMILY = 7


def register_frontend(fe: ProtocolFrontend) -> ProtocolFrontend:
    from cilium_tpu.core.flow import L7Type

    spec = fe.spec
    if not (int(L7Type.GENERIC) < spec.family <= MAX_FAMILY):
        raise ValueError(
            f"frontend {spec.name!r}: family {spec.family} outside "
            f"({int(L7Type.GENERIC)}, {MAX_FAMILY}] — base families "
            f"are reserved and the provenance word carries 3 bits")
    prev = _FAMILY_NAMES.get(spec.family)
    if prev is not None and prev != spec.name:
        raise ValueError(
            f"frontend {spec.name!r}: family {spec.family} already "
            f"claimed by {prev!r}")
    # ctlint: disable=unbounded-registry  # import-time frontend registrations (one per frontend module)
    _FRONTENDS[spec.name] = fe
    # ctlint: disable=unbounded-registry  # bounded by MAX_FAMILY (3-bit provenance family field)
    _FAMILY_NAMES[spec.family] = spec.name
    return fe


def register_proxy_parser(name: str) -> None:
    """Record a proxylib parser name in the unified registry (called
    by ``proxylib.parser.register_parser``). A name with an engine
    frontend is served by the engine path; a proxy-only name keeps the
    generic pair path."""
    # ctlint: disable=unbounded-registry  # import-time parser registrations (one per proxylib module)
    _PROXY_PARSERS.add(name)


def get(name: str) -> Optional[ProtocolFrontend]:
    return _FRONTENDS.get(name)


def frontends() -> Dict[str, ProtocolFrontend]:
    return dict(_FRONTENDS)


def family_of(proto: str) -> int:
    """Engine family id of a frontend ``l7proto`` (0 = not a
    frontend — the record stays on the generic pair path)."""
    fe = _FRONTENDS.get(proto)
    return fe.spec.family if fe is not None else 0


def family_name_of(proto: str) -> Optional[str]:
    fe = _FRONTENDS.get(proto)
    return fe.spec.family_name if fe is not None else None


def family_names() -> Dict[int, str]:
    """family id → memo/delta family name, every registered
    frontend."""
    return {fe.spec.family: fe.spec.family_name
            for fe in _FRONTENDS.values()}


def _ensure_parsers_loaded() -> None:
    """The proxy half of the registry populates when
    ``cilium_tpu.proxylib`` imports; validation must not depend on
    who imported what first."""
    import cilium_tpu.proxylib  # noqa: F401  (registers parsers)


def known_l7protos() -> Set[str]:
    _ensure_parsers_loaded()
    return set(_FRONTENDS) | set(_PROXY_PARSERS)


def validate_l7proto(proto: str) -> None:
    """Raise :class:`UnknownL7ProtoError` unless ``proto`` is an
    engine frontend or a registered proxy parser — the compile-time
    face of the unified registry (a typo'd ``l7proto`` used to
    silently compile to rules nothing could match)."""
    _ensure_parsers_loaded()
    if proto in _FRONTENDS or proto in _PROXY_PARSERS:
        return
    raise UnknownL7ProtoError(
        f"unknown l7proto {proto!r}: not an engine frontend and no "
        f"proxylib parser is registered under that name (known: "
        f"{sorted(set(_FRONTENDS) | set(_PROXY_PARSERS))})")


# the shipped frontends register on package import
from cilium_tpu.policy.compiler.frontends import (  # noqa: E402,F401
    cassandra,
    memcached,
    r2d2,
)
