"""Rule, IngressRule, EgressRule, PortRule + sanitization.

Reference: ``pkg/policy/api/rule.go``, ``l4.go``, ``rule_validation.go``
(SURVEY.md §2.1, unverified paths). The shape is::

    Rule{EndpointSelector, Ingress[], Egress[], Labels, Description}
    IngressRule{FromEndpoints[], FromEntities[], FromCIDR[], ToPorts[],
                IngressDeny variant via IngressCommonRule}
    PortRule{Ports []PortProtocol, Rules *L7Rules}

Deny rules (``IngressDeny``/``EgressDeny``) carry no L7 rules — the
reference forbids L7 on deny (rule_validation.go), and so do we.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Optional, Tuple

from .flow import Protocol
from .labels import LabelSet
from .l7 import (
    L7Rules,
    KAFKA_API_KEYS,
    MISMATCH_ACTIONS,
    SanitizeError,
)
from .selector import EndpointSelector, FQDNSelector


# SanitizeError is defined in l7.py (the bottom of the api import
# chain) and re-exported here as the long-standing public name.


_PROTO_NAMES = {
    "": Protocol.ANY,
    "any": Protocol.ANY,
    "tcp": Protocol.TCP,
    "udp": Protocol.UDP,
    "sctp": Protocol.SCTP,
    "icmp": Protocol.ICMP,
}


#: IANA service-name shape (k8s container port names): 1-15 chars of
#: [a-z0-9-], at least one letter, no leading/trailing/double dash
def _valid_port_name(name: str) -> bool:
    if not (1 <= len(name) <= 15) or name != name.lower():
        return False
    if name.startswith("-") or name.endswith("-") or "--" in name:
        return False
    if not all(c.isalnum() or c == "-" for c in name):
        return False
    return any(c.isalpha() for c in name)


@dataclasses.dataclass(frozen=True)
class PortProtocol:
    port: int = 0            # 0 = all ports
    protocol: Protocol = Protocol.ANY
    end_port: int = 0        # inclusive range end; 0 = single port
    #: NAMED port (reference pkg/policy/api/l4.go: Port may be an IANA
    #: service name): resolved against endpoint named-port tables at
    #: regeneration (pkg/policy/l4.go named-port resolution); when set,
    #: ``port`` is 0 until resolution
    name: str = ""

    @classmethod
    def from_dict(cls, d: Dict) -> "PortProtocol":
        port_s = str(d.get("port", "0") or "0")
        proto = _PROTO_NAMES.get(str(d.get("protocol", "") or "").lower())
        if proto is None:
            raise SanitizeError(f"unknown protocol {d.get('protocol')!r}")
        if not port_s.isdigit():
            if not _valid_port_name(port_s):
                raise SanitizeError(f"bad port name {port_s!r}")
            if d.get("endPort"):
                raise SanitizeError("endPort not allowed with a named port")
            return cls(port=0, protocol=proto, name=port_s)
        return cls(
            port=int(port_s),
            protocol=proto,
            end_port=int(d.get("endPort", 0) or 0),
        )

    def ports(self) -> Iterable[int]:
        if self.end_port and self.end_port > self.port:
            return range(self.port, self.end_port + 1)
        return (self.port,)


@dataclasses.dataclass(frozen=True)
class PortRule:
    ports: Tuple[PortProtocol, ...] = ()
    rules: Optional[L7Rules] = None

    @classmethod
    def from_dict(cls, d: Dict) -> "PortRule":
        return cls(
            ports=tuple(PortProtocol.from_dict(p) for p in (d.get("ports") or ())),
            rules=L7Rules.from_dict(d.get("rules")) if d.get("rules") else None,
        )


# Entities (reference: pkg/policy/api/entity.go) map to TUPLES of
# selectors (an entity may cover several reserved classes).
#: label every workload endpoint identity carries (value = local
#: cluster name) — how the ``cluster`` entity selects in-cluster
#: endpoints WITHOUT matching ``reserved:world`` or CIDR identities
#: (reference: EntitySelectorMapping + InitEntities(clusterName))
from .labels import CLUSTER_LABEL_KEY  # noqa: E402,F401
# (canonical definition lives in core.labels; re-exported here for the
# policy-layer consumers that historically imported it from this module)


def _reserved(name: str) -> EndpointSelector:
    return EndpointSelector(match_labels=((f"reserved:{name}", ""),))


def _cluster_entity(cluster_name: str) -> Tuple[EndpointSelector, ...]:
    # reference entity.go: cluster = host + remote-node + init + health
    # + ingress + unmanaged + every endpoint carrying the local
    # cluster label. Notably NOT world / kube-apiserver: a rule
    # `fromEntities: [cluster]` must not admit world traffic.
    return (
        _reserved("host"), _reserved("remote-node"), _reserved("init"),
        _reserved("health"), _reserved("ingress"), _reserved("unmanaged"),
        EndpointSelector(
            match_labels=((f"k8s:{CLUSTER_LABEL_KEY}", cluster_name),)),
    )


_ENTITY_SELECTORS: Dict[str, Tuple[EndpointSelector, ...]] = {
    "all": (EndpointSelector(),),
    "world": (_reserved("world"),),
    "host": (_reserved("host"),),
    "remote-node": (_reserved("remote-node"),),
    "health": (_reserved("health"),),
    "init": (_reserved("init"),),
    "unmanaged": (_reserved("unmanaged"),),
    "ingress": (_reserved("ingress"),),
    "kube-apiserver": (_reserved("kube-apiserver"),),
}


def entity_selectors(entity: str,
                     cluster_name: str = "default",
                     ) -> Tuple[EndpointSelector, ...]:
    """Selectors for an entity. ``cluster`` binds to the CALLER's
    cluster name (reference api.InitEntities binds it once per agent;
    here it's an argument so two agents with different cluster names
    in one process — clustermesh tests do this — don't fight over a
    process-global)."""
    if entity == "cluster":
        return _cluster_entity(cluster_name)
    sels = _ENTITY_SELECTORS.get(entity)
    if sels is None:
        raise SanitizeError(f"unknown entity {entity!r}")
    return sels


@dataclasses.dataclass(frozen=True)
class GroupsSpec:
    """``toGroups`` member (reference: ``pkg/policy/api/groups.go`` —
    cloud-provider group references, e.g. AWS security groups, that an
    operator resolves to CIDR sets). ``provider`` names a registered
    resolver (agent.register_group_provider); ``fields`` carries the
    provider-specific spec verbatim. Resolution happens at every
    regeneration, so refreshed provider data takes effect without
    policy rewrites (the reference re-derives on a timer)."""

    provider: str
    fields: Tuple[Tuple[str, str], ...] = ()

    @classmethod
    def from_dict(cls, d: Dict) -> "GroupsSpec":
        if not isinstance(d, dict) or len(d) != 1:
            raise SanitizeError(f"bad toGroups member {d!r}")
        provider, spec = next(iter(d.items()))
        if not isinstance(spec, dict) or not spec:
            raise SanitizeError(
                f"toGroups {provider!r} spec must be a non-empty object")
        return cls(provider=str(provider),
                   fields=tuple(sorted((str(k), str(v) if not
                                        isinstance(v, (list, tuple))
                                        else ",".join(map(str, v)))
                                       for k, v in spec.items())))


@dataclasses.dataclass(frozen=True)
class CIDRRule:
    """``fromCIDRSet``/``toCIDRSet`` member (reference:
    ``pkg/policy/api/cidr.go ·CIDRRule``): a prefix with carve-outs.
    Excepted sub-CIDRs are SUBTRACTED from the rule's peer set at
    resolve time — they produce no allow entries, so excepted traffic
    falls through to default-deny (matching the reference, where
    excepts become requirements excluding the sub-CIDR identities).

    ``group_ref`` (reference: ``cidrGroupRef``, v2alpha1
    CiliumCIDRGroup): instead of a literal prefix, name a cluster
    CIDR-group object; the resolver expands it to the group's CIDRs at
    resolve time (each inheriting this rule's excepts), so group edits
    re-target referencing policies on the next regeneration without
    touching the policies themselves."""

    cidr: str = ""
    except_cidrs: Tuple[str, ...] = ()
    group_ref: str = ""


@dataclasses.dataclass(frozen=True)
class ICMPField:
    """One ``icmps.fields`` member (reference: api.ICMPField) — an ICMP
    type for a family. The datapath keys ICMP exactly like L4: the type
    rides the key's port slot with the ICMP(v6) protocol number, so the
    engines need no new machinery; flows carry the type in ``dport``."""

    family: str = "IPv4"  # "IPv4" | "IPv6"
    icmp_type: int = 0

    @property
    def protocol(self) -> Protocol:
        return (Protocol.ICMPV6 if self.family == "IPv6"
                else Protocol.ICMP)


@dataclasses.dataclass(frozen=True)
class IngressRule:
    from_endpoints: Tuple[EndpointSelector, ...] = ()
    from_entities: Tuple[str, ...] = ()
    from_cidrs: Tuple[str, ...] = ()
    from_cidr_set: Tuple[CIDRRule, ...] = ()
    from_requires: Tuple[EndpointSelector, ...] = ()
    to_ports: Tuple[PortRule, ...] = ()
    icmps: Tuple[ICMPField, ...] = ()
    #: api.Rule Authentication.Mode: "" (unset) | "required" |
    #: "disabled"; "required" marks matching entries auth_required —
    #: the datapath lane the mutual-auth subsystem keys on
    auth_mode: str = ""
    deny: bool = False

    def peer_selectors(self, cluster_name: str = "default",
                       ) -> Tuple[EndpointSelector, ...]:
        sels = list(self.from_endpoints)
        for e in self.from_entities:
            sels += entity_selectors(e, cluster_name)
        if not sels and not self.from_cidrs and not self.from_cidr_set:
            # no peer constraint AT ALL → wildcard peer. A CIDR-only
            # rule must NOT wildcard: its peers are exactly the
            # CIDR-derived identities (resolved in PolicyResolver) —
            # wildcarding would silently drop the CIDR constraint.
            sels = [EndpointSelector()]
        return tuple(sels)


@dataclasses.dataclass(frozen=True)
class ServiceSelector:
    """``toServices`` member (reference: api.Service) — pick k8s
    services by name+namespace or by a label selector over service
    labels (full matchLabels + matchExpressions semantics via
    :class:`EndpointSelector`); the rule then allows egress to the
    service's backends."""

    name: str = ""
    namespace: str = "default"
    label_selector: Optional[EndpointSelector] = None
    #: namespace scope for the label-selector form; empty = every
    #: namespace (reference k8sServiceSelector semantics) — a NAMED
    #: namespace must constrain the match, or a label an attacker can
    #: apply in their own namespace would open the allow
    selector_namespace: str = ""

    def matches(self, svc_name: str, svc_namespace: str,
                svc_labels) -> bool:
        if self.name:
            return (svc_name == self.name
                    and svc_namespace == self.namespace)
        if self.label_selector is None:
            return False  # neither form given: selects nothing
        if (self.selector_namespace
                and svc_namespace != self.selector_namespace):
            return False
        return self.label_selector.matches(
            LabelSet.from_dict(dict(svc_labels)))


@dataclasses.dataclass(frozen=True)
class EgressRule:
    to_endpoints: Tuple[EndpointSelector, ...] = ()
    to_entities: Tuple[str, ...] = ()
    to_cidrs: Tuple[str, ...] = ()
    to_cidr_set: Tuple[CIDRRule, ...] = ()
    to_requires: Tuple[EndpointSelector, ...] = ()
    to_fqdns: Tuple[FQDNSelector, ...] = ()
    to_services: Tuple[ServiceSelector, ...] = ()
    to_groups: Tuple[GroupsSpec, ...] = ()
    to_ports: Tuple[PortRule, ...] = ()
    icmps: Tuple[ICMPField, ...] = ()
    auth_mode: str = ""  # see IngressRule.auth_mode
    deny: bool = False

    def peer_selectors(self, cluster_name: str = "default",
                       ) -> Tuple[EndpointSelector, ...]:
        sels = list(self.to_endpoints)
        for e in self.to_entities:
            sels += entity_selectors(e, cluster_name)
        if (not sels and not self.to_fqdns and not self.to_services
                and not self.to_cidrs and not self.to_cidr_set
                and not self.to_groups):  # see IngressRule: CIDR-only
            sels = [EndpointSelector()]  # rules must not wildcard
        return tuple(sels)


@dataclasses.dataclass(frozen=True)
class Rule:
    endpoint_selector: EndpointSelector = EndpointSelector()
    ingress: Tuple[IngressRule, ...] = ()
    egress: Tuple[EgressRule, ...] = ()
    labels: Tuple[str, ...] = ()          # rule provenance labels
    description: str = ""
    #: True when the rule came from a CCNP ``nodeSelector`` spec: the
    #: endpoint_selector then selects NODES (host endpoints carrying
    #: ``reserved:host``/``reserved:remote-node`` + node labels) and
    #: never pods — and pod rules never select host endpoints
    #: (reference: CiliumClusterwideNetworkPolicy.Spec.NodeSelector +
    #: host-firewall enforcement on the host endpoint)
    node_selector: bool = False

    def selects(self, endpoint_labels) -> bool:
        """Subject match with the pod/node scope split applied."""
        from .labels import SOURCE_RESERVED

        is_node = any(
            l.source == SOURCE_RESERVED and l.key in ("host",
                                                      "remote-node")
            for l in endpoint_labels)
        if is_node != self.node_selector:
            return False
        return self.endpoint_selector.matches(endpoint_labels)

    def key(self) -> str:
        return "&".join(self.labels) or self.description or str(hash(self))
