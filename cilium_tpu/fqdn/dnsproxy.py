"""DNS proxy verdict path.

Reference: ``pkg/fqdn/dnsproxy/proxy.go`` (SURVEY.md §2.2, §3.5): a
transparent proxy holding per-(endpoint, port) allow-rules;
``CheckAllowed(endpoint, dport, qname)`` is the verdict hot path
(BASELINE config[0]); allowed responses feed the NameManager.

Two matchers behind one interface, mirroring the feature gate:
* CPU: compiled-regex LRU (the reference's ``pkg/fqdn/re`` role)
* TPU: batch qnames through the banked-DFA engine (``check_batch``)
"""

from __future__ import annotations

import re
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from cilium_tpu.fqdn.namemanager import NameManager
from cilium_tpu.policy.compiler import matchpattern
from cilium_tpu.policy.compiler.dfa import compile_patterns
from cilium_tpu.policy.api.l7 import PortRuleDNS
from cilium_tpu.runtime import faults
from cilium_tpu.runtime.metrics import DNSPROXY_FALLBACKS, METRICS
from cilium_tpu.runtime.tracing import (
    PHASE_DEVICE,
    PHASE_FALLBACK,
    PHASE_HOST,
    TRACER,
)

#: fires in the banked-DFA batch path; a device fault degrades the
#: batch to the CPU regex matcher (same verdicts, slower)
QUERY_POINT = faults.register_point(
    "dnsproxy.query", "banked-DFA DNS batch verdict")


class DNSProxy:
    def __init__(self, name_manager: Optional[NameManager] = None,
                 use_tpu: bool = False) -> None:
        self._lock = threading.Lock()
        self.name_manager = name_manager
        self.use_tpu = use_tpu
        # (endpoint_id, dport) → list of regex sources
        self._rules: Dict[Tuple[int, int], List[str]] = {}
        self._compiled: Dict[Tuple[int, int], List["re.Pattern"]] = {}
        self._banked: Dict[Tuple[int, int], object] = {}

    def update_allowed(self, endpoint_id: int, dport: int,
                       rules: Sequence[PortRuleDNS]) -> None:
        """Install the allow-set for an endpoint+port (the reference's
        UpdateAllowed, called at regeneration time)."""
        srcs: List[str] = []
        for r in rules:
            if r.match_name:
                srcs.append(matchpattern.name_to_regex(r.match_name))
            elif r.match_pattern:
                srcs.append(matchpattern.to_regex(r.match_pattern))
        key = (endpoint_id, dport)
        with self._lock:
            if not srcs:
                self._rules.pop(key, None)
                self._compiled.pop(key, None)
                self._banked.pop(key, None)
                return
            self._rules[key] = srcs
            self._compiled[key] = [re.compile(s) for s in srcs]
            self._banked.pop(key, None)  # lazily rebuilt

    def check_allowed(self, endpoint_id: int, dport: int,
                      qname: str) -> bool:
        """The per-query hot path (CPU)."""
        q = matchpattern.sanitize_name(qname)
        with self._lock:
            pats = self._compiled.get((endpoint_id, dport))
        if pats is None:
            return False  # no rules installed → deny (proxy is opt-in)
        return any(p.match(q) for p in pats)

    def check_batch(self, endpoint_id: int, dport: int,
                    qnames: Sequence[str]) -> np.ndarray:
        """Batched verdicts; uses the banked-DFA engine when the TPU
        gate is on, else the regex set."""
        key = (endpoint_id, dport)
        with self._lock:
            srcs = self._rules.get(key)
            pats = self._compiled.get(key)
        if srcs is None or pats is None:
            return np.zeros(len(qnames), dtype=bool)
        # DNS batch = its own trace ingress (ISSUE 2): phase spans show
        # whether the batch rode the banked DFA or degraded to regex
        with TRACER.trace("dnsproxy.batch", endpoint=endpoint_id,
                          queries=len(qnames)):
            with TRACER.span("dns.sanitize", phase=PHASE_HOST,
                             records=len(qnames)):
                sanitized = [matchpattern.sanitize_name(q)
                             for q in qnames]
            if not self.use_tpu:
                with TRACER.span("dns.regex", phase=PHASE_FALLBACK,
                                 records=len(sanitized)):
                    return np.array(
                        [any(p.match(q) for p in pats)
                         for q in sanitized], dtype=bool)
            try:
                faults.maybe_fail(QUERY_POINT)
                with TRACER.span("dns.dfa", phase=PHASE_DEVICE,
                                 records=len(sanitized)):
                    st = self._get_banked(key, srcs)
                    from cilium_tpu.engine.dfa_kernel import (
                        dfa_scan_banked,
                    )

                    data = np.zeros((len(sanitized), 256),
                                    dtype=np.uint8)
                    lengths = np.zeros(len(sanitized), dtype=np.int32)
                    for i, q in enumerate(sanitized):
                        bs = q.encode("utf-8")[:256]
                        data[i, : len(bs)] = np.frombuffer(
                            bs, dtype=np.uint8)
                        lengths[i] = len(bs)
                    words = np.asarray(dfa_scan_banked(
                        st["trans"], st["byteclass"], st["start"],
                        st["accept"], data, lengths))
                    return (words.reshape(len(sanitized), -1)
                            .any(axis=1) != 0)
            except Exception:  # noqa: BLE001 — device sick: degrade
                # the regex set and the banked DFA are compiled from
                # the SAME sources, so the fallback answers identically
                # — correct but per-query (the reference's pkg/fqdn/re
                # path)
                METRICS.inc(DNSPROXY_FALLBACKS)
                with TRACER.span("dns.regex", phase=PHASE_FALLBACK,
                                 records=len(sanitized)):
                    return np.array(
                        [any(p.match(q) for p in pats)
                         for q in sanitized], dtype=bool)

    def _get_banked(self, key, srcs):
        """Staged device tensors for the key's automaton, cached keyed
        by the rule sources (a concurrent update_allowed can't leave a
        stale automaton, and steady-state calls skip stack+upload)."""
        import jax

        want = tuple(srcs)
        with self._lock:
            cached = self._banked.get(key)
            if cached is not None and cached[0] == want:
                return cached[1]
        stacked = compile_patterns(list(want)).stacked()
        # one batched pytree upload on a cache miss, not one
        # jnp.asarray transfer per table
        staged = jax.device_put({k: v for k, v in stacked.items()
                                 if k != "lane_of"})
        with self._lock:
            # only install if the rules haven't moved on meanwhile
            if self._rules.get(key) == list(want):
                self._banked[key] = (want, staged)
        return staged

    def observe_response(self, lookup_time: float, qname: str,
                         ips: Iterable[str], ttl: int = 0) -> None:
        """Forwarded-response hook → NameManager (§3.5 tail)."""
        if self.name_manager is not None:
            self.name_manager.update_generate_dns(lookup_time, qname, ips,
                                                  ttl)
