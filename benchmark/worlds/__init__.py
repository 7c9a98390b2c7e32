"""Worlds: a configuration's policy (CNP documents), its endpoints and
its record templates. A config file names its world by ``world``; the
module ``benchmark/worlds/<world>.py`` provides ``policy(cfg)`` →
``(docs, endpoints)`` and ``draw(cfg, traffic, rng, n, first_id)`` →
``n`` records.

A record is a plain tuple, the same for the program and the reference:
``(src, dst, dport, proto, direction, kind, payload)`` with endpoint
names for ``src``/``dst`` and ``kind`` one of ``""``, ``"http"``
(payload ``(method, path, host, headers)``), ``"kafka"`` (``(api_key,
api_version, client_id, topic)``), ``"dns"`` (``(query,)``) or
``"generic"`` (``(proto, fields)``).
"""

from __future__ import annotations



import importlib
from types import ModuleType
from typing import Dict

_L7 = {"": 0, "http": 1, "kafka": 2, "dns": 3, "generic": 4}


def world_module(cfg: dict) -> ModuleType:
    """The module of the config's ``world``."""
    return importlib.import_module(f"benchmark.worlds.{cfg['world']}")


def to_flow(flowmod: ModuleType, rec: tuple, ids: Dict[str, int]):
    """One record as a ``Flow`` of ``flowmod`` — the program's
    ``cilium_tpu.core.flow`` or the reference's copy of it."""
    src, dst, dport, proto, direction, kind, payload = rec
    f = flowmod.Flow(src_identity=ids[src], dst_identity=ids[dst],
                     dport=dport, protocol=flowmod.Protocol(proto),
                     direction=flowmod.TrafficDirection(direction),
                     l7=flowmod.L7Type(_L7[kind]))
    if kind == "http":
        method, path, host, headers = payload
        f.http = flowmod.HTTPInfo(method=method, path=path, host=host,
                                  headers=headers)
    elif kind == "kafka":
        api_key, api_version, client_id, topic = payload
        f.kafka = flowmod.KafkaInfo(api_key=api_key,
                                    api_version=api_version,
                                    client_id=client_id, topic=topic)
    elif kind == "dns":
        f.dns = flowmod.DNSInfo(query=payload[0])
    elif kind == "generic":
        f.generic = flowmod.GenericL7Info(proto=payload[0],
                                          fields=dict(payload[1]))
    return f


def resolve(mods, docs, endpoints):
    """``(per_identity map states, endpoint name → identity)`` for the
    CNP ``docs`` over ``endpoints`` — the steps of ``realize_scenario``
    (``cilium_tpu/ingest/synth.py``). ``mods`` has the modules
    ``identity``, ``labels``, ``cnp``, ``selectorcache``,
    ``repository`` and ``mapstate``: the program's or the reference's
    copies, so each side resolves with its own code."""
    alloc = mods.identity.IdentityAllocator()
    ids: Dict[str, int] = {}
    labelsets = {}
    for name, lbls in endpoints.items():
        ls = mods.labels.LabelSet.from_dict(lbls)
        ids[name] = int(alloc.allocate(ls))
        labelsets[name] = ls
    rules = [r for d in docs for r in mods.cnp.parse_cnp(d).rules]
    repo = mods.repository.Repository()
    repo.add(rules, sanitize=False)
    resolver = mods.mapstate.PolicyResolver(
        repo, mods.selectorcache.SelectorCache(alloc))
    per_identity = {ids[n]: resolver.resolve(labelsets[n])
                    for n in endpoints}
    return per_identity, ids
