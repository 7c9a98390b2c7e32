"""BASELINE configs[1]: 1k HTTP path/method/host/header regex rules on
one port-80 ingress CNP, and HTTP requests against them.

The rule and request shapes are a copy of ``synth_http_scenario``
(``cilium_tpu/ingest/synth.py``, PR 21): five rule kinds by ``i % 5``,
and per rule one template that hits and one that misses. The policy is
written as the CNP document a user would apply.
"""

from __future__ import annotations

import bisect
import itertools
import random
from typing import List, Tuple

ING, TCP = 1, 6


def _rule(i: int) -> dict:
    kind = i % 5
    if kind == 0:
        return {"method": "GET", "path": f"/api/v{i % 9}/svc{i}/[a-z0-9]+"}
    if kind == 1:
        return {"method": "POST", "path": f"/api/v1/items/{i}(/.*)?"}
    if kind == 2:
        return {"path": f"/public/{i}/.*", "host": f"svc{i % 50}[.]local"}
    if kind == 3:
        return {"method": "GET|HEAD",
                "path": f"/static/{i}/[0-9]+/[a-f0-9]+"}
    return {"method": "PUT", "path": f"/admin/{i}/config",
            "headers": [f"X-Role: admin{i % 10}"]}


def policy(cfg: dict) -> Tuple[List[dict], dict]:
    """The CNP document and the two endpoints."""
    doc = {
        "apiVersion": "cilium.io/v2",
        "kind": "CiliumNetworkPolicy",
        "metadata": {"name": "http-1k-regex"},
        "spec": {
            "endpointSelector": {"matchLabels": {"app": "server"}},
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"app": "client"}}],
                "toPorts": [{
                    "ports": [{"port": "80", "protocol": "TCP"}],
                    "rules": {"http": [_rule(i)
                                       for i in range(cfg["rules"])]},
                }],
            }],
        },
    }
    return [doc], {"server": {"app": "server"},
                   "client": {"app": "client"}}


def request(i: int, hit: bool, uid=None) -> tuple:
    """Rule ``i``'s request template (``synth_http_scenario``), with an
    optional per-record id ``uid`` put where it keeps the template's
    hit or miss (kinds 0-3; kind 0's miss takes it between slashes,
    which ``[a-z0-9]+`` does not cross); kind 4's path is exact, so its
    id rides the query string and every kind-4 record misses its rule:
    with ids, 40% of records hit."""
    kind = i % 5
    headers: tuple = ()
    if kind == 0:
        path = f"/api/v{i % 9}/svc{i}/x9y" if hit else f"/api/v{i % 9}/svc{i}/"
        method = "GET"
        if uid is not None:
            path += f"{uid:x}" if hit else f"{uid:x}/"
    elif kind == 1:
        path = f"/api/v1/items/{i}/sub" if hit else f"/api/v1/items/{i}x"
        method = "POST"
        if uid is not None:
            path += f"/{uid:x}" if hit else f"{uid:x}"
    elif kind == 2:
        path = f"/public/{i}/a/b" if hit else f"/private/{i}/a"
        method = "GET"
        if uid is not None:
            path += f"/{uid:x}"
    elif kind == 3:
        path = f"/static/{i}/123/abc9" if hit else f"/static/{i}/123/XYZ"
        method = "HEAD"
        if uid is not None:
            path += f"{uid:x}"
    else:
        path = f"/admin/{i}/config"
        method = "PUT"
        headers = ((("X-Role", f"admin{i % 10}"),) if hit
                   else (("X-Role", "nobody"),))
        if uid is not None:
            path += f"?u={uid:x}"
    return ("client", "server", 80, TCP, ING, "http",
            (method, path, f"svc{i % 50}.local", headers))


def zipf_cdf(n: int, s: float) -> List[float]:
    """Cumulative weights of ranks 1..n under Zipf exponent ``s``."""
    return list(itertools.accumulate(1.0 / (r ** s)
                                     for r in range(1, n + 1)))


def draw(cfg: dict, traffic: dict, rng: random.Random, n: int,
         first_id: int = 0) -> List[tuple]:
    """``n`` requests. ``traffic["rules"]``: ``"uniform"`` (as
    ``synth_http_scenario`` draws them) or ``"zipf"`` (exponent
    ``traffic["zipf_s"]`` over a seeded ranking of the rules);
    ``traffic["unique_paths"]`` gives record ``k`` the id
    ``first_id + k``. Half the records take the hit template."""
    n_rules = cfg["rules"]
    unique = traffic.get("unique_paths", False)
    if traffic.get("rules_by", "uniform") == "zipf":
        rank = list(range(n_rules))
        rng.shuffle(rank)
        cdf = zipf_cdf(n_rules, traffic["zipf_s"])
        top = cdf[-1]

        def pick() -> int:
            return rank[min(n_rules - 1,
                            bisect.bisect_left(cdf, rng.random() * top))]
    else:
        def pick() -> int:
            return rng.randrange(n_rules)
    out = []
    for k in range(n):
        i = pick()
        hit = rng.random() < 0.5
        out.append(request(i, hit, first_id + k if unique else None))
    return out
