#!/usr/bin/env python
"""Protocol-frontend bench lane (ISSUE 15): per-protocol verdict
throughput + clustermesh-scale cross-cluster churn.

``make bench-protocols`` runs two legs and appends provenance-stamped
JSON lines to ``BENCH_PROTO_r07.jsonl`` (consumed by perf-report):

* **per-protocol throughput** — for each frontend family (cassandra /
  memcache / r2d2) plus the mixed ``protocols`` scenario, compile the
  policy through the frontend registry and replay a capture-shaped
  corpus through the staged session (fused megakernel dispatch + the
  device verdict memo gather — the same modern stack the http lanes
  ride), reporting verdicts/s per lane. An ``http`` reference lane
  runs in the same process so a host-speed change is visible on the
  artifact itself (perf-report additionally gates the committed
  http/kafka lanes across rounds).

* **cross-cluster churn** — two live Agents: cluster ``alpha``
  publishes endpoint identities into its kvstore; cluster ``beta``
  watches them through clustermesh, re-allocates them locally, and
  serves an L7 frontend policy selecting alpha's pods. A remote-
  identity churn storm (default 50 add/remove updates) then measures
  update→enforcement latency END TO END — kvstore event → ipcache →
  selector cache → debounced regeneration → compiled frontend banks
  serving the new identity — with ZERO stale/ERROR verdicts tolerated
  at every step and the p99 gated against 2× the committed
  single-cluster churn number (BENCH_CHURN_r06.jsonl), the ISSUE-15
  acceptance bound.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

#: p99 gate: cross-cluster update→enforcement p99 must stay within
#: this factor of the committed single-cluster churn p99
P99_FACTOR = 2.0

#: per-protocol throughput lanes (scenario name, rules, flows)
PROTO_LANES = (("cassandra", 40, 120000), ("memcache", 40, 120000),
               ("r2d2", 40, 120000), ("protocols", 120, 200000),
               ("http", 200, 120000))


def _proto_scenario(name: str, n_rules: int, n_flows: int):
    """Single-protocol scenarios reuse the mixed generator with a
    1.0 share; http/protocols use their own generators."""
    from cilium_tpu.ingest import synth

    if name in ("http", "protocols"):
        return synth.scenario_by_name(name, n_rules, n_flows)
    return synth.synth_protocols_scenario(
        n_rules=n_rules, n_flows=n_flows, mix=((name, 1.0),))


def run_throughput(name: str, n_rules: int, n_flows: int,
                   cache_dir: str, log) -> dict:
    import numpy as np

    from cilium_tpu.core.config import Config
    from cilium_tpu.core.flow import Verdict
    from cilium_tpu.engine.verdict import CaptureReplay
    from cilium_tpu.ingest import synth
    from cilium_tpu.ingest.columnar import flows_to_columns

    scenario = _proto_scenario(name, n_rules, n_flows)
    per_identity, scenario = synth.realize_scenario(scenario)
    cfg = Config()
    cfg.enable_tpu_offload = True
    cfg.loader.cache_dir = cache_dir
    from cilium_tpu.runtime.loader import Loader

    loader = Loader(cfg)
    t0 = time.perf_counter()
    loader.regenerate(per_identity, revision=1)
    compile_s = time.perf_counter() - t0
    cols = flows_to_columns(scenario.flows)
    t0 = time.perf_counter()
    replay = CaptureReplay(loader.engine, cols.l7, cols.offsets,
                           cols.blob, cfg.engine, gen=cols.gen,
                           loader=loader)
    replay.stage_rows(cols.rec, cols.l7)
    replay.stage_unique()
    stage_s = time.perf_counter() - t0
    # memo fill (excluded from the throughput window by methodology —
    # same split as bench.py's e2e lane)
    out = replay.verdict_chunk(cols.rec, cols.l7)
    assert int(Verdict.ERROR) not in out["verdict"], "ERROR verdicts"
    # sampled oracle agreement: the lane is a correctness gate too
    sample = scenario.flows[:512]
    want = loader.fallback_engine.verdict_flows(sample)["verdict"]
    got = loader.engine.verdict_flows(sample)["verdict"]
    assert list(map(int, got)) == list(map(int, want)), \
        f"{name}: engine disagrees with oracle"
    reps, n = 3, len(scenario.flows)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = replay.verdict_chunk(cols.rec, cols.l7)
    dt = time.perf_counter() - t0
    vps = reps * n / dt
    m = replay.memo
    allowed = float(np.mean(np.asarray(out["verdict"])
                            == int(Verdict.REDIRECTED)))
    loader.close()
    log(f"[{name}] {vps / 1e6:.2f}M verdicts/s "
        f"(compile {compile_s:.2f}s, stage {stage_s * 1e3:.0f}ms, "
        f"allow {allowed:.2f})")
    line = {
        "metric": f"proto_{name}_verdicts_per_s",
        "value": round(vps, 1),
        "unit": "verdicts/s (memo-gather replay)",
        "lane": "bench-protocols",
        "protocol": name,
        "rules": n_rules,
        "flows": n,
        "compile_s": round(compile_s, 3),
        "stage_ms": round(stage_s * 1e3, 1),
        "memo_hit_ratio": round(m.hits / max(1, m.hits + m.misses), 6)
        if m else None,
        "allow_fraction": round(allowed, 4),
        "stream": "id+memo",
    }
    return line


# ---------------------------------------------------------------------------
# cross-cluster churn


_BETA_CNP = """\
apiVersion: cilium.io/v2
kind: CiliumNetworkPolicy
metadata:
  name: allow-remote-cassandra
spec:
  endpointSelector:
    matchLabels:
      app: store
  ingress:
    - fromEndpoints:
        - matchLabels:
            app: db
      toPorts:
        - ports:
            - port: "9042"
              protocol: TCP
          rules:
            l7proto: cassandra
            l7:
              - query_action: select
                query_table: users
              - query_action: batch
"""


def _baseline_churn_p99(root: str) -> float:
    path = os.path.join(root, "BENCH_CHURN_r06.jsonl")
    p99 = 1158.772                   # the committed r06 number
    try:
        with open(path) as fp:
            vals = []
            for raw in fp:
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    d = json.loads(raw)
                except ValueError:
                    continue
                if d.get("metric") == "churn_update_p99_ms":
                    vals.append(float(d["value"]))
            if vals:
                p99 = max(vals)
    except OSError:
        pass
    return p99


def run_crosscluster(updates: int, log, root: str = ".",
                     gate_p99: bool = True) -> dict:
    import tempfile
    import textwrap  # noqa: F401  (yaml inline above)

    from cilium_tpu.agent import Agent
    from cilium_tpu.core.config import Config
    from cilium_tpu.core.flow import (
        Flow,
        GenericL7Info,
        L7Type,
        Protocol,
        TrafficDirection,
        Verdict,
    )

    cfg_a = Config(cluster_name="alpha")
    cfg_b = Config(cluster_name="beta")
    cfg_b.enable_tpu_offload = True
    cfg_b.loader.cache_dir = tempfile.mkdtemp(prefix="ct_xc_")
    # per-event regeneration: the lane measures the un-coalesced
    # update→enforcement path (the debounced path coalesces storms —
    # a different, cheaper number)
    cfg_b.loader.identity_regen_debounce_s = 0.0
    a = Agent(cfg_a).start()
    b = Agent(cfg_b).start()
    try:
        b.endpoint_add(1, {"app": "store"}, ipv4="10.2.0.1")
        with tempfile.NamedTemporaryFile(
                "w", suffix=".yaml", delete=False) as f:
            f.write(_BETA_CNP)
            path = f.name
        try:
            b.policy_add_file(path)
        finally:
            os.unlink(path)
        b.clustermesh.connect("alpha", a.kvstore)
        store_id = b.endpoint_manager.get(1).identity

        def probe(remote_id: int, table: str, action="select"):
            return Flow(
                src_identity=remote_id, dst_identity=store_id,
                dport=9042, protocol=Protocol.TCP,
                direction=TrafficDirection.INGRESS,
                l7=L7Type.GENERIC,
                generic=GenericL7Info(
                    proto="cassandra",
                    fields={"query_action": action,
                            "query_table": table}))

        def enforced(remote_id) -> bool:
            out = b.loader.engine.verdict_flows(
                [probe(remote_id, "users"),
                 probe(remote_id, "secrets")])["verdict"]
            return (int(out[0]) == int(Verdict.REDIRECTED)
                    and int(out[1]) == int(Verdict.DROPPED))

        lat_ms = []
        errors = stale = 0
        live = []
        for step in range(updates):
            if live and step % 3 == 2:
                eid, ip = live.pop(0)
                a.endpoint_remove(eid)
                # removal propagates: the identity must stop being
                # resolvable in beta's ipcache
                t0 = time.perf_counter()
                while b.ipcache.lookup(ip) is not None:
                    if time.perf_counter() - t0 > 30:
                        raise AssertionError("remote delete stuck")
                    time.sleep(0.001)
                lat_ms.append((time.perf_counter() - t0) * 1e3)
                continue
            eid = 100 + step
            ip = f"10.1.{step // 200}.{step % 200 + 1}"
            t0 = time.perf_counter()
            a.endpoint_add(eid, {"app": "db", "pod": f"p{step}"},
                           ipv4=ip)
            remote_id = b.ipcache.lookup(ip)
            assert remote_id is not None, "remote identity missing"
            while not enforced(remote_id):
                if time.perf_counter() - t0 > 60:
                    raise AssertionError(
                        f"update {step} never enforced")
                time.sleep(0.001)
            lat_ms.append((time.perf_counter() - t0) * 1e3)
            live.append((eid, ip))
            # staleness + ERROR sweep over every LIVE remote identity
            for _eid, lip in live:
                rid = b.ipcache.lookup(lip)
                out = b.loader.engine.verdict_flows(
                    [probe(rid, "users"), probe(rid, "secrets"),
                     probe(rid, "users", action="batch")])["verdict"]
                vals = list(map(int, out))
                if int(Verdict.ERROR) in vals:
                    errors += 1
                # batch rule carries no table constraint → allows
                want = [int(Verdict.REDIRECTED), int(Verdict.DROPPED),
                        int(Verdict.REDIRECTED)]
                if vals != want:
                    stale += 1
        assert errors == 0, f"{errors} ERROR verdicts under churn"
        assert stale == 0, f"{stale} stale verdicts under churn"
        lat_ms.sort()
        p99 = lat_ms[min(len(lat_ms) - 1, int(0.99 * len(lat_ms)))]
        p50 = lat_ms[len(lat_ms) // 2]
        base = _baseline_churn_p99(root)
        bound = P99_FACTOR * base
        if gate_p99:
            assert p99 <= bound, (
                f"cross-cluster update->enforcement p99 {p99:.0f}ms "
                f"blew the bound {bound:.0f}ms (= {P99_FACTOR} x the "
                f"committed single-cluster churn {base:.0f}ms)")
        log(f"[crosscluster] {updates} remote-identity updates: "
            f"p50 {p50:.0f}ms p99 {p99:.0f}ms (bound {bound:.0f}ms), "
            f"0 stale / 0 ERROR")
        return {
            "metric": "crosscluster_update_p99_ms",
            "value": round(p99, 3),
            "unit": "ms remote-identity update->enforcement p99",
            "lane": "bench-protocols",
            "updates": updates,
            "p50_ms": round(p50, 3),
            "p99_bound_ms": round(bound, 3),
            "baseline_churn_p99_ms": base,
            "p99_gated": bool(gate_p99),
            "stale": stale,
            "errors": errors,
            "protocol": "cassandra",
        }
    finally:
        b.stop()
        a.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="protocol-frontend throughput + cross-cluster "
                    "churn lane")
    ap.add_argument("--updates", type=int, default=50)
    ap.add_argument("--flows-scale", type=float, default=1.0,
                    help="scale every lane's flow count (smoke runs)")
    ap.add_argument("--skip-throughput", action="store_true")
    ap.add_argument("--skip-crosscluster", action="store_true")
    ap.add_argument("--no-p99-gate", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--verbose", action="store_true", default=True)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr)

    import tempfile

    from cilium_tpu.runtime.provenance import stamp

    lines = []
    if not args.skip_throughput:
        with tempfile.TemporaryDirectory(prefix="ct_proto_") as cache:
            for name, rules, flows in PROTO_LANES:
                lines.append(run_throughput(
                    name, rules, max(2048, int(flows
                                               * args.flows_scale)),
                    cache, log))
    if not args.skip_crosscluster:
        lines.append(run_crosscluster(args.updates, log,
                                      gate_p99=not args.no_p99_gate))
    out_lines = [stamp(dict(ln)) for ln in lines]
    if args.out:
        with open(args.out, "a") as fp:
            for ln in out_lines:
                fp.write(json.dumps(ln) + "\n")
    for ln in out_lines:
        print(json.dumps(ln))
    return 0


if __name__ == "__main__":
    sys.exit(main())
