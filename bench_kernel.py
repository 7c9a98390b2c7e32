#!/usr/bin/env python
"""Megakernel microbench: fused verdict step vs the three-op path,
plus the per-bank-shape dense-DFA vs bitset-NFA sweep.

The lane behind ``make bench-kernel``: where ``bench.py`` buries the
verdict step inside a full e2e run, this bench isolates exactly what
the MXU-native megakernel (``engine/megakernel.py``) changed:

* **headline lane** — the 1k-rule config's verdict step, measured two
  ways over distinct permuted device copies: the THREE-OP path
  (mapstate → scan → resolve as three separately-jitted,
  completion-forced dispatches — the pre-megakernel execution shape,
  the same decomposition ``EnginePhaseProbe`` attributes) vs the
  FUSED megakernel (one dispatch). The line carries both rates, the
  speedup, p50/p99 per batch for each path, the engine's kernel plan
  (autotune picks per field/bank shape), and the resolve-plan group
  count. ``--min-speedup`` (the strict-mode gate; default 2.0 per the
  ROADMAP target) fails the lane when the fused step stops paying.
* **shape sweep** — dense vs bitset-NFA measured per synthetic bank
  shape through the SAME autotuner the engine uses
  (``megakernel.autotune_field``): a literal-heavy bank (small DFA,
  small NFA), a state-explosion bank (alternation/wildcard-heavy:
  the regime the NFA arm exists for), and a wide dense bank. One
  provenance-stamped line per shape with both timings and the pick.

Every line is ``bench_schema``-stamped so ``cilium-tpu perf-report``
trends them and its regression gate covers the device-lane
verdicts/s trajectory.

Usage: python bench_kernel.py [--config http] [--rules 1000]
       [--flows 8192] [--min-speedup 2.0] [--out BENCH_KERNEL.jsonl]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _percentile(sorted_times, q: float) -> float:
    i = min(len(sorted_times) - 1, int(len(sorted_times) * q))
    return sorted_times[i]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="http",
                    choices=["http", "fqdn", "kafka"])
    ap.add_argument("--rules", type=int, default=1000)
    ap.add_argument("--flows", type=int, default=8192)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--min-speedup", type=float, default=2.0,
                    help="strict gate: fail when fused/three-op falls "
                         "below this (0 disables)")
    ap.add_argument("--out", default=None,
                    help="also append the JSON lines here")
    ap.add_argument("--skip-sweep", action="store_true")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    def log(msg: str) -> None:
        if args.verbose:
            print(msg, file=sys.stderr)

    import jax
    import numpy as np

    from cilium_tpu.core.config import Config
    from cilium_tpu.engine import megakernel
    from cilium_tpu.engine.phases import (
        _force,
        _live_mapstate,
        _live_resolve,
        _live_scan,
        _timed,
    )
    from cilium_tpu.engine.verdict import (
        encode_flows,
        flowbatch_to_host_dict,
    )
    from cilium_tpu.ingest import synth
    from cilium_tpu.runtime.loader import Loader
    from cilium_tpu.runtime.provenance import stamp

    cfg = Config.from_env()
    cfg.enable_tpu_offload = True

    per_identity, scenario = synth.realize_scenario(
        synth.scenario_by_name(args.config, args.rules, args.flows))
    loader = Loader(cfg)
    t0 = time.perf_counter()
    engine = loader.regenerate(per_identity, revision=1)
    log(f"policy staged in {time.perf_counter() - t0:.2f}s; "
        f"impl plan {engine.impl_plan}")

    host = flowbatch_to_host_dict(encode_flows(
        scenario.flows, engine.policy.kafka_interns, cfg.engine))
    arrays = engine._arrays
    _ms = jax.jit(_live_mapstate)
    _scan = jax.jit(_live_scan)
    _res = jax.jit(_live_resolve)
    fused = engine._step

    # distinct permuted device copies per timed call (bench.py
    # methodology: no caching layer may shortcut repeats)
    prng = np.random.default_rng(0)
    n = len(scenario.flows)

    def copies(k):
        out = []
        for _ in range(k):
            perm = prng.permutation(n)
            out.append({k2: jax.device_put(v[perm])
                        for k2, v in host.items()})
        jax.block_until_ready(out)
        return out

    warm = copies(1)[0]
    # compile both paths off the clock
    _timed(lambda: fused(arrays, warm), 1)

    def three_op(batch):
        m = _ms(arrays, batch)
        _force(m)
        w = _scan(arrays, batch)
        _force(w)
        return _res(arrays, m, w, batch)

    _timed(lambda: three_op(warm), 1)

    def run(step):
        batches = copies(args.reps)
        times = []
        for b in batches:
            t0 = time.perf_counter()
            _force(step(b))
            times.append(time.perf_counter() - t0)
        times.sort()
        return times

    t_three = run(three_op)
    t_fused = run(lambda b: fused(arrays, b))
    fused_p50 = t_fused[len(t_fused) // 2]
    three_p50 = t_three[len(t_three) // 2]
    fused_vps = n / fused_p50
    three_vps = n / three_p50
    speedup = three_p50 / fused_p50
    log(f"three-op {three_p50 * 1e3:.1f}ms ({three_vps:,.0f} vps)  "
        f"fused {fused_p50 * 1e3:.1f}ms ({fused_vps:,.0f} vps)  "
        f"{speedup:.2f}x")

    groups = (engine.policy.resolve_meta or {}).get("groups")
    lines = [{
        "metric": (f"kernel_fused_verdicts_per_sec_{args.config}_"
                   f"{args.rules}rules"),
        "value": round(fused_vps, 1),
        "unit": "verdicts/s (fused megakernel, per-batch forced)",
        "vs_baseline": round(fused_vps / 10e6, 4),
        "batch": n,
        "separate_op_verdicts_per_sec": round(three_vps, 1),
        "fused_speedup": round(speedup, 3),
        "fused_p50_ms": round(fused_p50 * 1e3, 3),
        "fused_p99_ms": round(_percentile(t_fused, 0.99) * 1e3, 3),
        "three_op_p50_ms": round(three_p50 * 1e3, 3),
        "three_op_p99_ms": round(_percentile(t_three, 0.99) * 1e3, 3),
        "fused_dispatches": 1,
        "three_op_dispatches": 3,
        "resolve_groups": groups,
        "impl_plan": dict(engine.impl_plan),
        "kernel_report": engine.kernel_report,
    }]

    # ---- per-bank-shape dense vs bitset-NFA sweep ----------------------
    if not args.skip_sweep:
        from cilium_tpu.core.config import EngineConfig
        from cilium_tpu.engine import nfa_kernel
        from cilium_tpu.policy.compiler.dfa import compile_patterns

        shapes = {
            # literal-heavy: tiny DFA and tiny NFA — gather's home turf
            "literal": ([f"/svc{i}/get" for i in range(24)], 8),
            # state-explosion regime: .* prefixes multiply DFA subsets
            # while the position count stays the pattern length sum
            "explosion": ([f"a.*{c}x[0-9]z" for c in "bcdefgh"], 7),
            # wide dense bank: many classes, mid-size DFA
            "wide": ([f"/api/v{i}/[a-z]+/{i}(/.*)?"
                      for i in range(16)], 4),
        }
        ecfg = EngineConfig()
        for name, (pats, bank_size) in shapes.items():
            banked = compile_patterns(pats, bank_size=bank_size)
            st = banked.stacked()
            arrays_s = {f"sweep_{k}": jax.device_put(v)
                        for k, v in st.items()}
            banks = nfa_kernel.banks_from_dfa(banked, ecfg)
            nfa_stacked = (nfa_kernel.stack_nfa_banks(banks)
                           if banks is not None else None)
            report = megakernel.autotune_field(
                f"sweep-{name}", arrays_s, "sweep", nfa_stacked,
                width=32, interpret=jax.default_backend() != "tpu")
            log(f"sweep {name}: {report}")
            lines.append({
                "metric": f"kernel_scan_sweep_{name}",
                "value": report["dense_ms"],
                "unit": "ms (dense arm, 256x32 probe batch)",
                "vs_baseline": 0.0,
                "dense_ms": report["dense_ms"],
                "nfa_ms": report["nfa_ms"],
                "impl": report["impl"],
                "dfa_states": int(st["trans"].shape[1]),
                "nfa_positions": (
                    int(nfa_stacked["nfa_follow"].shape[1])
                    if nfa_stacked is not None else None),
                "patterns": len(pats),
            })

    out_fp = open(args.out, "a") if args.out else None
    for line in lines:
        stamp(line)
        text = json.dumps(line)
        print(text, flush=True)
        if out_fp:
            out_fp.write(text + "\n")
    if out_fp:
        out_fp.close()

    if args.min_speedup and speedup < args.min_speedup:
        print(f"bench-kernel GATE FAILED: fused speedup {speedup:.2f}x "
              f"< {args.min_speedup}x", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
