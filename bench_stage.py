#!/usr/bin/env python
"""Staging microbench: capture → staged replay session, decomposed.

The fast lane behind ``make bench-stage``: where ``bench.py``'s e2e
lane buries session staging inside a full throughput run, this bench
measures ONLY the ingest/staging pipeline the columnar-ingest work
targets — columnar capture write, file open/section reads, and the
CaptureReplay staging phases (string-table device scans / whole-file
featurize / hash dedup / unique-table H2D), plus the verdict-memo
fill — and prints one provenance-stamped JSON line per lane
(``bench_schema`` + fingerprint, like every official bench line, so
``cilium-tpu perf-report`` can trend them and attribute regressions).

Two staging samples are taken in-process: ``cold`` (first session —
pays jit tracing and whatever the persistent XLA cache cannot serve)
and ``warm`` (second session over the same shapes — the steady state
a daemon or repeat bench sees). The headline ``stage_ms`` metric is
the cold number: that is what a fresh replay pays.

Usage: python bench_stage.py [--rules 1000] [--capture-flows 200000]
       [--config http] [--verbose]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="http",
                    choices=["http", "fqdn", "kafka", "generic"])
    ap.add_argument("--rules", type=int, default=1000)
    ap.add_argument("--capture-flows", type=int, default=200000)
    ap.add_argument("--scenario-flows", type=int, default=10000)
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    def log(msg: str) -> None:
        if args.verbose:
            print(msg, file=sys.stderr)

    from cilium_tpu.core.config import Config
    from cilium_tpu.engine.verdict import CaptureReplay
    from cilium_tpu.ingest import binary, synth
    from cilium_tpu.runtime.metrics import (
        CAPTURE_STAGE_SECONDS,
        METRICS,
    )
    from cilium_tpu.runtime.provenance import stamp

    cfg = Config.from_env()
    cfg.enable_tpu_offload = True

    scenario = synth.scenario_by_name(args.config, args.rules,
                                      args.scenario_flows)
    per_identity, scenario = synth.realize_scenario(scenario)

    from cilium_tpu.runtime.loader import Loader

    engine = Loader(cfg).regenerate(per_identity, revision=1)

    cap = os.path.join(tempfile.gettempdir(),
                       f"ct_stage_{os.getuid()}_{args.config}_"
                       f"{args.rules}r_{args.capture_flows}f.bin")
    t0 = time.perf_counter()
    n = synth.write_scenario_capture(cap, scenario, args.capture_flows)
    write_ms = round((time.perf_counter() - t0) * 1e3, 1)
    log(f"columnar capture write: {n} records in {write_ms}ms")

    t0 = time.perf_counter()
    rec_all = binary.map_capture(cap)
    l7_all, offsets, blob = binary.read_l7_sidecar(cap)
    gen_all = binary.read_gen_sidecar(cap)
    open_ms = round((time.perf_counter() - t0) * 1e3, 1)

    # memo-fill is deliberately NOT in the stage split: stage_ms
    # covers ingest staging only (the memo fill is the compile/warm
    # analog, reported as memo_fill_ms) — sum(split) ≤ stage_ms holds
    # here exactly as on bench.py's e2e lines
    phases = ("tables", "featurize", "dedup", "table-h2d")

    def marks():
        return {ph: METRICS.histo_sum(CAPTURE_STAGE_SECONDS,
                                      {"phase": ph})
                for ph in phases}

    def stage_once():
        mark0 = marks()
        t0 = time.perf_counter()
        replay = CaptureReplay(engine, l7_all, offsets, blob,
                               cfg.engine, gen=gen_all)
        replay.stage_rows(rec_all, l7_all)
        ratio = replay.stage_unique(
            drop_if_ratio_at_least=cfg.engine.stage_unique_drop_ratio)
        if replay.row_idx is not None:
            replay.stage_unique_device()
        stage_ms = round((time.perf_counter() - t0) * 1e3, 1)
        memo_fill_ms = None
        if replay.row_idx is not None and cfg.engine.verdict_memo:
            import numpy as np

            t1 = time.perf_counter()
            memo = replay.stage_verdict_memo()
            np.asarray(memo.table[:2])  # completion-forced
            memo_fill_ms = round((time.perf_counter() - t1) * 1e3, 1)
        split = {ph: round((after - mark0[ph]) * 1e3, 1)
                 for ph, after in marks().items()}
        return replay, stage_ms, split, ratio, memo_fill_ms

    replay, cold_ms, cold_split, ratio, cold_fill = stage_once()
    _, warm_ms, warm_split, _, warm_fill = stage_once()
    log(f"stage cold {cold_ms}ms {cold_split}; "
        f"warm {warm_ms}ms {warm_split}")

    lanes = [
        {"metric": f"stage_ms_{args.config}_{args.rules}rules",
         "value": cold_ms, "unit": "ms (cold session staging)",
         "vs_baseline": 0.0,
         "stage_ms": cold_ms, "stage_phases_ms": cold_split,
         "stage_warm_ms": warm_ms, "stage_warm_phases_ms": warm_split,
         "memo_fill_ms": cold_fill, "memo_fill_warm_ms": warm_fill,
         "capture_records": int(len(rec_all)),
         "unique_rows": int(replay.n_unique),
         "dedup_ratio": round(ratio, 6),
         "capture_write_ms": write_ms, "capture_open_ms": open_ms},
    ]
    rc = 0
    for lane in lanes:
        stamp(lane)
        print(json.dumps(lane), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
