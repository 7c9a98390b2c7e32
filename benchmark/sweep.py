"""The served cell's knee, found once on the chip (not part of a run):

    python3 -m benchmark.sweep --workload http-served-zipf --seconds 8 --rates 20000,40000,...

One process; each rate runs the cell's window as ``benchmark.run``
would, with only ``rate_records_s`` changed. Per rate it prints one
JSON line: offered and completed records/s, chunks, failed, p50/p99,
the generator's lateness, and ``backlog``: the median latency of the
window's last quarter of chunks ÷ that of its first quarter (a queue
that grows through the window reads well above 1).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

import numpy as np

from benchmark import compare, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="http-served-zipf")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = run.benchmark_spec()
    cell, cfg, traffic = run.cell_parts(spec, args.workload)
    try:
        devices = run.device_gate(cell["chips"])
    except run.NoChip as e:
        return int(e.code)
    from benchmark.kinds import served
    from benchmark.meter import CompileMeter
    from benchmark.program import enable_compile_cache

    enable_compile_cache(os.path.join(run.CACHE, "jax"))
    meter = CompileMeter()
    for rate in [float(r) for r in args.rates.split(",")]:
        scratch = tempfile.mkdtemp(prefix="sweep_")
        try:
            env = run.Env(cell=cell, cfg=cfg,
                          traffic={**traffic, "rate_records_s": rate},
                          seed=args.seed, seconds=args.seconds,
                          trace=False, devices=devices, meter=meter,
                          t0=run.T0, cache_dir=run.CACHE, scratch=scratch,
                          trace_dir="", reference=compare.Reference,
                          control=lambda d, e: None)
            res = served.run(env)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
        ctx = res["ctx"]
        lat = ctx["latency_s"]
        q = max(1, len(lat) // 4)
        done = ctx["completed"] * traffic["chunk_records"]
        print(json.dumps({
            "offered_records_s": rate,
            "completed_records_s": done / ctx["window_s"],
            "chunks": ctx["chunks"], "failed": res["failed"],
            "correct": compare.is_correct(res["checks"]),
            "p50_ms": res["e2e"].get("served_p50_ms"),
            "p99_ms": res["e2e"].get("served_p99_ms"),
            "late_p99_ms": float(np.percentile(ctx["late_s"], 99)) * 1e3,
            "backlog": (float(np.median(lat[-q:]) / np.median(lat[:q]))
                        if lat else None),
            "compiles_in_window": ctx["compiles_in_window"],
        }), flush=True)
    meter.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
