"""A served cell's knee, found once on the chip (not part of a run):

    python3 -m benchmark.sweep --workload http-served-zipf --seconds 8 --rates 20000,40000,...

One process; each rate runs the cell's window as ``benchmark.run``
would, through the module its traffic file's ``kind`` names
(``benchmark/kinds/<kind>.py``), with only ``rate_records_s`` changed.
Per rate it prints one JSON line: offered and completed records/s,
chunks, failed, p50/p99, the generator's lateness, and ``backlog``: the
median latency of the window's last quarter of chunks ÷ that of its
first quarter (a queue that grows through the window reads well above
1). A kind whose ``ctx`` lacks any of ``CTX_KEYS`` cannot be swept: the
sweep exits non-zero with a message.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile

import numpy as np

from benchmark import compare, run

#: what a kind's ``ctx`` must hold for a rate's line
CTX_KEYS = ("latency_s", "completed", "chunks", "late_s")


class NotSweepable(Exception):
    """The kind's ``ctx`` lacks one of ``CTX_KEYS``."""


def point(kind, cell: dict, cfg: dict, traffic: dict, rate: float,
          seed: int, seconds: float, devices, meter,
          cache_dir: str = run.CACHE) -> dict:
    """One rate's line: the window of ``kind`` at ``rate`` records/s.
    Raises ``NotSweepable`` when the kind's ``ctx`` lacks one of
    ``CTX_KEYS``."""
    scratch = tempfile.mkdtemp(prefix="sweep_")
    try:
        env = run.Env(cell=cell, cfg=cfg,
                      traffic={**traffic, "rate_records_s": rate},
                      seed=seed, seconds=seconds, trace=False,
                      devices=devices, meter=meter, t0=run.T0,
                      cache_dir=cache_dir, scratch=scratch, trace_dir="",
                      reference=compare.Reference,
                      control=lambda d, e: None)
        res = kind.run(env)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    ctx = res["ctx"]
    lacks = [k for k in CTX_KEYS if k not in ctx]
    if lacks:
        raise NotSweepable(f"benchmark.sweep: kind {traffic['kind']!r} "
                           f"reports no {', '.join(lacks)} in its ctx; "
                           f"a sweep needs {', '.join(CTX_KEYS)}")
    lat = ctx["latency_s"]
    q = max(1, len(lat) // 4)
    done = ctx["completed"] * traffic["chunk_records"]
    return {
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
    }


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
    from benchmark.meter import CompileMeter
    from benchmark.program import enable_compile_cache

    kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
    enable_compile_cache(os.path.join(run.CACHE, "jax"))
    meter = CompileMeter()
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            line = point(kind, cell, cfg, traffic, rate, args.seed,
                         args.seconds, devices, meter)
            print(json.dumps(line), flush=True)
    except NotSweepable as e:
        print(e, file=sys.stderr)
        return 2
    finally:
        meter.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
