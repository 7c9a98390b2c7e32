"""The chip benchmark's one entry point:

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to a cell is found by name: the cell in
``BENCHMARK.json`` names its configuration (``configs[].file``) and its
traffic mix (``benchmark/traffic/<traffic>.json``), whose ``kind``
names the driver of the window (``benchmark/kinds/<kind>.py``); each
per-layer metric is read by ``benchmark/metrics/<name>.py``. A cell or
metric is added by adding files and entries.

The run exits non-zero, printing no result, when JAX's first device is
not a TPU or there are fewer chips than the cell asks for. Its last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, (traced) ``breakdown``, and last
``checks``, the compared numbers with their limits, which also end
standard error.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # noqa: E402 — set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: caches the benchmark keeps in its checkout (listed in .gitignore)
CACHE = os.path.join(HERE, ".cache")


class NoChip(SystemExit):
    pass


@dataclasses.dataclass
class Env:
    """What a kind's ``run`` gets."""
    cell: dict
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    meter: object
    t0: float
    cache_dir: str
    scratch: str
    trace_dir: str
    #: ``(docs, endpoints)`` → the plain reference
    reference: Callable
    #: ``(docs, endpoints)`` → the control that takes the program's
    #: place in the comparison, or None (every real run)
    control: Callable
    #: a line for standard output, before the result
    log: Callable = print


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_spec(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell_parts(spec: dict, workload: str, root: str = ROOT):
    """``(cell, config, traffic)`` of the named cell."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"cells: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "benchmark", "traffic",
                                     f"{cell['traffic']}.json"))
    return cell, cfg, traffic


def device_gate(chips: int):
    """The TPU devices, or exit non-zero before any other work."""
    # libtpu logs under /tmp/tpu_logs unless told otherwise; a run
    # writes only inside its checkout and the TMPDIR it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"benchmark: no TPU (JAX found {devs[0].platform}); the "
              f"benchmark runs on the chip only", file=sys.stderr)
        raise NoChip(2)
    if len(devs) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        raise NoChip(2)
    return devs[:chips]


def metric_reader(name: str, root: str = ROOT):
    """``read(ctx)`` of ``benchmark/metrics/<name>.py``, or, for a
    metric split by the end-to-end metric it moves (``<stem>.<split>``),
    of ``<stem>.py``."""
    path = os.path.join(root, "benchmark", "metrics", f"{name}.py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(root, "benchmark", "metrics",
                            f"{name.rsplit('.', 1)[0]}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_for(spec: dict, cell: str, e2e_names) -> List[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose ``moves`` the cell reports."""
    out = []
    for m in spec["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e_names:
            out.append(m)
    return out


def e2e_for(spec: dict, cell: str) -> List[dict]:
    return [m for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             devices, root: str = ROOT, control: bool = False,
             log=print, cache_dir: str = CACHE) -> dict:
    """One run of a cell on ``devices``; returns the result object.
    ``root`` holds ``BENCHMARK.json`` and the cell's files;
    ``cache_dir`` the compile and artifact caches."""
    from benchmark import compare
    from benchmark.meter import CompileMeter
    from benchmark.program import enable_compile_cache

    spec = benchmark_spec(root)
    cell, cfg, traffic = cell_parts(spec, workload, root)
    enable_compile_cache(os.path.join(cache_dir, "jax"))
    meter = CompileMeter()
    scratch = tempfile.mkdtemp(prefix="bench_")
    try:
        env = Env(cell=cell, cfg=cfg, traffic=traffic, seed=seed,
                  seconds=seconds, trace=trace, devices=devices,
                  meter=meter, t0=T0, cache_dir=cache_dir,
                  scratch=scratch,
                  trace_dir=os.path.join(cache_dir, "trace", workload),
                  reference=compare.Reference,
                  control=(lambda d, e: compare.Reference(
                      d, e, control=True)) if control else
                  (lambda d, e: None), log=log)
        kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
        res = kind.run(env)
    finally:
        meter.close()
        shutil.rmtree(scratch, ignore_errors=True)
    ctx = res["ctx"]
    ctx["cell"] = workload
    ctx["device_kind"] = devices[0].device_kind
    log(f"set-up: {ctx['compiles_setup']} compilations, "
        f"{ctx['cache_hits_setup']} of them from the persistent cache")
    log(f"compilations in the window: {ctx['compiles_in_window']}")
    log(f"window: {ctx['window_s']:.3f}s; e2e: "
        f"{json.dumps(res['e2e'])}")
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    metrics = {}
    e2e_spec = e2e_for(spec, workload)
    if trace:
        tr = ctx["trace"] or {}
        if tr:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            log(f"trace: reduced in {tr['reduce_s']:.3f}s; device "
                f"seconds per module: {json.dumps(tr['module_s'])}")
        for m in per_layer_for(spec, workload,
                               {e["name"] for e in e2e_spec}):
            v = metric_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in e2e_spec:
            if m["name"] in res["e2e"]:
                metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                      "unit": m["unit"]}
    out = {"correct": compare.is_correct(res["checks"]),
           "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": device}
    if trace and ctx["trace"]:
        out["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                            "idle_gaps": ctx["trace"]["idle_gaps"]}
    out["checks"] = res["checks"]
    return out


def fill_cache(workload: str, seed: int) -> int:
    """On a checkout's first run of ``workload``, run the cell once in a
    child process (before this one touches JAX, so the chip is free) to
    compile every program it uses into the persistent cache. Every
    window, the first included, then runs programs loaded from the
    cache: compiled in the process instead, the scan replays
    ``http-replay-fresh`` ~20% faster (PR 22), and a checkout's first
    run would read apart from the rest. Returns the child's exit code
    (0 once the cache is filled)."""
    mark = os.path.join(CACHE, "filled", workload)
    if os.path.exists(mark):
        return 0
    rc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", workload,
         "--seed", str(seed), "--seconds", "0", "--fill-cache"],
        cwd=ROOT, stdout=2).returncode
    if rc == 0:
        os.makedirs(os.path.dirname(mark), exist_ok=True)
        open(mark, "w").close()
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description="cilium-tpu chip benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="compare against the control (the reference "
                         "with L7 unenforced) in the program's place; "
                         "the run must come out not correct")
    ap.add_argument("--fill-cache", action="store_true",
                    help="the child of a checkout's first run of the "
                         "cell: compile every program into the cache")
    args = ap.parse_args(argv)
    spec = benchmark_spec()
    cell, _, _ = cell_parts(spec, args.workload)
    if not args.fill_cache:
        rc = fill_cache(args.workload, args.seed)
        if rc:
            return rc
    try:
        devices = device_gate(cell["chips"])
    except NoChip as e:
        return int(e.code)
    out = run_cell(args.workload, args.seed, args.seconds,
                   bool(args.trace), devices, control=args.control,
                   log=lambda m: print(m, flush=True))
    for name, c in out["checks"].items():
        bound = ">=" if c.get("at_least") else "<="
        print(f"check {name}: {c['value']} (limit {bound} {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
