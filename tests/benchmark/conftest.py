"""Fixtures of the benchmark's CPU rehearsal: a tiny copy of the
benchmark's data (fewer rules, small segments and chunks) and a run of
a cell on the CPU with the chip gate skipped."""

from __future__ import annotations

import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: what the tiny copy changes, per traffic kind / config key
TINY_TRAFFIC = {
    "replay": {"segment_records": 512, "compare_per_segment": 32,
               "compare_max": 4096},
    "served": {"chunk_records": 64, "pool_images": 8, "connections": 2,
               "rate_records_s": 4000},
}
TINY_RULES = 50


def make_tiny_root(dst: str) -> str:
    """BENCHMARK.json, configs, traffic and metric readers under
    ``dst``, cut to a size the CPU runs in a second or two."""
    os.makedirs(os.path.join(dst, "benchmark", "configs"))
    os.makedirs(os.path.join(dst, "benchmark", "traffic"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "benchmark", "metrics"),
                    os.path.join(dst, "benchmark", "metrics"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    src = os.path.join(REPO, "benchmark", "configs")
    for f in os.listdir(src):
        if f.endswith(".json"):
            with open(os.path.join(src, f)) as fh:
                cfg = json.load(fh)
            if "rules" in cfg:
                cfg["rules"] = TINY_RULES
            with open(os.path.join(dst, "benchmark", "configs", f),
                      "w") as fh:
                json.dump(cfg, fh)
    src = os.path.join(REPO, "benchmark", "traffic")
    for f in os.listdir(src):
        with open(os.path.join(src, f)) as fh:
            t = json.load(fh)
        t.update(TINY_TRAFFIC[t["kind"]])
        with open(os.path.join(dst, "benchmark", "traffic", f), "w") as fh:
            json.dump(t, fh)
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(str(tmp_path / "root"))


@pytest.fixture
def run_tiny(tiny_root, tmp_path, monkeypatch):
    """``run_tiny(cell, trace=False, control=False, seed=...)`` → (result,
    log lines): one CPU run of the cell on the tiny copy, the compile
    cache left as the test process has it."""
    import jax

    from benchmark import program, run

    monkeypatch.setattr(program, "enable_compile_cache", lambda path: None)

    def go(cell, trace=False, control=False, seed=2**31 + 17,
           seconds=1.0):
        lines = []
        out = run.run_cell(cell, seed, seconds, trace, jax.devices()[:1],
                           root=tiny_root, control=control,
                           log=lines.append,
                           cache_dir=str(tmp_path / "cache"))
        return out, lines

    return go
