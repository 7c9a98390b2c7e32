"""Drivers of the timed window, one module per traffic ``kind``
(``benchmark/kinds/<kind>.py``, named by the traffic file). Each has
``run(env) -> dict`` with the keys ``e2e`` (end-to-end metric values),
``attempted``, ``failed``, ``checks`` (``compare.checks``),
``memory_peak_bytes`` and ``ctx`` (what the per-layer readers of
``benchmark/metrics/`` read). Each also declares ``TINY``, the traffic
keys the CPU rehearsal (``tests/benchmark/``) cuts to a size the CPU
runs in a second or two.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import time
from typing import Optional

from benchmark import spans as _spans
from benchmark import trace as _trace


def span(name: str):
    """A host span in the profiler's trace (free when none is on)."""
    import jax

    return jax.profiler.TraceAnnotation(f"{_trace.PREFIX}{name}")


def memory_peak(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None


@contextlib.contextmanager
def traced(env):
    """The profiler on around the window when ``env.trace``; yields a
    dict that holds the reduction once the block has left."""
    import jax

    result: dict = {}
    if not env.trace:
        yield result
        return
    shutil.rmtree(env.trace_dir, ignore_errors=True)
    os.makedirs(env.trace_dir, exist_ok=True)
    # host spans and device events; no Python call tracing, which
    # slows the served path past its knee (PR 22)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(env.trace_dir, profiler_options=opts)
    try:
        with span("window"):
            yield result
    finally:
        jax.profiler.stop_trace()
    t0 = time.perf_counter()
    reduced = _spans.reduce_dir(env.trace_dir)
    if reduced is not None:
        result.update(reduced, reduce_s=time.perf_counter() - t0)
    shutil.rmtree(env.trace_dir, ignore_errors=True)
