"""Records the small chip trace that ``tests/benchmark`` checks
``benchmark/trace.py`` against (run once, on the chip):

    python3 -m benchmark.record_trace <out.xplane.pb>

Three jitted steps inside the harness's window span, with a host span
each and idle host time between them.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

from benchmark import trace


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    out = (argv or sys.argv[1:])[0]
    step = jax.jit(lambda x: jnp.tanh(x @ x).sum(axis=0))
    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    d = tempfile.mkdtemp(prefix="trace_")
    try:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            for i in range(3):
                with jax.profiler.TraceAnnotation(f"{trace.PREFIX}step"):
                    step(x).block_until_ready()
                with jax.profiler.TraceAnnotation(f"{trace.PREFIX}host"):
                    time.sleep(0.01)
        jax.profiler.stop_trace()
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        shutil.copy(trace.find_xplane(d), out)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    print(trace.Trace(out).reduce())
    return 0


if __name__ == "__main__":
    sys.exit(main())
