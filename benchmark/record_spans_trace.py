"""Records the small chip trace that ``tests/benchmark`` checks
``benchmark/spans.py`` against (run once, on the chip):

    python3 -m benchmark.record_spans_trace <out.xplane.pb>

Inside the harness's window span, on two threads, through the
program's own span primitive (``cilium_tpu.runtime.tracing``):

1. a device step under ``capture.verdict`` whose readback is the
   ``.wait`` span ``capture.readback.wait``; one between each phase
   below, so each phase is an idle gap of its own;
2. 30 ms with no span at all;
3. 40 ms under the harness span ``bench.host``;
4. 50 ms under a lone ``serve.ticket.wait``;
5. 60 ms in which a second thread runs one ``serve.cycle`` while this
   one waits in ``serve.ticket.wait`` under ``bench.host``;
6. four chunks (``stream.chunk``: 5 ms of host work, then a 10 ms
   ``serve.ticket.wait``) while the second thread cycles
   (``serve.cycle`` of 3 ms holding a ``serve.readback.wait``, then a
   2 ms ``serve.sleep.wait``).
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import threading
import time

from benchmark import spans, trace


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    from cilium_tpu.runtime.tracing import host_sync, span

    out = (argv or sys.argv[1:])[0]
    step = jax.jit(lambda x: jnp.tanh(x @ x).sum(axis=0))
    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()

    def device():
        with span("capture.verdict"):
            y = step(x)
            with host_sync("capture.readback"):
                y.block_until_ready()

    def long_cycle():
        with span("serve.cycle"):
            time.sleep(0.06)

    def cycles(stop: threading.Event):
        while not stop.is_set():
            with span("serve.cycle"):
                time.sleep(0.003)
                with host_sync("serve.readback"):
                    pass
            with span("serve.sleep.wait"):
                time.sleep(0.002)

    d = tempfile.mkdtemp(prefix="trace_")
    try:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            device()
            time.sleep(0.03)
            device()
            with jax.profiler.TraceAnnotation(f"{trace.PREFIX}host"):
                time.sleep(0.04)
            device()
            with span("serve.ticket.wait"):
                time.sleep(0.05)
            device()
            other = threading.Thread(target=long_cycle)
            with jax.profiler.TraceAnnotation(f"{trace.PREFIX}host"):
                with span("serve.ticket.wait"):
                    other.start()
                    other.join()
            device()
            stop = threading.Event()
            other = threading.Thread(target=cycles, args=(stop,))
            other.start()
            for _ in range(4):
                with span("stream.chunk"):
                    time.sleep(0.005)
                    with span("serve.ticket.wait"):
                        time.sleep(0.01)
            stop.set()
            other.join()
            device()
        jax.profiler.stop_trace()
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        shutil.copy(trace.find_xplane(d), out)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return spans.main([out])


if __name__ == "__main__":
    sys.exit(main())
