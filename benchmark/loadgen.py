"""The served cell's load generator, in a process of its own (as a proxy
is), so that its threads do not take the interpreter lock from the
server's. It speaks to the server only through ``StreamClient``
connections on the server's socket; it never touches a device.

Protocol on the pipe: the child connects and warms every connection
with every image, sends ``"warm"``; the parent sends the plan
``(offsets, picks)``; the child sends chunk ``j`` on connection
``j % connections`` at ``base + offsets[j]`` (its own clock,
``time.perf_counter``, the system's monotonic clock), finishes every
connection (which waits for every answer) and sends back one dict:
``base``, ``late`` (send − due, s, per chunk), ``got`` (chunk →
``(verdicts, completion time)``) and ``errors``.
"""

from __future__ import annotations

import os
import sys
import threading
import time


def main(conn, sock_path: str, images, widths, connections: int,
         pipeline_depth: int, timeout: float) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"  # the chip is the server's
    import numpy as np

    from benchmark.program import Program

    clients = [Program.stream_client(sock_path, widths, timeout,
                                     pipeline_depth)
               for _ in range(connections)]
    try:
        for cl in clients:
            seqs = [cl.send_image(img) for img in images]
            for s in seqs:
                cl.result(s)
        conn.send("warm")
        offsets, picks = conn.recv()
        sched = {}
        got = {}
        errors = [0]
        lock = threading.Lock()

        def collect(c: int, cl) -> None:
            try:
                for seq, verdicts in cl.results():
                    now = time.perf_counter()
                    with lock:
                        j = sched.get((c, seq))
                        if isinstance(verdicts, Exception) or j is None:
                            errors[0] += 1
                        else:
                            got[j] = (np.asarray(verdicts), now)
            except Exception as e:  # noqa: BLE001 — counted as missing
                print(f"loadgen collector {c}: {e!r}", file=sys.stderr)

        threads = [threading.Thread(target=collect, args=(c, cl),
                                    daemon=True)
                   for c, cl in enumerate(clients)]
        for t in threads:
            t.start()
        late = []
        base = time.perf_counter() + 0.05
        for j, off in enumerate(offsets):
            due = base + off
            now = time.perf_counter()
            if due > now:
                time.sleep(due - now)
            c = j % connections
            with lock:
                seq = clients[c].send_image(images[picks[j]])
                sched[(c, seq)] = j
            late.append(time.perf_counter() - due)
        for cl in clients:
            cl.finish()
        for t in threads:
            t.join(timeout=timeout)
        conn.send({"base": base, "late": late, "got": got,
                   "errors": errors[0]})
    finally:
        for cl in clients:
            cl.close()
        conn.close()
