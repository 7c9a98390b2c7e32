"""99th percentile over every chunk of the window, scheduled send to
verdicts received. A per-layer reading, not an end-to-end metric: the
server stalls on the host for ~150 ms a few times a minute, and how
many stalls fall in one 51 s window swings it from ~44 to ~125 ms
(PERF.md §2)."""

import numpy as np


def read(ctx):
    lat = ctx.get("latency_s")
    if not lat:
        return None
    return float(np.percentile(np.asarray(lat, dtype=float), 99)) * 1e3
