"""99th percentile of (actual send − scheduled send) over the window's
chunks: how late the benchmark's own load generator ran."""

import numpy as np


def read(ctx):
    late = ctx.get("late_s")
    if not late:
        return None
    return float(np.percentile(np.asarray(late, dtype=float), 99)) * 1e3
