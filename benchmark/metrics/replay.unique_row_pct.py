"""Unique rows ÷ records over the window's replay sessions
(``CaptureReplay.n_unique`` after ``stage_unique``): 100% means the row
dedup and the verdict memo have nothing to serve."""


def read(ctx):
    segs = ctx.get("segments") or []
    records = sum(s["records"] for s in segs)
    if not records:
        return None
    return 100.0 * sum(s["unique_rows"] for s in segs) / records
