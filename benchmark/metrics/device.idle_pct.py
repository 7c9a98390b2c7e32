"""1 − (union of device-operation intervals ÷ traced window), from the
profiler trace (``benchmark/trace.py``). One reader for
``device.idle_pct.<split>``: the contract splits a metric whose cells
report different end-to-end metrics (``.replay``, ``.served``)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr.get("window_s", 0) <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
