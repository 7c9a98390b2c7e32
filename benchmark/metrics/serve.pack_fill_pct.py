"""Mean distinct streams per ring pack (``cilium_tpu_serve_pack_streams``
over the window's packs) ÷ the cell's connections: how much of the
offered concurrency each fused dispatch batches together."""


def read(ctx):
    c = ctx["counters"]
    if c.get("packs", 0) <= 0 or not ctx.get("connections"):
        return None
    return 100.0 * c["pack_streams"] / c["packs"] / ctx["connections"]
