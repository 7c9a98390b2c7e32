"""Ring packs (``cilium_tpu_serve_pack_records`` observations; each pack
is one fused dispatch and one readback) ÷ chunks sent in the window."""


def read(ctx):
    packs = ctx["counters"].get("packs", 0)
    if packs <= 0 or not ctx.get("chunks"):
        return None
    return packs / ctx["chunks"]
