"""The largest replica's share of the window's routed chunks over the
mean share: the largest per-host delta of
``cilium_tpu_fleet_routed_chunks_total{host}`` ÷ their mean. 1.0 is an
even spread; with four replicas 4.0 is one replica taking every chunk.
Program counters, read by the fleet kind at each edge of the window."""


def read(ctx):
    routed = list((ctx.get("routed") or {}).values())
    if not routed or sum(routed) <= 0:
        return None
    return max(routed) * len(routed) / sum(routed)
