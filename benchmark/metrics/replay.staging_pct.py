"""Share of the replay window spent in ``CaptureReplay`` staging: the
sum over phases of ``cilium_tpu_capture_stage_seconds{phase}`` (tables:
featurizer build and the device string-table scan; featurize; dedup;
table-h2d; memo-fill) across the window, ÷ the window. Host clock."""


def read(ctx):
    stage = sum(v for k, v in ctx["counters"].items()
                if k.startswith("stage_s."))
    if not ctx.get("segments") or stage <= 0:
        return None
    return 100.0 * stage / ctx["window_s"]
