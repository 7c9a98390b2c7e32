"""Share of the replay window the replay thread spent blocked on the
device: Σ ``cilium_tpu.capture.*.wait`` spans (the verdict readback,
which waits out the string-table scan and the fused step) ÷ the
window. Program spans (``benchmark/spans.py``)."""

from benchmark import spans


def read(ctx):
    s = spans.program_spans()
    return spans.device_wait_pct(s, ctx["window_s"]) if s else None
