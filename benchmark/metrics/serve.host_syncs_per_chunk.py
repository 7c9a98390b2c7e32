"""Blocking device-to-host reads of the served path per chunk: the
window's ``cilium_tpu.serve.readback.wait`` spans, each made by the call
that counts ``cilium_tpu_engine_host_syncs_total{site="serve.readback"}``,
÷ its ``cilium_tpu.stream.chunk`` spans. Program spans
(``benchmark/spans.py``)."""

from benchmark import spans


def read(ctx):
    s = spans.program_spans()
    return spans.host_syncs_per_chunk(s) if s else None
