"""p50 of ``cilium_tpu.serve.ticket.wait`` over the window's chunks:
the worker waiting on the pack thread, from its submit to the chunk's
verdicts on the host (pack, fused dispatch, readback). Program spans
(``benchmark/spans.py``)."""

from benchmark import spans


def read(ctx):
    s = spans.program_spans()
    return spans.ring_wait_p50_ms(s) if s else None
