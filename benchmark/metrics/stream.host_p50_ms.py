"""p50 over the window's chunks of the stream worker's host time: each
``cilium_tpu.stream.chunk`` span less the ``serve.ticket.wait`` inside
it (frame parse, lease, submit: session-lock wait and id encode).
Program spans (``benchmark/spans.py``)."""

from benchmark import spans


def read(ctx):
    s = spans.program_spans()
    return spans.host_p50_ms(s) if s else None
