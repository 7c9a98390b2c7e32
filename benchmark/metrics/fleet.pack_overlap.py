"""How many replicas' pack cycles are open at once, on average while
any is: Σ of the wall-clock durations of the ``cilium_tpu.serve.cycle``
spans, over every pack thread, ÷ the length of their union. 1.0 means
the cycles never overlap in wall time (the replicas pack in series);
with four replicas, up to 4.0 when all four are open at once. A span
measures wall time, so a cycle that waits inside it for the
interpreter lock or another lock still counts as open: the reading
cannot tell packing in parallel from packing that such a lock
serialises. Empty cycles count too. Program spans
(``benchmark/spans.py``)."""

from benchmark import spans, trace


def read(ctx):
    cycles = [(s.start, s.end) for s in spans.program_spans() or ()
              if s.name == spans.CYCLE]
    union = sum(e - s for s, e in trace._union(cycles))
    if union <= 0:
        return None
    return sum(e - s for s, e in cycles) / union
