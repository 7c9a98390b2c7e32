"""p99 of the intervals between successive ``cilium_tpu.serve.cycle``
starts on the pack thread: the 2 ms pack interval plus the cycle when
the thread keeps its cadence, longer where it stalls. Program spans
(``benchmark/spans.py``)."""

from benchmark import spans


def read(ctx):
    s = spans.program_spans()
    return spans.cycle_gap_p99_ms(s) if s else None
