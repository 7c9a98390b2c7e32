"""The program's own host spans, as the per-layer metrics read them.

The program writes ``cilium_tpu.<layer>.<what>`` spans on the
profiler's host lines (``cilium_tpu/runtime/tracing.py``; the catalog
is in ``docs/OBSERVABILITY.md``). They come two ways, as ``Span``
tuples ``(name, start_ns, end_ns, line)``:

* ``program_spans()``: the program's log of the spans it closed while
  the profiler was on, read in the run's own process after the window
  (a ``--trace 1`` run's readers: the trace file is gone by then). The
  line is the thread. A program without the log gives None.
* ``SpanTrace(path)``: the same spans read back from a ``.xplane.pb``.
  Every Python thread's host line is named ``python``, so the line is
  ``(plane, index)``; the span names say whose work it is.

``SpanTrace`` also names each idle gap of the device by what the host
was doing for most of it, in this order: the program's working spans,
then its ``.wait`` spans (it blocked on a lock, a readback or its own
sleep), then the harness's ``bench.*`` spans, then ``idle (no host
span)``. A name counts in its tier when its spans (their union, over
all lines) cover at least half of the gap: the span that ran the step
before a gap, or launches the one after it, overlaps the gap's edges
on every trace and would otherwise name them all. Within a tier the
most cover wins, then the shorter spans. Device busy time, the window,
module and operation times are ``benchmark/trace.py``'s, unchanged.

    python3 -m benchmark.spans <trace.xplane.pb>

prints the reduction with the gaps so named, the coverage of the
window by each host line's program spans, and the five metrics below.
"""

from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence

import numpy as np

from benchmark import trace

#: program span names start with this
PREFIX = "cilium_tpu."
#: a span that blocks ends with this
WAIT = ".wait"
IDLE = "idle (no host span)"

CHUNK = PREFIX + "stream.chunk"
TICKET_WAIT = PREFIX + "serve.ticket.wait"
CYCLE = PREFIX + "serve.cycle"
SERVE_SYNC = PREFIX + "serve.readback.wait"
#: the replay thread's waits on the device all start so and end in WAIT
CAPTURE = PREFIX + "capture."


class Span(NamedTuple):
    name: str
    start: int
    end: int
    line: object


def program_spans() -> Optional[List[Span]]:
    """The spans the program closed while the profiler was on, or None
    when the program keeps no such log or it is empty."""
    try:
        from cilium_tpu.runtime.tracing import profiled_spans
    except ImportError:
        return None
    spans = [Span(*s) for s in profiled_spans()]
    return spans or None


def _named(spans: Iterable[Span], name: str) -> List[Span]:
    return [s for s in spans if s.name == name]


def _pct(values: Sequence[float], q: float) -> Optional[float]:
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, dtype=float), q))


def inner_ns(spans: Sequence[Span], outer: str, inner: str
             ) -> List[int]:
    """For each ``outer`` span, the nanoseconds of the ``inner`` spans
    on its line that lie inside it."""
    by_line: Dict[object, List[Span]] = defaultdict(list)
    for s in spans:
        if s.name == inner:
            by_line[s.line].append(s)
    for v in by_line.values():
        v.sort()
    starts = {k: [s.start for s in v] for k, v in by_line.items()}
    out = []
    for o in _named(spans, outer):
        ins = by_line.get(o.line, [])
        i = bisect.bisect_left(starts.get(o.line, []), o.start)
        t = 0
        while i < len(ins) and ins[i].start < o.end:
            t += min(ins[i].end, o.end) - ins[i].start
            i += 1
        out.append(t)
    return out


def host_p50_ms(spans: Sequence[Span]) -> Optional[float]:
    """p50 over chunks of ``stream.chunk`` less its
    ``serve.ticket.wait``: the worker's host time per chunk."""
    chunks = _named(spans, CHUNK)
    waits = inner_ns(spans, CHUNK, TICKET_WAIT)
    return _pct([(c.end - c.start - w) / 1e6
                 for c, w in zip(chunks, waits)], 50)


def ring_wait_p50_ms(spans: Sequence[Span]) -> Optional[float]:
    """p50 of ``serve.ticket.wait``: submit to verdicts on the host."""
    return _pct([(s.end - s.start) / 1e6
                 for s in _named(spans, TICKET_WAIT)], 50)


def cycle_gap_p99_ms(spans: Sequence[Span]) -> Optional[float]:
    """p99 of the intervals between successive ``serve.cycle`` starts
    on one line (the pack thread)."""
    by_line: Dict[object, List[int]] = defaultdict(list)
    for s in _named(spans, CYCLE):
        by_line[s.line].append(s.start)
    gaps: List[float] = []
    for starts in by_line.values():
        starts.sort()
        gaps.extend((b - a) / 1e6 for a, b in zip(starts, starts[1:]))
    return _pct(gaps, 99)


def host_syncs_per_chunk(spans: Sequence[Span]) -> Optional[float]:
    """Blocking device→host reads on the served path (each one a
    ``serve.readback.wait`` span and one count of
    ``cilium_tpu_engine_host_syncs_total{site="serve.readback"}``)
    ÷ ``stream.chunk`` spans."""
    chunks = len(_named(spans, CHUNK))
    if not chunks:
        return None
    return len(_named(spans, SERVE_SYNC)) / chunks


def device_wait_pct(spans: Sequence[Span], window_s: float
                    ) -> Optional[float]:
    """Σ ``cilium_tpu.capture.*.wait`` ÷ the window: the share of the
    replay window the replay thread spent blocked on the device."""
    waits = [s for s in spans
             if s.name.startswith(CAPTURE) and s.name.endswith(WAIT)]
    if not waits or window_s <= 0:
        return None
    return 100.0 * sum(s.end - s.start for s in waits) / 1e9 / window_s


def coverage(spans: Sequence[Span], lo: int, hi: int
             ) -> Dict[object, float]:
    """Share of [lo, hi) that each line's spans cover (their union)."""
    by_line: Dict[object, list] = defaultdict(list)
    for s in spans:
        by_line[s.line].append((s.start, s.end))
    return {k: sum(e - s for s, e in trace._clip(trace._union(v), lo, hi))
            / max(1, hi - lo) for k, v in by_line.items()}


def _best(spans: Iterable[Span], lo: int, hi: int) -> Optional[str]:
    """The name whose spans cover the most of [lo, hi) (their union),
    if that is at least half of it; ties go to the shorter spans."""
    clipped: Dict[str, list] = defaultdict(list)
    length: Dict[str, int] = defaultdict(int)
    for s in spans:
        if s.end > lo and s.start < hi:
            clipped[s.name].append((max(s.start, lo), min(s.end, hi)))
            length[s.name] += s.end - s.start
    best, best_key = None, None
    for name, iv in clipped.items():
        cover = sum(e - s for s, e in trace._union(iv))
        key = (cover, -length[name])
        if 2 * cover >= hi - lo and (best_key is None or key > best_key):
            best, best_key = name, key
    return best


class SpanTrace(trace.Trace):
    """``trace.Trace`` plus the program's spans, keyed by host line,
    and idle gaps named by the tiers above."""

    def __init__(self, path: str):
        super().__init__(path)
        from jax.profiler import ProfileData

        #: program spans on the host lines, by start
        self.program: List[Span] = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        self.program.append(Span(
                            e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns),
                            (plane.name, k)))
        self.program.sort(key=lambda s: (s.start, s.end))
        harness = [Span(n, s, e, None) for n, s, e in self.spans
                   if n != trace.WINDOW]
        self._tiers = (
            [s for s in self.program if not s.name.endswith(WAIT)],
            [s for s in self.program if s.name.endswith(WAIT)],
            harness)

    def host_activity(self, lo: int, hi: int) -> str:
        for tier in self._tiers:
            name = _best(tier, lo, hi)
            if name is not None:
                return name
        return IDLE

    def metrics(self) -> Dict[str, Optional[float]]:
        """The five span metrics over this trace's window."""
        win = self.window()
        s = self.program
        if win is not None:
            s = [x for x in s if x.end > win[0] and x.start < win[1]]
        window_s = (win[1] - win[0]) / 1e9 if win else 0.0
        return {"stream.host_p50_ms": host_p50_ms(s),
                "serve.ring_wait_p50_ms": ring_wait_p50_ms(s),
                "serve.cycle_gap_p99_ms": cycle_gap_p99_ms(s),
                "serve.host_syncs_per_chunk": host_syncs_per_chunk(s),
                "replay.device_wait_pct": device_wait_pct(s, window_s)}


def main(argv: Optional[List[str]] = None) -> int:
    path = (argv if argv is not None else sys.argv[1:])[0]
    t = SpanTrace(path)
    out = {"reduction": t.reduce(), "metrics": t.metrics()}
    win = t.window()
    if win is not None:
        out["coverage"] = {f"{k[0]}#{k[1]}": v for k, v in
                           coverage(t.program, *win).items()}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
