"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers
the benchmark reports: device busy time inside the traced window,
device time per XLA module, the device operations that took most time,
and the longest idle gaps named by what the host was doing in them.

Planes named ``/device:<platform>:<n>`` are devices; on each, the line
``XLA Ops`` holds the operations (their union is the busy time) and the
line ``XLA Modules`` the whole programs. Host spans are the
``jax.profiler.TraceAnnotation`` names that the harness writes on the
``/host:CPU`` plane; the span ``WINDOW`` marks the traced window.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

#: the host span that marks the traced window
WINDOW = "bench.window"
#: host spans written by the harness start with this
PREFIX = "bench."

_DEVICE = re.compile(r"^/device:([A-Za-z]+):(\d+)$")
# an XLA module event is named "<module>(<program id>)"
_MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_name(event_name: str) -> str:
    return _MODULE_ID.sub("", event_name)


class Trace:
    """The events of one trace, split into device and host lines."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        self._load(ProfileData.from_file(path))

    def _load(self, pd) -> None:
        """Collect the events of the parsed trace ``pd``."""
        #: device plane → list of (name, start_ns, end_ns) per line kind
        self.ops: Dict[str, List[Tuple[str, int, int]]] = {}
        self.modules: Dict[str, List[Tuple[str, int, int]]] = {}
        #: harness host spans (name, start_ns, end_ns)
        self.spans: List[Tuple[str, int, int]] = []
        for plane in pd.planes:
            if _DEVICE.match(plane.name):
                for line in plane.lines:
                    evs = [(e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
                           for e in line.events]
                    if line.name == "XLA Ops":
                        self.ops[plane.name] = evs
                    elif line.name == "XLA Modules":
                        self.modules[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(PREFIX):
                            self.spans.append(
                                (e.name, int(e.start_ns),
                                 int(e.start_ns + e.duration_ns)))

    def window(self) -> Optional[Tuple[int, int]]:
        w = [(s, e) for n, s, e in self.spans if n == WINDOW]
        if not w:
            return None
        return min(s for s, _ in w), max(e for _, e in w)

    def reduce(self, top: int = 10) -> Optional[dict]:
        """``busy_s`` (union of device operations in the window,
        averaged over the devices that ran any), ``window_s``,
        ``module_s`` (device seconds per XLA module, summed over
        devices), ``device_ops`` and ``idle_gaps`` (each the ``top``
        longest, ``[name, seconds]``). None without a window or with
        no device operation in it."""
        win = self.window()
        if win is None:
            return None
        lo, hi = win
        busy: List[float] = []
        gaps: List[Tuple[int, int]] = []
        op_s: Dict[str, float] = defaultdict(float)
        for plane, evs in self.ops.items():
            iv = _union(_clip([(s, e) for _, s, e in evs], lo, hi))
            if not iv:
                continue
            busy.append(sum(e - s for s, e in iv) / 1e9)
            edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
            gaps.extend((edges[i], edges[i + 1])
                        for i in range(0, len(edges), 2)
                        if edges[i + 1] > edges[i])
            for name, s, e in evs:
                if e > lo and s < hi:
                    op_s[name] += (min(e, hi) - max(s, lo)) / 1e9
        if not busy:
            return None
        mod_s: Dict[str, float] = defaultdict(float)
        for evs in self.modules.values():
            for name, s, e in evs:
                if e > lo and s < hi:
                    mod_s[module_name(name)] += (min(e, hi)
                                                 - max(s, lo)) / 1e9
        gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
        return {
            "busy_s": sum(busy) / len(busy),
            "window_s": (hi - lo) / 1e9,
            "module_s": dict(mod_s),
            "device_ops": [[n, s] for n, s in sorted(
                op_s.items(), key=lambda kv: kv[1], reverse=True)[:top]],
            "idle_gaps": [[self.host_activity(s, e), (e - s) / 1e9]
                          for s, e in gaps[:top]],
        }

    def host_activity(self, lo: int, hi: int) -> str:
        """The innermost harness span that covers most of [lo, hi)."""
        best, best_key = "idle (no host span)", (0, 0)
        for name, s, e in self.spans:
            if name == WINDOW:
                continue
            cover = min(e, hi) - max(s, lo)
            # most cover first, then the shortest (innermost) span
            key = (cover, -(e - s))
            if cover > 0 and key > best_key:
                best, best_key = name, key
        return best
