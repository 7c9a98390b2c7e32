"""Share of the HBM roofline reached by the replay's device work: the
string-table scan and the fused verdict step.

The work is counted from the problem, whatever arm implements it: per
replayed session, the unpadded bytes of its string table (each unique
field string, handed to the scan once), plus one read of every staged
transition table of the policy, plus the output lanes written. The
least time is that count ÷ the device's HBM bandwidth
(``benchmark/peaks.json``): a lower bound, so the share passes 100%
only where the trace misses part of the work. The device time is the
summed duration of the XLA modules below, inside the traced window.
"""

import re

from benchmark.peaks import peaks

#: XLA modules of the scan and the verdict step, by name in the trace.
#: The string-table scan is an anonymous jit that the trace names
#: ``jit__unknown`` (PR 22's chip trace); it gets a stable name when the
#: program's spans come (PERF.md, Open questions).
MODULES = re.compile(r"^jit_(verdict_step_capture|_unknown)$")
#: staged policy arrays that are a bank's automaton: transitions, byte
#: classes and accept sets
TABLES = re.compile(r"_(trans|byteclass|accept)$")


def work_bytes(ctx) -> int:
    tables = sum(v for k, v in ctx["policy_array_bytes"].items()
                 if TABLES.search(k))
    return sum(s["table_bytes"] + tables + s["out_bytes"]
               for s in ctx["segments"])


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("segments"):
        return None
    dev_s = sum(s for m, s in tr["module_s"].items() if MODULES.match(m))
    if dev_s <= 0:
        return None
    least_s = work_bytes(ctx) / peaks(ctx["device_kind"])["hbm_bytes_s"]
    return 100.0 * least_s / dev_s
