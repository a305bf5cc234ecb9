"""round_gap_ms: milliseconds the first device spends between two rounds:
from the end of one execution of the compiled round to the start of the
next inside the traced window (the loss fetch, the trainer's bookkeeping,
the small programs it runs in between, the dispatch); the median over the
hand-overs.

layer: round; unit: ms; source: device_trace; moves: train_img_s in the
round cells.  The trainer's own ``stall_s`` counts the host's seconds in a
fetch, which at ``harvest_lag`` 0 is the round's length and moves only
with the rate; it stays on the ``counters`` line.  Absent where the driver
runs no rounds or the traced window held one.
"""

import statistics

from ..lib import trace as tracelib


def read(cap) -> float | None:
    if not any(s.name == tracelib.SPAN_PREFIX + "train_round"
               for s in cap.trace.spans):
        return None
    gaps = tracelib.program_gaps(cap.trace, min(cap.trace.devices))
    return 1000.0 * statistics.median(gaps) if gaps else None
