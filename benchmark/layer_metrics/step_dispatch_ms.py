"""step_dispatch_ms: milliseconds of host time ``Solver.step`` takes to
hand one compiled step to the runtime; the median over the steps of the
traced window.

layer: step; unit: ms; source: program_span (``sparknet.step.dispatch``,
round the call of the jitted step); moves: train_img_s.  Where the runtime
has a step queued already the call returns at once; a call that waits for
the device shows here.  Absent where the driver runs no ``Solver`` or the
program has no such span.
"""

from ..lib import program_spans


def read(cap) -> float | None:
    return program_spans.length_ms(program_spans.load(cap), "step.dispatch")
