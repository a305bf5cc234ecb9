"""feed_stack_ms: milliseconds of one host batch's assembly spent in
``np.stack`` over its records, on the thread that also submits and collects
the reads; the median over the batches of the traced window.

layer: feed; unit: ms; source: program_span (``sparknet.feed.stack``, a
child of ``sparknet.feed.assemble``); moves: train_fed_img_s.  Absent
where no record feed ran or the program has no such span.
"""

from ..lib import program_spans


def read(cap) -> float | None:
    return program_spans.length_ms(program_spans.load(cap), "feed.stack")
