"""feed_assemble_ms: milliseconds ``records_feed`` takes to make one host
batch on its one thread: the reads submitted, every one waited for, the
records stacked; the median over the batches of the traced window.

layer: feed; unit: ms; source: program_span (``sparknet.feed.assemble``);
moves: train_fed_img_s.  Where it equals the batch over the cell's rate,
that thread is the feed's rate.  Absent where no record feed ran or the
program has no such span.
"""

from ..lib import program_spans


def read(cap) -> float | None:
    return program_spans.length_ms(program_spans.load(cap), "feed.assemble")
