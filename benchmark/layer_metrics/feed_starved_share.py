"""feed_starved_share: share of the traced window in which the consumer
of the ``DeviceFeed`` was blocked for a staged batch.

layer: feed; unit: %; source: program_span (``sparknet.feed.wait``, the
program's own span in ``DeviceFeed.__next__``; the same seconds are
``FeedStats``'s ``wait_s``); moves: train_fed_img_s.  ``feed_wait_share``
times the same wait from outside, over the untraced window.  Absent where
the mix has no feed or the program no such span.
"""

from ..lib import program_spans


def read(cap) -> float | None:
    spans = program_spans.load(cap)
    if not any(s.name == "feed.wait" for s in spans):
        return None
    lo, hi = cap.trace.window()
    return 100.0 * program_spans.seconds(spans, "feed.wait") / (
        (hi - lo) / 1e12)
