"""feed_wait_share: share of the window in which ``Solver.step`` was
blocked in ``next()`` on the feed.

layer: feed; unit: %; source: program_span (the benchmark's own wrapper
around the iterator handed to ``set_train_data``; ``FeedStats`` records the
producers' time and not the consumer's wait); moves: train_img_s in the
fed cells.  Absent where the mix has no feed.
"""


def read(cap) -> float | None:
    if not cap.counters.get("feed_device"):
        return None
    w = cap.window
    waited = cap.cell.spans.seconds("next_batch", w.t0, w.t0 + w.seconds)
    return 100.0 * waited / w.seconds
