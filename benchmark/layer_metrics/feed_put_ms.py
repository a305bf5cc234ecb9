"""feed_put_ms: milliseconds a staging thread spends on one batch's
``device_put``, settled on that thread.

layer: feed; unit: ms; source: program_counter
(``FeedStats.per_batch()["device_put_s"]`` of the ``DeviceFeed``; the
record stage's ``read_s`` and ``decode_s`` are on the run's ``counters``
line); moves: train_img_s in the fed cells.  Absent where the mix has no
feed.
"""


def read(cap) -> float | None:
    stats = cap.counters.get("feed_device")
    if not stats:
        return None
    return 1000.0 * stats["per_batch"]["device_put_s"]
