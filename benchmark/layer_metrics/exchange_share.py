"""exchange_share: device time in collective operations (all-reduce,
reduce-scatter, all-gather) over the time in all operations, on the first
device, inside the traced window.

layer: exchange; unit: %; source: device_trace; moves: train_img_s in the
cells on four chips.  Absent where no collective ran.
"""

from ..lib import trace as tracelib


def read(cap) -> float | None:
    ops = tracelib.in_window(cap.trace, min(cap.trace.devices))
    share = tracelib.time_share(ops, tracelib.is_collective)
    return 100.0 * share if share else None
