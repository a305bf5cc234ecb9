"""device_idle_share: 1 minus the union of the device's operation
intervals over the traced window, averaged over the chips used.

layer: device; unit: %; source: device_trace; moves: train_img_s;
cells: all.
"""

from ..lib import trace as tracelib


def read(cap) -> float | None:
    busy_s, window_s = tracelib.busy_seconds(cap.trace)
    return 100.0 * (1.0 - busy_s / window_s)
