"""augment_share: device time in the on-device augmentation (crop, mirror,
cast, mean) over the time in all operations, first device, traced window.

layer: graph; unit: %; source: device_trace (operations whose innermost
``L[...]`` scope is ``L[augment]``, the scope ``ops/augment.augment_batch``
and ``device_crop_mirror_mean`` open); moves: train_img_s.  Absent where
no operation carries the scope.
"""

from ..lib import trace as tracelib


def read(cap) -> float | None:
    ops = tracelib.in_window(cap.trace, min(cap.trace.devices))
    share = tracelib.time_share(ops, lambda o: o.layer() == "augment")
    return 100.0 * share if share else None
