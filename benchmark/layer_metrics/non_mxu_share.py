"""non_mxu_share: device time in operations that are neither a
convolution nor a dot (LRN kernels, reduce-window, select-and-scatter,
loop fusions, copies) over the time in all operations, first device,
traced window.

layer: graph; unit: %; source: device_trace; moves: train_img_s.  By HLO
category and not by ``L[<layer>]`` scope: a fused chain's scope holds its
convolution too.
"""

from ..lib import trace as tracelib


def read(cap) -> float | None:
    ops = tracelib.in_window(cap.trace, min(cap.trace.devices))
    share = tracelib.time_share(ops, tracelib.is_mxu)
    return None if share is None else 100.0 * (1.0 - share)
