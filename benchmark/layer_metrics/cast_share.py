"""cast_share: device time of the casts between the stored and the compute
dtype that stand alone (a fusion rooted in a cast, and the layout copies
the compiler derives from one) over the time in all operations, first
device, traced window.  A convert fused into a convolution is the
convolution's and is not counted.

layer: graph; unit: %; source: device_trace (operations with the sub-scope
``cast`` in their scope path, which ``graph/net.py`` opens inside each
layer's ``L[...]``: ``L[conv1]/cast`` forward,
``transpose(jvp(L[conv1]))/cast`` backward); moves: train_img_s.  Absent
where no operation carries the sub-scope.
"""

from ..lib import trace as tracelib

SCOPE = "/cast/"


def is_cast(op) -> bool:
    return SCOPE in op.scope + "/"


def read(cap) -> float | None:
    if not cap.trace.devices:
        return None
    ops = tracelib.in_window(cap.trace, min(cap.trace.devices))
    share = tracelib.time_share(ops, is_cast)
    return 100.0 * share if share else None
