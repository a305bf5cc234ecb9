"""unscoped_share: device time of the operations that carry no ``L[...]``
scope at all (``Op.layer()`` is ``None``: what ``top_ops`` prints as ``-``)
over the time in all operations, first device, traced window.

layer: graph; unit: %; source: device_trace; moves: train_img_s.  The
program opens a scope round every layer (``graph/net.py``), every phase of
the step (``L[step.input]``, ``L[step.grads]``, ``L[step.update]``) and of
the round (``L[round.average]``, ``L[round.sync]``), so what is left here is
what the compiler made with no metadata of the program's.  Absent where the
trace holds no device operation.
"""

from ..lib import trace as tracelib


def read(cap) -> float | None:
    if not cap.trace.devices:
        return None
    ops = tracelib.in_window(cap.trace, min(cap.trace.devices))
    share = tracelib.time_share(ops, lambda o: o.layer() is None)
    return None if share is None else 100.0 * share
