"""update_share: device time of the gradient preparation and the update
(clipping's norm and scale, the division by ``iter_size``, the regulariser;
the learning rate and the solver rule over every parameter) over the time
in all operations, first device, traced window.

layer: step; unit: %; source: device_trace (operations whose innermost
``L[...]`` scope is ``L[step.grads]`` or ``L[step.update]``, which
``solvers/step.apply_update`` opens round ``preprocess_grads`` and round the
rate and the rule);
moves: train_img_s.  An update the compiler fuses into the product that
makes its gradient (an output fusion named after the layer's ``dot_general``
or convolution) is that layer's and is not counted.  Absent where no
operation carries either scope.
"""

from ..lib import trace as tracelib

SCOPES = ("step.grads", "step.update")


def read(cap) -> float | None:
    if not cap.trace.devices:
        return None
    ops = tracelib.in_window(cap.trace, min(cap.trace.devices))
    share = tracelib.time_share(ops, lambda o: o.layer() in SCOPES)
    return 100.0 * share if share else None
