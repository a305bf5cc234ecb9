"""recompute_share: device time of the forward operations that run again in
the backward pass (``jax.checkpoint``) over the time in all operations,
first device, traced window.  ``lib/trace.top_ops`` counts them as ``bwd``;
the model's operations (``mfu_lm``, ``mfu_seq``) do not count them at all.

layer: graph; unit: %; source: device_trace (operations with JAX's own
``rematted_computation`` in their scope path, under the ``transpose(jvp(
L[<layer>]))`` of the layer that recomputes); moves: train_img_s.  Absent
where no operation carries the name.
"""

from ..lib import trace as tracelib

SCOPE = "rematted_computation"


def read(cap) -> float | None:
    if not cap.trace.devices:
        return None
    ops = tracelib.in_window(cap.trace, min(cap.trace.devices))
    share = tracelib.time_share(ops, lambda o: SCOPE in o.scope)
    return 100.0 * share if share else None
