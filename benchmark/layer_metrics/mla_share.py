"""mla_share: device time under the latent attention layers' ``L[<name>]``
scopes (the projections, the latent, rotary, the core, and their backward
pass and recomputation) over the time in all operations, first device,
traced window.

layer: graph; unit: %; source: device_trace; moves: train_img_s.  Absent
where the net has no ``LatentAttention`` layer or no operation carries its
scope.
"""

from ..lib import seq_flops
from ..lib import trace as tracelib

TYPE = "LatentAttention"


def read(cap) -> float | None:
    if not cap.trace.devices:
        return None
    names = set(seq_flops.layer_names(cap.driver.train_net_param(), TYPE))
    ops = tracelib.in_window(cap.trace, min(cap.trace.devices))
    share = tracelib.time_share(ops, lambda o: o.layer() in names)
    return 100.0 * share if share else None
