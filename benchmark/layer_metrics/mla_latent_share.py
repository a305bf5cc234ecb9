"""mla_latent_share: device time of the operations in the ``mla_latent``
sub-scope of the latent attention layers (the latent's down-projection and
its norm, the rotary key's projection and turn, the up-projection to the
heads' keys and values, and the keys' assembly; forward, recomputed and
backward) over the time in all operations, first device, traced window.

layer: graph; unit: %; source: device_trace; moves: train_img_s.  Absent
where no operation carries the scope.
"""

from ..lib import trace as tracelib

SCOPE = "mla_latent"


def read(cap) -> float | None:
    if not cap.trace.devices:
        return None
    ops = tracelib.in_window(cap.trace, min(cap.trace.devices))
    share = tracelib.time_share(ops, lambda o: SCOPE in o.scope)
    return 100.0 * share if share else None
