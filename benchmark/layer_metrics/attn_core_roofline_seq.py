"""attn_core_roofline_seq: ``attn_core_roofline`` for a net of any sequence
layer types: the least time the chip could take for the attention core's
operations (scores and weighted sum over the pairs the causal and window
masks let through, at the head size the model has whatever the lowering
pads it to, forward and the two backward products, ``lib/seq_flops.py``)
at the bf16 peak, over the device time of the operations in the
``attn_core`` scope (forward, recomputed forward and backward alike).  The
bound that applies is compute.  Read by scope, so the yardstick is the
same work whether a Pallas kernel or XLA does it; a head of 64 fills half
of the matrix unit's 128 columns, so a kernel that pads it to 128 reads at
most 50 here.

layer: kernels; unit: %; source: device_trace; moves: train_img_s.  Absent
where no operation carries the scope.
"""

from ..lib import peaks, seq_flops
from ..lib import trace as tracelib

SCOPE = "attn_core"


def read(cap) -> float | None:
    if not cap.trace.devices:
        return None
    ops = [o for o in tracelib.in_window(cap.trace, min(cap.trace.devices))
           if SCOPE in o.scope]
    if not ops:
        return None
    net = cap.driver.train_net_param()
    per_step = (seq_flops.train_flops_per_sequence(net)["core"]
                * seq_flops.sequences_per_step(net))
    least_s = per_step * cap.traced.steps / peaks.peaks(
        cap.device["kind"])["flops_per_s"]
    return 100.0 * least_s / (sum(o.dur_ps for o in ops) / 1e12)
