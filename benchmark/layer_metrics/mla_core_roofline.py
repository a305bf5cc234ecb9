"""mla_core_roofline: the least time the chip could take for the latent
attention core's operations (scores at the query/key head of ``nope +
rope``, 192 in DeepSeek-V2, and the weighted sum at the value head, 128,
over the pairs the causal mask lets through, forward and the two backward
products: the ``core`` count of the operations module the configuration
names, ``cap.driver.ops``) at the bf16 peak, over the device time of the
operations in the ``attn_core`` scope (forward, recomputed forward and
backward alike).  The bound that applies is compute.  Read by scope, so the
yardstick is the same work whether a Pallas kernel or XLA does it; a head
of 192 fills one and a half of the matrix unit's 128 columns in the scores,
so a kernel that pads it to 256 reads at most 80 here.

layer: kernels; unit: %; source: device_trace; moves: train_img_s.  Absent
where no operation carries the scope.
"""

from ..lib import peaks
from ..lib import trace as tracelib

SCOPE = "attn_core"


def read(cap) -> float | None:
    if not cap.trace.devices:
        return None
    ops = [o for o in tracelib.in_window(cap.trace, min(cap.trace.devices))
           if SCOPE in o.scope]
    if not ops:
        return None
    net, count = cap.driver.train_net_param(), cap.driver.ops
    per_step = (count.train_flops_per_sequence(net)["core"]
                * count.sequences_per_step(net))
    least_s = per_step * cap.traced.steps / peaks.peaks(
        cap.device["kind"])["flops_per_s"]
    return 100.0 * least_s / (sum(o.dur_ps for o in ops) / 1e12)
