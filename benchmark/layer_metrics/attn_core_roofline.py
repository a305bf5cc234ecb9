"""attn_core_roofline: the attention core's share of its roofline: the
least time the chip could take for the core's operations (scores and
weighted sum over the pairs the causal and window masks let through,
forward and the two backward products, ``lib/lm_flops.py``) at the bf16
peak, over the device time of the operations in the ``attn_core`` scope
(forward, recomputed forward and backward alike).  The bound that applies
is compute.  Read by scope, so the yardstick is the same work whether a
Pallas kernel or XLA does it; on a TPU the operations there are JAX's
``splash_attention`` kernels (``splash_mqa_fwd_residuals``,
``splash_mqa_dq_no_residuals``, ``splash_mqa_dkv_no_residuals`` in the
trace).

layer: kernels; unit: %; source: device_trace; moves: train_img_s.  Absent
where no operation carries the scope.
"""

from ..lib import lm_flops, peaks
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
    per_step = (lm_flops.train_flops_per_sequence(net)["core"]
                * lm_flops.sequences_per_step(net))
    least_s = per_step * cap.traced.steps / peaks.peaks(
        cap.device["kind"])["flops_per_s"]
    return 100.0 * least_s / (sum(o.dur_ps for o in ops) / 1e12)
