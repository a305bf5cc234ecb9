"""moe_experts_roofline_seq: ``moe_experts_roofline`` for a net of any
sequence layer types: the least time the chip could take for the experts'
grouped products, the larger of their operations (at the rows an even
router sends the held experts, forward and the two backward products) at
the bf16 peak and of their bytes (each held expert's matrices and the
routed rows once a pass, ``lib/seq_flops.py``) at the memory's bandwidth,
over the device time of the operations in the ``moe_experts`` scope
(forward, recomputed forward and backward alike).  Read by scope, so the
yardstick is the same work whatever tiles the grouped products take at the
experts' width; on a TPU the operations there are JAX's ``megablox``
kernels (``gmm``, ``tgmm``).

layer: kernels; unit: %; source: device_trace; moves: train_img_s.  Absent
where no operation carries the scope.
"""

import jax.numpy as jnp

from ..lib import peaks, seq_flops
from ..lib import trace as tracelib

SCOPE = "moe_experts"


def read(cap) -> float | None:
    if not cap.trace.devices:
        return None
    ops = [o for o in tracelib.in_window(cap.trace, min(cap.trace.devices))
           if SCOPE in o.scope]
    if not ops:
        return None
    net = cap.driver.train_net_param()
    peak = peaks.peaks(cap.device["kind"])
    sequences = seq_flops.sequences_per_step(net)
    flops = seq_flops.train_flops_per_sequence(net)["experts"] * sequences
    moved = seq_flops.expert_bytes_per_sequence(
        net, jnp.dtype(cap.cell.mix["compute_dtype"]).itemsize) * sequences
    least_s = cap.traced.steps * max(flops / peak["flops_per_s"],
                                     moved / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (sum(o.dur_ps for o in ops) / 1e12)
