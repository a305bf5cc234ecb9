"""moe_experts_roofline_ops: ``moe_experts_roofline_seq`` with its counts
from the operations module the configuration names (``cap.driver.ops``,
``modules.operations``), so that a token configuration whose layer types
``seq_flops`` does not know is read by the same yardstick: the larger of
the grouped products' operations (at the rows an even router sends the
held experts, forward and the two backward products) at the bf16 peak and
of their bytes (each held expert's matrices and the routed rows once a
pass) at the memory's bandwidth, over the device time of the operations in
the ``moe_experts`` scope (forward, recomputed forward and backward).

layer: kernels; unit: %; source: device_trace; moves: train_img_s.  Absent
where no operation carries the scope.
"""

import jax.numpy as jnp

from ..lib import peaks
from ..lib import trace as tracelib

SCOPE = "moe_experts"


def read(cap) -> float | None:
    if not cap.trace.devices:
        return None
    ops = [o for o in tracelib.in_window(cap.trace, min(cap.trace.devices))
           if SCOPE in o.scope]
    if not ops:
        return None
    net, count = cap.driver.train_net_param(), cap.driver.ops
    peak = peaks.peaks(cap.device["kind"])
    sequences = count.sequences_per_step(net)
    flops = count.train_flops_per_sequence(net)["experts"] * sequences
    moved = count.expert_bytes_per_sequence(
        net, jnp.dtype(cap.cell.mix["compute_dtype"]).itemsize) * sequences
    least_s = cap.traced.steps * max(flops / peak["flops_per_s"],
                                     moved / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (sum(o.dur_ps for o in ops) / 1e12)
