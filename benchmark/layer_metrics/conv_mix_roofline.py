"""conv_mix_roofline: the share of its roofline that the part of the gated
short convolution between its two products reaches (``B * x``, the taps
over the positions, ``C *``, and their backward pass): the least time the
chip could take to move the bytes it cannot do without
(``lib/seq_flops.py``: 11 passes over a ``positions x hidden`` array in the
compute dtype a sequence a layer, 4 forward and 7 backward) at the memory's
bandwidth, over the device time of every operation in the ``conv_mix``
scope (forward, recomputed forward and backward alike).  The bound that
applies is bandwidth: a few operations an element.  Read by scope, so the
yardstick is the same work whether XLA's fusions or a kernel do it.

layer: kernels; unit: %; source: device_trace; moves: train_img_s.  Absent
where no operation carries the scope.
"""

import jax.numpy as jnp

from ..lib import peaks, seq_flops
from ..lib import trace as tracelib

SCOPE = "conv_mix"


def read(cap) -> float | None:
    if not cap.trace.devices:
        return None
    ops = [o for o in tracelib.in_window(cap.trace, min(cap.trace.devices))
           if SCOPE in o.scope]
    if not ops:
        return None
    net = cap.driver.train_net_param()
    per_step = (seq_flops.conv_mix_bytes_per_sequence(
        net, jnp.dtype(cap.cell.mix["compute_dtype"]).itemsize)
        * seq_flops.sequences_per_step(net))
    least_s = per_step * cap.traced.steps / peaks.peaks(
        cap.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(o.dur_ps for o in ops) / 1e12)
