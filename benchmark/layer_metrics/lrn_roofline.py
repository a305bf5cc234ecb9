"""lrn_roofline: the fused LRN kernels' share of their roofline: the
least time the chip could take for the bytes each call must move (forward:
read the input, write the output; backward: read the input and the output's
gradient, write the input's gradient; from the layers' shapes,
``lib/flops.py``) over the device time of the kernels named
``relu_lrn_fwd`` and ``relu_lrn_bwd`` in the trace.  The bound that applies
is memory bandwidth: the kernels do a few operations per element.

layer: kernels; unit: %; source: device_trace; moves: train_img_s.  Absent
where the fusion plan fuses no LRN chain, so that no such kernel ran.
"""

import jax.numpy as jnp

from ..lib import flops, peaks
from ..lib import trace as tracelib

KERNELS = ("relu_lrn_fwd", "relu_lrn_bwd")


def read(cap) -> float | None:
    ops = tracelib.in_window(cap.trace, min(cap.trace.devices))
    fwd, bwd = (tracelib.kernel_ops(ops, k) for k in KERNELS)
    if not fwd or not bwd:
        return None
    need = flops.lrn_min_bytes_per_step(
        cap.driver.train_net_param(),
        jnp.dtype(cap.cell.mix["compute_dtype"]).itemsize)
    # per device: the net declares the batch of all the cell's workers
    per_step = (need["fwd"] + need["bwd"]) / len(cap.driver.used_devices())
    steps = len(fwd) / need["layers"]
    least_s = per_step * steps / peaks.peaks(
        cap.device["kind"])["hbm_bytes_per_s"]
    took_s = sum(o.dur_ps for o in fwd + bwd) / 1e12
    return 100.0 * least_s / took_s
