"""mfu_ops: model FLOP/s utilization of a sequence-model cell, ``mfu_seq``
with its count from the operations module the configuration names
(``cap.driver.ops``, ``modules.operations``): the operations one
sequence's forward and backward passes require, from the layers' shapes,
times the sequences per second of the traced window, over the chip's bf16
peak (``lib/peaks.py``).  The cell's share of the whole step's peak.

layer: step; unit: %; source: host_clock; moves: train_img_s.  Absent
where the cell's driver names no operations module.
"""

from ..lib import peaks


def read(cap) -> float | None:
    count = getattr(cap.driver, "ops", None)
    if count is None or cap.traced is None:
        return None
    per_sequence = count.train_flops_per_sequence(
        cap.driver.train_net_param())["total"]
    peak = peaks.peaks(cap.device["kind"])["flops_per_s"]
    return 100.0 * per_sequence * cap.traced.img_s / (cap.cell.chips * peak)
