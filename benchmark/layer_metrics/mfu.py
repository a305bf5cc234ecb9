"""mfu: model FLOP/s utilization: the operations the forward and
backward passes require per image, from the layers' shapes
(``lib/flops.py``), times the images per second of the traced window, over
chips times the chip's peak (``lib/peaks.py``).

layer: step; unit: %; source: host_clock; moves: train_img_s; cells: all.
The peak is the chip's bf16 peak for every cell, the float32 ones too: it
is the only matrix peak the chip has.
"""

from ..lib import flops, peaks


def read(cap) -> float | None:
    per_image = flops.train_flops_per_image(cap.driver.train_net_param())
    peak = peaks.peaks(cap.device["kind"])["flops_per_s"]
    return 100.0 * per_image * cap.traced.img_s / (cap.cell.chips * peak)
