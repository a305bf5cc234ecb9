"""mfu_seq: model FLOP/s utilization of a sequence-model cell: the
operations one sequence's forward and backward passes require, from the
layers' shapes (``lib/seq_flops.py``: two a multiply-accumulate of every
projection at the share held, of the routed experts at the rows an even
router sends them, and of the attention core's causal pairs at the head
size the model has, three times for the forward and the two backward
products, recomputation not counted), times the sequences per second of the
traced window, over the chip's bf16 peak (``lib/peaks.py``).  The cell's
share of the whole step's peak; ``mfu_lm``'s definition for a net of any
sequence layer types.

layer: step; unit: %; source: host_clock; moves: train_img_s.
"""

from ..lib import peaks, seq_flops


def read(cap) -> float | None:
    per_sequence = seq_flops.train_flops_per_sequence(
        cap.driver.train_net_param())["total"]
    peak = peaks.peaks(cap.device["kind"])["flops_per_s"]
    return 100.0 * per_sequence * cap.traced.img_s / (cap.cell.chips * peak)
