"""moe_imbalance: the most rows a held expert was sent over the mean a
held expert was sent, the largest over the expert layers; from the
driver's ``moe_load`` counter (one forward a sequence of the first resident
batch after the window, on the weights the run left;
``ops.sequence.moe_load``).  1 is an even router; ``moe_load_seeded`` beside
it on the ``counters`` line is the same on the weights the seed gave.

layer: graph; unit: x; source: program_counter; moves: train_img_s.  Absent
where the driver counts no expert layer's load.
"""


def read(cap) -> float | None:
    load = cap.counters.get("moe_load")
    if not load:
        return None
    return max(max(v["rows"]) * len(v["rows"]) / sum(v["rows"])
               for v in load.values())
