"""x4_scaling_eff: the cell's rate over its chips times the rate of the
same round on a one-device mesh, measured for a few rounds after the
window of the traced run.

layer: round; unit: %; source: host_clock; moves: train_img_s in the round
cells.  Absent where the driver measures no one-device round.
"""


def read(cap) -> float | None:
    one = cap.extra.get("one_device_img_s")
    if not one:
        return None
    return 100.0 * cap.window.img_s / (cap.cell.chips * one)
