"""train_img_s: images whose step completed inside the window over the
window's seconds, summed over the chips of the cell.

unit: img/s; better: higher; source: host_clock.  The window is closed by
the last unit's own loss fetch, which waits for its last step.
"""


def read(cap) -> float | None:
    return cap.window.img_s
