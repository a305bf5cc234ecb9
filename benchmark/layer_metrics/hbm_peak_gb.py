"""hbm_peak_gb: peak memory on the fullest chip, after the window: the
buffers the process held plus what the runtime reserved for the loaded
programs' temporaries.

layer: device; unit: GB; source: program_counter
(``device.memory_stats()``: ``peak_bytes_in_use`` + ``peak_bytes_reserved``,
``lib/harness.py`` ``device_info``); moves: train_img_s, through the batch
a chip can hold; cells: all.
"""


def read(cap) -> float | None:
    peak = cap.device["memory_peak_bytes"]
    return peak / 1e9 if peak else None
