"""setup_s: process start to the first measured step: imports, weights
and inputs from the seed, compilation or the read of the compile cache,
the correctness check against the reference, and the warm-up.

unit: s; better: lower; source: host_clock.
"""


def read(cap) -> float | None:
    return cap.setup["setup_s"]
