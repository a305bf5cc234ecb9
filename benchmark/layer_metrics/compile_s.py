"""compile_s: seconds JAX spent tracing, lowering and compiling (or
reading the compile cache) during set-up.

layer: entry; unit: s; source: program_counter (``jax.monitoring``
duration events, as ``chip_smoke.py``'s ``Clock`` sums them);
moves: setup_s; cells: all.
"""


def read(cap) -> float | None:
    return cap.setup["compile_s"]
