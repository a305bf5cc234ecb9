"""train_fed_img_s: ``train_img_s`` of a cell whose batches come through
the host feed, read in the same way.

unit: img/s; better: higher; source: host_clock.  A name of its own only
because a metric has one bound: the host pipeline sets this rate, and it
spreads from run to run a hundred times as widely as the rate of a cell
the device bounds (PERF.md, PR 22), so one bound for both would either
hide losses there or refuse noise here.
"""

from .train_img_s import read  # noqa: F401
