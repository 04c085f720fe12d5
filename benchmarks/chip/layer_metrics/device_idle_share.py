"""``device_idle_share``: the share of the traced window in which no
operation ran on the device, averaged over the cell's chips (profiler
trace, ``XLA Ops`` lines). Moves ``round_s`` wherever the host loop, not
the device, sets the pace."""
from __future__ import annotations


def compute(ctx):
    red = ctx["trace"]
    if not red.busy_s or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.mean_busy_s / red.window_s)
