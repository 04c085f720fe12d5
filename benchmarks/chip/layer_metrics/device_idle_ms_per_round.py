"""``device_idle_ms_per_round``: the milliseconds a round leaves the device
idle, over the traced window (profiler trace, ``XLA Ops`` lines; averaged
over the cell's chips): the window less the device's busy time, over the
rounds run in it. Where the host loop sets the pace, this is the host's
part of every round, so it moves the rounds' tail."""
from __future__ import annotations


def compute(ctx):
    red = ctx["trace"]
    if not red.busy_s or ctx["rounds"] <= 0:
        return None
    return 1e3 * (red.window_s - red.mean_busy_s) / ctx["rounds"]
