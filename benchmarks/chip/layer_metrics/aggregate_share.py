"""``aggregate_share``: the aggregation kernel's share of the device's busy
time in the traced window, averaged over the cell's chips.

The kernel is found by the function that made it: XLA names the kernel's
instruction after the jitted function that holds the ``pallas_call``
(``_aggregate_impl.1``, ``_qagg_impl.7``). Its self time is summed over
the window. Where no such operation ran, the metric is left out.

A share of busy time and not of a roofline: in the dense cells XLA keeps
the kernel's ``(m, N)`` input in on-chip memory, where it reads faster
than the HBM bandwidth of ``peaks.py``, so no HBM roofline bounds it."""
from __future__ import annotations

KERNEL_FUNCTIONS = ("_aggregate_impl", "_qagg_impl")


def compute(ctx):
    red = ctx["trace"]
    kernel_s = sum(s for name, s in red.op_s.items()
                   if name.startswith(KERNEL_FUNCTIONS))
    if kernel_s <= 0 or red.mean_busy_s <= 0:
        return None
    return 100.0 * kernel_s / red.mean_busy_s
