"""``dispatch_idle_ms_per_round``: device idle time a round inside the host
loop's ``fedavg.dispatch`` spans (the call of the round's compiled
program), once the device's times are on the host's clock, averaged over
the cell's chips, over the rounds in the traced window. Left out where
the trace holds no such span."""
from __future__ import annotations

from benchmarks.chip import span_reduce


def compute(ctx):
    return span_reduce.per_round_ms(ctx["spans"], ctx["rounds"])[
        "dispatch_idle_ms_per_round"]
