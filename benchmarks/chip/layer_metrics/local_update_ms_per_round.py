"""``local_update_ms_per_round``: device time a round spends in the
clients' local SGD: the self time of the ops whose innermost ``fedavg.*``
scope is ``fedavg.client_update`` (the vmapped forward, backward and
update over every client's steps), averaged over the cell's chips, over
the rounds in the traced window. Left out where no op carries the
scope."""
from __future__ import annotations

from benchmarks.chip import span_reduce


def compute(ctx):
    return span_reduce.per_round_ms(ctx["spans"], ctx["rounds"])[
        "local_update_ms_per_round"]
