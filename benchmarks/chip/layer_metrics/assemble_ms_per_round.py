"""``assemble_ms_per_round``: device time a round spends assembling the
cohort's minibatches: the self time of the ops under the program's
``fedavg.assemble`` scope (the gather of each client's rows from the
device pool), averaged over the cell's chips, over the rounds in the
traced window. Left out where no op carries the scope."""
from __future__ import annotations

from benchmarks.chip import span_reduce


def compute(ctx):
    return span_reduce.per_round_ms(ctx["spans"], ctx["rounds"])[
        "assemble_ms_per_round"]
