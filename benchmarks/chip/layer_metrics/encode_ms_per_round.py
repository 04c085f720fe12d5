"""``encode_ms_per_round``: device time a round spends encoding the
clients' uploads: the self time of the ops under the program's
``fedavg.encode`` scope (the deltas, their ravel and the codec's encode),
averaged over the cell's chips, over the rounds in the traced window.
Left out where no op carries the scope, as in a cell without a codec."""
from __future__ import annotations

from benchmarks.chip import span_reduce


def compute(ctx):
    return span_reduce.per_round_ms(ctx["spans"], ctx["rounds"])[
        "encode_ms_per_round"]
