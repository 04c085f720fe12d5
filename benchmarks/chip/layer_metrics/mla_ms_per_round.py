"""``mla_ms_per_round``: device time a round spends in multi-head latent
attention: the self time of the ops under the model's ``model.mla`` scope
(projections, rotary embedding, the attention kernel; forward, recomputed
forward and backward alike), averaged over the cell's chips, over the
rounds in the traced window. Left out where no op carries the scope."""
from __future__ import annotations


def compute(ctx):
    s = ctx["spans"].segment_s.get("model.mla")
    if s is None or ctx["rounds"] <= 0:
        return None
    return 1e3 * s / ctx["rounds"]
