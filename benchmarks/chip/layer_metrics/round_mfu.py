"""``round_mfu``: the whole round's share of the chips' bf16 peak.

The training FLOPs the round requires (``flops/<model>.py`` per real
example: forward from the layer shapes, times three, times the m x E x n_k
examples the cohort trains; masked padding steps do not count) over the
traced window's seconds, over chips x peak. Host-clock window, so idle
time counts against it."""
from __future__ import annotations


def compute(ctx):
    per_example = ctx["train_flops_per_example"]
    if per_example is None or ctx["window_s"] <= 0:
        return None
    flops = per_example * ctx["examples_per_round"] * ctx["rounds"]
    peak = ctx["peak"]["bf16_flops"] * ctx["chips"]
    return 100.0 * flops / ctx["window_s"] / peak
