"""``moe_experts_mfu``: the held routed experts' grouped matmuls as a share
of the chips' bf16 peak: their training FLOPs at the expected share
(``flops/transformer.py``: k x held / E expert evaluations a token, times
the round's tokens) over the device self time of the held experts (ops
under ``model.moe.experts`` plus XLA's ``ragged-dot*`` kernels, which carry
no scope path), over chips x peak. Left out where no op carries the
scope."""
from __future__ import annotations

from pathlib import Path

from benchmarks.chip.harness import load_module

HERE = Path(__file__).resolve().parent


def compute(ctx):
    s = ctx["spans"].segment_s.get("model.moe.experts")
    if s is None or ctx["rounds"] <= 0:
        return None
    s += load_module(HERE / "moe_ms_per_round.py").grouped_matmul_s(
        ctx["trace"])
    flops_file = load_module(HERE.parent / "flops" / "transformer.py")
    flops = (flops_file.held_expert_train_flops_per_example(ctx["config"])
             * ctx["examples_per_round"] * ctx["rounds"])
    peak = ctx["peak"]["bf16_flops"] * ctx["chips"]
    return 100.0 * flops / s / peak
