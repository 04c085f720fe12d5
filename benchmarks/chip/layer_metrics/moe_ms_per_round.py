"""``moe_ms_per_round``: device time a round spends in the MoE layers: the
self time of the ops under the model's ``model.moe`` scope (router,
dispatch, the held experts, combine, shared experts), plus XLA's grouped
matmul kernels (ops named ``ragged-dot*``: the compiler gives them no scope
path, and only the held experts use them), averaged over the cell's chips,
over the rounds in the traced window. Left out where no op carries the
scope."""
from __future__ import annotations

GROUPED = "ragged-dot"


def grouped_matmul_s(trace) -> float:
    """Self time of the grouped matmul kernels and their metadata."""
    return sum(v for k, v in trace.op_s.items() if k.startswith(GROUPED))


def compute(ctx):
    s = ctx["spans"].segment_s.get("model.moe")
    if s is None or ctx["rounds"] <= 0:
        return None
    return 1e3 * (s + grouped_matmul_s(ctx["trace"])) / ctx["rounds"]
