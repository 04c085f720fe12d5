"""The comparison that decides ``correct``.

The timed path's first three calls (steps) run in set-up from weights the
benchmark made; the reference follows the same rounds. A cell compares
some of three numbers, each against its limit in ``limits/<cell>.json``,
which also says how each is taken:

- ``loss_gap``: the largest relative gap between a round's train loss and
  the reference's, over the rounds of the first ``steps`` steps (default:
  all three).
- ``update_gap``: the first step's change of the weights, leaf by leaf:
  the gap between the program's norm and the reference's, over the larger
  of the reference's norm of that leaf and of the median leaf; the worst
  leaf.
- ``change_gap``: the same for the change after the last step.

Leaves whose reference change is under a thousandth of the median leaf's
are left out of the norms' comparison: they move by rounding alone. A
number the limits file does not name is not compared.
"""
from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE_LEAF = 1e-3
NUMBERS = ("loss_gap", "update_gap", "change_gap")


def _leaf_norms(tree_a, tree_b):
    import jax

    return [
        float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64)))
        for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b))
    ]


def norm_gap(prog_after, ref_after, start):
    """Gap between the norms of ``after - start``, leaf by leaf: the worst
    leaf's."""
    got = _leaf_norms(prog_after, start)
    want = _leaf_norms(ref_after, start)
    if not all(math.isfinite(v) for v in got + want):
        return math.inf
    med = float(np.median(want))
    gaps = [abs(g - w) / max(w, med) for g, w in zip(got, want)
            if w >= NEGLIGIBLE_LEAF * med]
    if not gaps:
        return 0.0
    return max(gaps)


def loss_gap(prog_losses, ref_losses):
    if len(prog_losses) != len(ref_losses):
        return math.inf
    gaps = [abs(p - r) / abs(r) for p, r in zip(prog_losses, ref_losses)]
    return max(gaps) if all(math.isfinite(g) for g in gaps) else math.inf


def readings(prog, ref, start, step_rounds, spec: dict) -> dict:
    """The numbers ``spec`` (a cell's limits) names. ``prog``/``ref``:
    ``{"losses": [...], "params": {round: tree}}``; ``step_rounds``: the
    rounds after each step."""
    out = {}
    if "loss_gap" in spec:
        steps = int(spec["loss_gap"].get("steps", len(step_rounds)))
        last = step_rounds[steps - 1]
        out["loss_gap"] = loss_gap(prog["losses"][:last], ref["losses"][:last])
    if "update_gap" in spec:
        r = step_rounds[0]
        out["update_gap"] = norm_gap(prog["params"][r], ref["params"][r], start)
    if "change_gap" in spec:
        r = step_rounds[-1]
        out["change_gap"] = norm_gap(prog["params"][r], ref["params"][r], start)
    return out


def judge(values: dict, limits: dict):
    """``(correct, checks)``: every number finite and at or under its
    limit. ``checks`` maps each name to ``{"value", "limit"}``."""
    checks = {k: {"value": float(v), "limit": float(limits[k])}
              for k, v in values.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
