"""The numbers that decide ``correct``, on trees built by hand."""
from __future__ import annotations

import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import compare  # noqa: E402

START = {"a": {"w": np.zeros(4, np.float32), "b": np.zeros(2, np.float32)},
         "c": {"w": np.zeros(3, np.float32)}}


def _moved(scale_a=1.0, scale_c=1.0, b=1.0):
    return {"a": {"w": np.full(4, 0.5 * scale_a, np.float32),
                  "b": np.full(2, 0.5 * b, np.float32)},
            "c": {"w": np.full(3, 2.0 * scale_c, np.float32)}}


def test_equal_changes_read_zero():
    assert compare.norm_gap(_moved(), _moved(), START) == 0.0


def test_an_unchanged_state_reads_one():
    assert compare.norm_gap(START, _moved(), START) == pytest.approx(1.0)


def test_the_worst_leaf_is_measured_against_the_larger_norm():
    # Leaf norms of the reference: a.w 1.0, a.b 0.707, c.w 3.46; median 1.0.
    got = _moved(scale_a=1.1)                     # a.w 1.1, a.b 0.778
    assert compare.norm_gap(got, _moved(), START) == pytest.approx(0.1, rel=1e-5)
    got = _moved(scale_c=1.1)                     # c.w off by 10% of itself
    assert compare.norm_gap(got, _moved(), START) == pytest.approx(0.1, rel=1e-5)


def test_leaves_that_move_by_rounding_alone_are_left_out():
    ref = _moved(b=1e-5)          # a.b moves 1e-5 of the median leaf
    got = _moved(b=5e-5)
    assert compare.norm_gap(got, ref, START) == 0.0


def test_non_finite_readings_fail():
    bad = _moved()
    bad["c"]["w"][0] = np.nan
    assert compare.norm_gap(bad, _moved(), START) == math.inf
    assert compare.loss_gap([1.0, float("nan")], [1.0, 0.5]) == math.inf
    assert compare.loss_gap([1.0], [1.0, 0.5]) == math.inf
    assert compare.loss_gap([1.1, 0.5], [1.0, 0.5]) == pytest.approx(0.1)


def test_judge_needs_every_number_at_or_under_its_limit():
    limits = {"x": 1e-3, "y": 0.0}
    assert compare.judge({"x": 1e-3, "y": 0}, limits)[0]
    ok, checks = compare.judge({"x": 2e-3, "y": 0}, limits)
    assert not ok and checks["x"] == {"value": 2e-3, "limit": 1e-3}
    assert not compare.judge({"x": math.inf, "y": 0}, limits)[0]


def test_one_odd_leaf_sets_the_reading():
    got = _moved(scale_c=1.5)                     # one leaf of three off
    assert compare.norm_gap(got, _moved(), START) == pytest.approx(0.5)


def test_update_and_change_gaps_read_the_first_and_last_steps():
    ref = {"losses": [1.0] * 3, "params": {1: _moved(), 2: _moved(),
                                            3: _moved()}}
    prog = {"losses": [1.0] * 3, "params": {1: _moved(scale_a=1.3),
                                             2: _moved(scale_c=2.0),
                                             3: _moved(scale_c=1.1)}}
    spec = {"update_gap": {"limit": 1}, "change_gap": {"limit": 1}}
    out = compare.readings(prog, ref, START, (1, 2, 3), spec)
    assert out["update_gap"] == pytest.approx(0.3, rel=1e-5)
    assert out["change_gap"] == pytest.approx(0.1, rel=1e-5)


def test_readings_take_what_the_limits_name():
    ref = {"losses": [2.0, 1.0, 0.5], "params": {1: _moved(), 2: _moved(),
                                                  3: _moved()}}
    prog = {"losses": [2.0, 1.0, 1.0], "params": {1: _moved(), 2: _moved(),
                                                   3: _moved(scale_c=1.2)}}
    spec = {"loss_gap": {"limit": 1, "steps": 2}}
    assert compare.readings(prog, ref, START, (1, 2, 3), spec) == {
        "loss_gap": 0.0}
    spec = {"loss_gap": {"limit": 1}, "change_gap": {"limit": 1}}
    out = compare.readings(prog, ref, START, (1, 2, 3), spec)
    assert out["loss_gap"] == pytest.approx(1.0)
    assert out["change_gap"] == pytest.approx(0.2, rel=1e-5)
    assert set(out) == {"loss_gap", "change_gap"}
