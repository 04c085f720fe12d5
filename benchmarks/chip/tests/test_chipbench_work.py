"""The work a round must do, counted from shapes: FLOPs per example from
hand counts, and each configuration's parameter count against the
program's model."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import harness  # noqa: E402

CONFIGS = harness.BENCH_DIR / "configs"


def _flops(model):
    mod = harness.load_module(harness.BENCH_DIR / "flops" / f"{model}.py")
    cfg = json.loads((CONFIGS / f"{model}.json").read_text())
    return mod, cfg


def test_mnist_cnn_forward_flops_match_the_hand_count():
    mod, cfg = _flops("mnist_cnn")
    conv1 = 2 * 5 * 5 * 1 * 32 * 28 * 28        # 1.25 MFLOP
    conv2 = 2 * 5 * 5 * 32 * 64 * 14 * 14       # 20.07 MFLOP
    fc = 2 * 3136 * 512                         # 3.21 MFLOP
    out = 2 * 512 * 10                          # 0.01 MFLOP
    assert mod.forward_flops(cfg) == conv1 + conv2 + fc + out
    assert mod.forward_flops(cfg) / 1e6 == pytest.approx(24.54, abs=0.01)
    assert mod.train_flops_per_example(cfg) == 3 * mod.forward_flops(cfg)


def test_mnist_2nn_forward_flops_match_the_hand_count():
    mod, cfg = _flops("mnist_2nn")
    assert mod.forward_flops(cfg) == 2 * (784 * 200 + 200 * 200 + 200 * 10)
    assert mod.forward_flops(cfg) / 1e6 == pytest.approx(0.398, abs=0.001)


@pytest.mark.parametrize("model", ["mnist_cnn", "mnist_2nn"])
def test_config_parameter_count_matches_the_program(model):
    import jax

    from repro.specs.spec import ModelSpec
    from repro.utils.tree import tree_size

    cfg = json.loads((CONFIGS / f"{model}.json").read_text())
    ref = harness.load_module(CONFIGS / f"{model}.py")
    ours = tree_size(ref.init(jax.random.PRNGKey(0), cfg))
    theirs = tree_size(ModelSpec(model).build().init(jax.random.PRNGKey(0)))
    assert ours == theirs == cfg["params"]
