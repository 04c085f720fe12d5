"""Training FLOPs per example of the paper's MNIST 2NN, from its layer
shapes in ``configs/mnist_2nn.json``. Multiply-adds count 2 FLOPs; biases
and ReLUs are left out. Training is forward plus backward, three times the
forward."""
from __future__ import annotations


def forward_flops(config: dict) -> float:
    return sum(2.0 * l["in"] * l["out"] for l in config["layers"].values())


def train_flops_per_example(config: dict) -> float:
    return 3.0 * forward_flops(config)
