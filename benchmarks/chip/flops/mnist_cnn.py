"""Training FLOPs per example of the paper's MNIST CNN, from its layer
shapes in ``configs/mnist_cnn.json``. Multiply-adds count 2 FLOPs; biases,
ReLUs and pooling are left out. Training is forward plus backward, three
times the forward."""
from __future__ import annotations


def forward_flops(config: dict) -> float:
    layers = config["layers"]
    h, w, _ = config["input_shape"]
    total = 0.0
    for name in ("conv1", "conv2"):
        kh, kw, c_in, c_out = layers[name]["kernel"]
        total += 2.0 * kh * kw * c_in * c_out * h * w   # SAME, stride 1
        h, w = h // 2, w // 2                            # 2x2 max pool
    for name in ("fc", "out"):
        total += 2.0 * layers[name]["in"] * layers[name]["out"]
    return total


def train_flops_per_example(config: dict) -> float:
    return 3.0 * forward_flops(config)
