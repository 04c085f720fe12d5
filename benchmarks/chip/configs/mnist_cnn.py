"""Plain reference of the paper's MNIST CNN, built from ``mnist_cnn.json``.

Parameters are a dict of layers, each ``{"w", "b"}``: the layout the
program's model takes, so one weight tree feeds both. ``apply`` computes in
the parameters' dtype at the given matmul precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _glorot(key, shape, dtype):
    fan_in, fan_out = math.prod(shape[:-1]), shape[-1]
    lim = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def init(key, config, dtype=jnp.float32):
    layers = config["layers"]
    shapes = {
        "conv1": tuple(layers["conv1"]["kernel"]),
        "conv2": tuple(layers["conv2"]["kernel"]),
        "fc": (layers["fc"]["in"], layers["fc"]["out"]),
        "out": (layers["out"]["in"], layers["out"]["out"]),
    }
    keys = jax.random.split(key, len(shapes))
    return {
        name: {"w": _glorot(k, shape, dtype),
               "b": jnp.zeros((shape[-1],), dtype)}
        for k, (name, shape) in zip(keys, shapes.items())
    }


def _conv_relu_pool(p, x, precision):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision,
    )
    y = jax.nn.relu(y + p["b"])
    return jax.lax.reduce_window(
        y, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )


def apply(params, x, precision):
    """Logits ``(B, classes)`` of images ``x`` ``(B, 28, 28, 1)``."""
    x = x.astype(params["conv1"]["w"].dtype)
    x = _conv_relu_pool(params["conv1"], x, precision)
    x = _conv_relu_pool(params["conv2"], x, precision)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(
        jnp.dot(x, params["fc"]["w"], precision=precision) + params["fc"]["b"]
    )
    return jnp.dot(x, params["out"]["w"], precision=precision) + params["out"]["b"]
