"""Plain reference of the paper's MNIST 2NN, built from ``mnist_2nn.json``.

Parameters are a dict of layers, each ``{"w", "b"}``: the layout the
program's model takes, so one weight tree feeds both. ``apply`` computes in
the parameters' dtype at the given matmul precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def init(key, config, dtype=jnp.float32):
    layers = config["layers"]
    keys = jax.random.split(key, len(layers))
    params = {}
    for k, (name, layer) in zip(keys, layers.items()):
        d_in, d_out = layer["in"], layer["out"]
        lim = math.sqrt(6.0 / (d_in + d_out))
        params[name] = {
            "w": jax.random.uniform(k, (d_in, d_out), dtype, -lim, lim),
            "b": jnp.zeros((d_out,), dtype),
        }
    return params


def apply(params, x, precision):
    """Logits ``(B, classes)`` of images ``x`` ``(B, 28, 28, 1)``."""
    x = x.reshape(x.shape[0], -1).astype(params["fc1"]["w"].dtype)
    for name in ("fc1", "fc2"):
        p = params[name]
        x = jax.nn.relu(jnp.dot(x, p["w"], precision=precision) + p["b"])
    p = params["out"]
    return jnp.dot(x, p["w"], precision=precision) + p["b"]
