"""The benchmark's own traffic generator: a synthetic client population.

Copied from the program (``repro.data.synthetic.make_image_classification``
and ``repro.data.partition``) so that a change to the program cannot move
the yardstick. Two changes from the original: only the training set is
made (no evaluation runs in the window), and the per-example loop is one
vectorised gather, so the 60,000-image population costs about a second of
set-up instead of several.

Every array is a pure function of ``seed``.
"""
from __future__ import annotations

import numpy as np


def _upsample(img: np.ndarray, hw) -> np.ndarray:
    """Bilinear upsample (h0, w0, c) -> (h, w, c)."""
    h0, w0, _ = img.shape
    h, w = hw
    yi = np.linspace(0, h0 - 1, h)
    xi = np.linspace(0, w0 - 1, w)
    y0 = np.floor(yi).astype(int)
    x0 = np.floor(xi).astype(int)
    y1 = np.minimum(y0 + 1, h0 - 1)
    x1 = np.minimum(x0 + 1, w0 - 1)
    wy = (yi - y0)[:, None, None]
    wx = (xi - x0)[None, :, None]
    top = img[y0][:, x0] * (1 - wx) + img[y0][:, x1] * wx
    bot = img[y1][:, x0] * (1 - wx) + img[y1][:, x1] * wx
    return (top * (1 - wy) + bot * wy).astype(np.float32)


def make_images(n: int, *, image_shape, n_classes: int, seed: int,
                difficulty: float = 1.0):
    """MNIST-like images: a smooth random template per class, shifted by up
    to 3 pixels, scaled by U(0.7, 1.3), plus N(0, 0.35) pixel noise.
    Returns ``(x, y)``: float32 ``(n, *image_shape)`` and int32 ``(n,)``."""
    rng = np.random.default_rng(seed)
    h, w, ch = image_shape
    low = rng.normal(size=(n_classes, 7, 7, ch)).astype(np.float32)
    templates = np.stack([_upsample(low[c], (h, w)) for c in range(n_classes)])
    templates /= np.maximum(
        np.abs(templates).max(axis=(1, 2, 3), keepdims=True), 1e-6
    )
    y = rng.integers(0, n_classes, size=n)
    shifts = rng.integers(-3, 4, size=(n, 2))
    scale = rng.uniform(0.7, 1.3, size=(n, 1, 1, 1)).astype(np.float32)
    noise = rng.standard_normal((n, h, w, ch), dtype=np.float32)
    # np.roll(template, shift) for every example at once.
    rows = (np.arange(h)[None, :] - shifts[:, :1]) % h          # (n, h)
    cols = (np.arange(w)[None, :] - shifts[:, 1:]) % w          # (n, w)
    x = templates[y[:, None, None], rows[:, :, None], cols[:, None, :]]
    x *= scale
    x += noise * np.float32(0.35 * difficulty)
    return x, y.astype(np.int32)


def partition_iid(n_examples: int, n_clients: int, seed: int):
    rng = np.random.default_rng(seed)
    return list(np.array_split(rng.permutation(n_examples), n_clients))


def partition_pathological_noniid(labels, n_clients: int,
                                  shards_per_client: int, seed: int):
    """The paper's partition: sort by label, cut 2K shards, give each
    client ``shards_per_client`` of them."""
    rng = np.random.default_rng(seed)
    order = np.argsort(labels, kind="stable")
    n_shards = n_clients * shards_per_client
    shards = np.array_split(order, n_shards)
    shard_ids = rng.permutation(n_shards)
    return [
        np.concatenate([shards[i] for i in shard_ids[
            k * shards_per_client:(k + 1) * shards_per_client]])
        for k in range(n_clients)
    ]


def make_clients(config: dict, partition: dict, seed: int):
    """The cell's client population: a list of per-client ``(x, y)``.

    ``config`` gives the input shape, classes, client count and examples
    per client; ``partition`` is the traffic's ``{"kind": "iid" |
    "pathological_noniid", "shards_per_client": ...}``."""
    k = int(config["clients"])
    n = k * int(config["examples_per_client"])
    x, y = make_images(
        n, image_shape=tuple(config["input_shape"]),
        n_classes=int(config["n_classes"]), seed=seed,
    )
    kind = partition["kind"]
    if kind == "iid":
        parts = partition_iid(n, k, seed + 1)
    elif kind == "pathological_noniid":
        parts = partition_pathological_noniid(
            y, k, int(partition.get("shards_per_client", 2)), seed + 1
        )
    else:
        raise ValueError(f"unknown partition kind {kind!r}")
    return [(x[ix], y[ix]) for ix in parts]
