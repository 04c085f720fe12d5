"""The benchmark's own traffic generator: a synthetic client population.

Images: copied from the program (``repro.data.synthetic
.make_image_classification`` and ``repro.data.partition``) so that a change
to the program cannot move the yardstick. Two changes from the original:
only the training set is made (no evaluation runs in the window), and the
per-example loop is one vectorised gather, so the 60,000-image population
costs about a second of set-up instead of several.

Token sequences (a configuration with ``"population": "tokens"``): the
benchmark's own, for next-token language models (:func:`make_tokens`).
Each sequence's topic is the label the partitions split on.

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


# Keys a configuration with ``"population": "tokens"`` must give.
TOKEN_KEYS = ("vocab_size", "seq_len", "clients", "examples_per_client",
              "n_topics")
# Every token of a topic's source has this many successors, with weights
# drawn from a flat Dirichlet: about 2.3 nats of next-token entropy.
SUCCESSORS = 16


def make_tokens(n: int, *, vocab_size: int, seq_len: int, n_topics: int,
                seed: int):
    """``n`` token sequences from ``n_topics`` order-1 sources over the
    vocabulary, each sequence from one topic, as many of each as ``n``
    allows. A source gives every token ``SUCCESSORS`` successor ids and
    their weights (a table of ``vocab_size x SUCCESSORS``, never a dense
    ``vocab_size x vocab_size`` matrix); a sequence starts at a uniform
    token and draws one position at a time, all sequences at once.

    Returns ``(x, y, topic)``: int32 ``(n, seq_len)`` inputs, the next
    tokens ``y = tokens[:, 1:]`` for ``x = tokens[:, :-1]``, and int32
    ``(n,)`` topics."""
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab_size, size=(n_topics, vocab_size, SUCCESSORS))
    cdf = np.cumsum(rng.dirichlet(np.ones(SUCCESSORS),
                                  size=(n_topics, vocab_size)), axis=-1)
    topic = rng.permutation(np.arange(n) % n_topics)
    tokens = np.empty((n, seq_len + 1), np.int32)
    tokens[:, 0] = rng.integers(0, vocab_size, size=n)
    for t in range(seq_len):
        prev = tokens[:, t]
        u = rng.random(n)
        j = np.minimum((u[:, None] >= cdf[topic, prev]).sum(axis=1),
                       SUCCESSORS - 1)
        tokens[:, t + 1] = succ[topic, prev, j]
    return tokens[:, :-1], tokens[:, 1:], topic.astype(np.int32)


def check_config(config: dict) -> None:
    """Raise where the configuration's population cannot be made."""
    kind = config.get("population", "images")
    if kind == "tokens":
        missing = [k for k in TOKEN_KEYS if k not in config]
        if missing:
            raise KeyError(f"token population {config.get('name')!r} lacks "
                           f"{missing}")
    elif kind != "images":
        raise ValueError(f"unknown population {kind!r}")


def make_clients(config: dict, partition: dict, seed: int):
    """The cell's client population: a list of per-client ``(x, y)``.

    ``config`` gives the client count and examples (sequences) per client,
    and the input shape and classes of images or, with ``"population":
    "tokens"``, the vocabulary, sequence length and topics of token
    sequences; ``partition`` is the traffic's ``{"kind": "iid" |
    "pathological_noniid", "shards_per_client": ...}``, which splits on the
    class or the topic."""
    check_config(config)
    k = int(config["clients"])
    n = k * int(config["examples_per_client"])
    if config.get("population") == "tokens":
        x, y, labels = make_tokens(
            n, vocab_size=int(config["vocab_size"]),
            seq_len=int(config["seq_len"]), n_topics=int(config["n_topics"]),
            seed=seed,
        )
    else:
        x, y = make_images(
            n, image_shape=tuple(config["input_shape"]),
            n_classes=int(config["n_classes"]), seed=seed,
        )
        labels = y
    kind = partition["kind"]
    if kind == "iid":
        parts = partition_iid(n, k, seed + 1)
    elif kind == "pathological_noniid":
        parts = partition_pathological_noniid(
            labels, k, int(partition.get("shards_per_client", 2)), seed + 1
        )
    else:
        raise ValueError(f"unknown partition kind {kind!r}")
    return [(x[ix], y[ix]) for ix in parts]
