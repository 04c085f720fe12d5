"""The benchmark's population generator: the image populations of the
existing configurations are the same arrays as before token sequences were
added, and the token population is seeded, well formed and split by
topic."""
from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import harness, population  # noqa: E402

# SHA-256 of every client's (x, y) at seed 1618033988, each configuration
# under its cell's partition, taken from the generator as it was before the
# token population was added.
PINNED = {
    ("mnist_cnn", "pathological_noniid"):
        "33f9fea842ef41394414826263b48d3b5c60870ac84f013b4ef15e3818a4b28c",
    ("mnist_2nn", "iid"):
        "a98f9d49e141bca65df5dd10eddc109bcc0b5a7d225ef26bee72fda874b65374",
}
TOKENS = {"name": "tokens", "population": "tokens", "vocab_size": 50,
          "seq_len": 12, "clients": 4, "examples_per_client": 6,
          "n_topics": 4}
ONE_TOPIC_A_SILO = {"kind": "pathological_noniid", "shards_per_client": 1}


def _digest(clients) -> str:
    h = hashlib.sha256()
    for x, y in clients:
        for a in (x, y):
            h.update(f"{a.dtype.str}{a.shape}".encode())
            h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config, kind", sorted(PINNED))
def test_image_populations_are_unchanged(config, kind):
    cfg = json.loads((harness.BENCH_DIR / "configs" / f"{config}.json")
                     .read_text())
    partition = {"kind": kind, "shards_per_client": 2}
    got = population.make_clients(cfg, partition, 1618033988)
    assert _digest(got) == PINNED[config, kind]


def test_tokens_are_a_pure_function_of_the_seed():
    a = population.make_clients(TOKENS, ONE_TOPIC_A_SILO, 2**31 + 3)
    b = population.make_clients(TOKENS, ONE_TOPIC_A_SILO, 2**31 + 3)
    c = population.make_clients(TOKENS, ONE_TOPIC_A_SILO, 2**31 + 4)
    assert _digest(a) == _digest(b) != _digest(c)


@pytest.mark.parametrize("partition", [{"kind": "iid"}, ONE_TOPIC_A_SILO],
                         ids=["iid", "one_topic_a_silo"])
def test_token_clients_hold_next_token_pairs(partition):
    clients = population.make_clients(TOKENS, partition, 11)
    assert len(clients) == TOKENS["clients"]
    for x, y in clients:
        shape = (TOKENS["examples_per_client"], TOKENS["seq_len"])
        assert x.shape == y.shape == shape
        assert x.dtype == y.dtype == np.int32
        assert x.min() >= 0 and max(x.max(), y.max()) < TOKENS["vocab_size"]
        np.testing.assert_array_equal(x[:, 1:], y[:, :-1])


def _successors(x, y):
    succ = {}
    for a, b in zip(x.ravel().tolist(), y.ravel().tolist()):
        succ.setdefault(a, set()).add(b)
    return succ


def test_each_token_follows_one_of_its_topic_successors():
    # 40 tokens, about 100 transitions from each in each topic: a source
    # over the whole vocabulary would show most of the 40 after a token.
    x, y, topic = population.make_tokens(
        120, vocab_size=40, seq_len=100, n_topics=3, seed=5)
    assert np.bincount(topic).tolist() == [40, 40, 40]
    for t in range(3):
        succ = _successors(x[topic == t], y[topic == t])
        assert max(len(s) for s in succ.values()) <= population.SUCCESSORS
    # the topics are different sources
    assert max(len(s) for s in _successors(x, y).values()) > population.SUCCESSORS


def test_the_topic_partition_gives_each_silo_its_topic():
    x, y, topic = population.make_tokens(
        TOKENS["clients"] * TOKENS["examples_per_client"],
        vocab_size=TOKENS["vocab_size"], seq_len=TOKENS["seq_len"],
        n_topics=TOKENS["n_topics"], seed=21)
    parts = population.partition_pathological_noniid(topic, 4, 1, 22)
    assert sorted(int(np.unique(topic[ix]).item()) for ix in parts) == [0, 1, 2, 3]
    clients = population.make_clients(TOKENS, ONE_TOPIC_A_SILO, 21)
    for (cx, cy), ix in zip(clients, parts):
        np.testing.assert_array_equal(cx, x[ix])
        np.testing.assert_array_equal(cy, y[ix])


@pytest.mark.parametrize("key", population.TOKEN_KEYS)
def test_a_token_config_without_one_of_its_keys_is_refused(key):
    cfg = {k: v for k, v in TOKENS.items() if k != key}
    with pytest.raises(KeyError, match=key):
        population.check_config(cfg)
    with pytest.raises(KeyError, match=key):
        population.make_clients(cfg, {"kind": "iid"}, 1)


def test_an_unknown_population_is_refused():
    with pytest.raises(ValueError, match="unknown population"):
        population.check_config(dict(TOKENS, population="audio"))
