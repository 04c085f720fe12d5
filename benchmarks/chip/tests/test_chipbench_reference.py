"""The plain reference round: its loss over class ids of any shape, and
the cohort run in blocks of clients, which must train on exactly the
draws of the whole cohort at once."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import population, reference  # noqa: E402

TOKENS = {"population": "tokens", "vocab_size": 32, "seq_len": 10,
          "clients": 5, "examples_per_client": 8, "n_topics": 5}
HIDDEN = 16
ROUNDS = 3
Q4 = {"kind": "quantize", "bits": 4, "chunk": 512}


def _old_loss(logits, y):
    # The reference's loss as it was for one class id an example.
    import jax
    import jax.numpy as jnp

    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def test_the_loss_for_one_label_an_example_is_unchanged():
    import jax

    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    logits = jax.random.normal(k1, (64, 10))
    y = jax.random.randint(k2, (64,), 0, 10)
    assert str(jax.make_jaxpr(reference.cross_entropy)(logits, y)) == str(
        jax.make_jaxpr(_old_loss)(logits, y))
    assert float(reference.cross_entropy(logits, y)) == float(
        _old_loss(logits, y))


def test_the_loss_over_token_positions_is_the_mean_over_all():
    import jax

    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    logits = jax.random.normal(k1, (4, 6, 11))
    y = jax.random.randint(k2, (4, 6), 0, 11)
    flat = _old_loss(logits.reshape(24, 11), y.reshape(24))
    assert float(reference.cross_entropy(logits, y)) == pytest.approx(
        float(flat), rel=1e-6)


def test_a_block_draws_the_rows_of_its_slots_in_the_whole_cohort():
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(9)
    whole = reference._client_orders(key, jnp.arange(5, dtype=jnp.int32),
                                     8, 2)
    part = reference._client_orders(key, jnp.asarray([3, 4], jnp.int32), 8, 2)
    np.testing.assert_array_equal(np.asarray(part), np.asarray(whole)[3:])


def _bigram_apply(params, x, precision):
    import jax.numpy as jnp

    h = jnp.tanh(params["embed"][x])
    return jnp.dot(h, params["out"], precision=precision)


@pytest.fixture(scope="module")
def tokens():
    import jax

    clients = population.make_clients(
        TOKENS, {"kind": "pathological_noniid", "shards_per_client": 1}, 17)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {
        "embed": np.asarray(jax.random.normal(k1, (32, HIDDEN)) * 0.5),
        "out": np.asarray(jax.random.normal(k2, (HIDDEN, 32)) * 0.3),
    }
    return clients, params


@pytest.mark.parametrize("codec", [None, Q4], ids=["dense", "q4"])
@pytest.mark.parametrize("block", [1, 2])
def test_the_cohort_in_blocks_matches_it_whole(tokens, codec, block):
    import jax

    clients, params = tokens
    spec = {"fedavg": {"C": 1.0, "E": 2, "B": 4, "lr": 0.5},
            "codec": codec, "execution": {}}
    at = tuple(range(1, ROUNDS + 1))
    whole = reference.run_reference(_bigram_apply, clients, params, spec, 23,
                                    ROUNDS, at)
    parts = reference.run_reference(_bigram_apply, clients, params, spec, 23,
                                    ROUNDS, at, clients_per_block=block)
    np.testing.assert_allclose(parts["losses"], whole["losses"], rtol=1e-6)
    for r in at:
        for a, b in zip(jax.tree.leaves(parts["params"][r]),
                        jax.tree.leaves(whole["params"][r])):
            # float32 rounding of the weights (|w| < 4) and of the sums
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)
