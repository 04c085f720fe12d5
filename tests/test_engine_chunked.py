"""The chunked cohort (``ExecutionSpec.clients_per_chunk=1``): the same
round as the whole-cohort vmap within float32 summation order, refused
where its form does not apply, and absent from the default round program,
which lowers to the same text as before the lane existed."""
import hashlib
import json
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from repro.core import RoundEngine  # noqa: E402
from repro.specs import ExperimentSpec  # noqa: E402


def _spec(clients_per_chunk=None, **over):
    d = {
        "name": "chunked", "model": {"kind": "mnist_2nn", "kwargs": {}},
        "partition": {"kind": "iid", "n_clients": 8, "seed": 1},
        "fedavg": {"C": 0.5, "E": 2, "B": 10, "lr": 0.1, "seed": 1},
        "strategy": {"kind": "fedavg"}, "codec": None,
        "execution": {"clients_per_chunk": clients_per_chunk},
    }
    d.update(over)
    return ExperimentSpec.from_json(json.dumps(d))


def _clients(n=8, per=30, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=(per, 784)).astype(np.float32),
             rng.integers(0, 10, size=per).astype(np.int32))
            for _ in range(n)]


def test_chunked_round_matches_the_whole_cohort():
    clients = _clients()
    whole = RoundEngine.from_spec(_spec(None), clients)
    chunked = RoundEngine.from_spec(_spec(1), clients)
    whole.run(2)
    chunked.run(2)
    for a, b in zip(jax.tree.leaves(whole.params),
                    jax.tree.leaves(chunked.params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        [r.train_loss for r in whole.history.records],
        [r.train_loss for r in chunked.history.records], rtol=1e-6)
    assert chunked.num_compilations == 1
    assert whole.history.records[0].counters is None


@pytest.mark.parametrize("over", [
    {"codec": {"kind": "quantize", "bits": 8}},
    {"topology": {"kind": "ring"}},
    {"async_spec": {"buffer_k": 2}},
    {"execution": {"clients_per_chunk": 1, "mesh_axes": "clients"}},
], ids=["codec", "gossip", "async", "mesh"])
def test_chunked_cohort_refuses_other_lanes(over):
    with pytest.raises(ValueError, match="clients_per_chunk"):
        _spec(1, **over)


@pytest.mark.parametrize("c", [0, 2, 4])
def test_only_chunks_of_one_client_are_taken(c):
    with pytest.raises(ValueError, match="chunks of one client"):
        _spec(c)


# SHA-256 of the three benchmark cells' round programs (a population of 20
# clients x 60 examples, the cells' traffic), lowered on the CPU, without
# source locations and result names: the program before the chunked lane.
DEFAULT_ROUND_TEXT = {
    "cnn_fedavg_paper": "91e2a549c239ae5c",
    "2nn_fedsgd_q4_m100": "4b6eb27e222c2f31",
    "2nn_fedsgd_rounds": "673767ff7dcfc58a",
}


def _round_text_digest(cell_name):
    from benchmarks.chip import harness, population

    cell = harness.load_cell(cell_name)
    cfg = dict(cell.config, clients=20, examples_per_client=60)
    spec = harness.build_spec(cell, 7)
    clients = population.make_clients(cfg, cell.spec_overrides["partition"], 7)
    eng = RoundEngine.from_spec(
        spec, clients, init_params=cell.model.init(jax.random.PRNGKey(0), cfg))
    text = eng.lower_round(spec.execution.rounds_per_step or 1).as_text()
    text = re.sub(r"\s*loc\([^\n]*?\)(?=\n|$)", "", text)
    text = "\n".join(line for line in text.splitlines()
                     if not line.startswith("#loc"))
    text = re.sub(r" \{jax.result_info = \"[^\"]*\"\}", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("cell", list(DEFAULT_ROUND_TEXT))
def test_default_round_program_is_unchanged(cell):
    assert _round_text_digest(cell) == DEFAULT_ROUND_TEXT[cell]
