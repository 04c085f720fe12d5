"""Quickstart: FederatedAveraging from a declarative paper preset.

    PYTHONPATH=src python examples/quickstart.py

Experiments are values: pick a preset from the ``specs/`` registry, adapt
it with ``dataclasses.replace``, and hand it to ``RoundEngine.from_spec``.
The spec JSON-round-trips (``spec.to_json()``), so the exact run is
shareable as a file — see specs/README.md for the full grid.
"""
import dataclasses

import jax

from repro.core import RoundEngine, make_eval_fn
from repro.data import make_image_classification
from repro.specs import PartitionSpec, get_spec
from repro.utils.compile_cache import use_compile_cache

use_compile_cache()

# 1. The paper's non-IID MNIST 2NN cell, scaled to quickstart size: 50
#    clients of ~2 classes each (pathological partition), C=20%/round.
spec = dataclasses.replace(
    get_spec("mnist_2nn_noniid"),
    partition=PartitionSpec("pathological_noniid", n_clients=50,
                            shards_per_client=2),
    fedavg=dataclasses.replace(get_spec("mnist_2nn_noniid").fedavg,
                               C=0.2, lr=0.05),
)

# 2. A federated dataset: the synthetic MNIST stand-in, split by the
#    spec's own partition description.
train, test, _ = make_image_classification(5000, 1000, seed=0, difficulty=1.5)
fed = spec.build_partition(labels=train.y)
clients = [(train.x[ix].reshape(len(ix), -1), train.y[ix])
           for ix in fed.client_indices]

# 3. Run rounds until 80% test accuracy. The spec names the model
#    (199,210-param 2NN); build it once — eval fn and engine share it —
#    and from_spec packs all 50 clients onto the device once, so every
#    round reuses ONE compiled executable.
model = spec.build_model()
params = model.init(jax.random.PRNGKey(spec.fedavg.seed))
ev = make_eval_fn(model.apply, test.x.reshape(len(test.x), -1), test.y)
engine = RoundEngine.from_spec(spec, clients, eval_fn=ev,
                               loss_fn=model.loss, init_params=params)
history = engine.run(30, eval_every=1, target_acc=0.80, verbose=True)
print("rounds to 80%:", history.rounds_to_target(0.80))
print("round executables compiled:", engine.num_compilations)
