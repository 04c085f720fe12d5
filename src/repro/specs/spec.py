"""ExperimentSpec: the declarative, JSON-round-trippable front door.

The paper's whole empirical program is a grid over a handful of declarative
knobs — Algorithm 1's (C, E, B), a model family, IID vs pathological
non-IID partition — plus, post-paper, a server strategy and an upload
codec. ``ExperimentSpec`` captures exactly that grid as one frozen value::

    spec = ExperimentSpec(
        name="mnist_2nn_noniid",
        model=ModelSpec("mnist_2nn"),
        partition=PartitionSpec("pathological_noniid", n_clients=100),
        fedavg=FedAvgConfig(C=0.1, E=5, B=10, lr=0.1),
        strategy=FedAvgM(momentum=0.9),
        codec=CodecSpec("quantize", bits=8),
        execution=ExecutionSpec(device_sampling=True, rounds_per_step=20),
    )
    engine = RoundEngine.from_spec(spec, client_data, eval_fn=ev)
    spec == ExperimentSpec.from_json(spec.to_json())   # always

Design rules:

- A spec describes an EXPERIMENT, not a dataset: ``client_data`` (and the
  eval fn) stay arguments to ``from_spec``. ``partition`` records how the
  data was split so the grid is enumerable from code
  (``scripts/build_experiments_md.py``); ``build_partition`` realizes it
  for callers that hold raw labels.
- Everything serializes: sub-specs are frozen dataclasses of plain scalars,
  strategies go through ``core.strategies.strategy_to_json``. The one
  unserializable engine knob — a callable ``lr`` schedule — raises at
  ``to_json`` time rather than silently dropping.
- The ``specs/`` registry (``repro.specs.presets``) holds the paper
  presets; new scenario PRs land as a preset or a strategy, not another
  ``RoundEngine.__init__`` kwarg.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Mapping, Optional

from repro.core.fedavg import FedAvgConfig
from repro.core.latency import LatencyModel
from repro.core.strategies import (
    FedAvg,
    ServerStrategy,
    strategy_from_json,
    strategy_to_json,
)


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """A registered model family plus its construction kwargs.

    ``kind`` indexes ``MODELS`` (the ``repro.models`` factories); ``kwargs``
    are passed to the factory (e.g. ``{"vocab_size": 70, "hidden": 128}``
    for ``char_lstm``). Kwargs that only resolve at data time (a corpus
    vocab) can be overridden via ``build(**overrides)``."""

    kind: str
    kwargs: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def build(self, **overrides):
        if self.kind not in MODELS:
            raise ValueError(
                f"unknown model kind {self.kind!r}; known: {sorted(MODELS)}"
            )
        return MODELS[self.kind](**{**dict(self.kwargs), **overrides})


@dataclasses.dataclass(frozen=True)
class PartitionSpec:
    """How the training set splits into clients (paper Section 3).

    kinds: ``iid`` | ``pathological_noniid`` (sort-by-label shards,
    ``shards_per_client`` each) | ``unbalanced`` (log-normal sizes) |
    ``dirichlet`` (label skew at ``alpha``)."""

    kind: str = "iid"
    n_clients: int = 100
    shards_per_client: int = 2
    alpha: float = 0.5
    seed: int = 0

    def build(self, labels=None, n_examples: Optional[int] = None):
        """Realize the partition: label-driven kinds need ``labels``,
        size-driven kinds need ``n_examples`` (inferred from labels when
        both make sense)."""
        from repro.data import partition as P

        if labels is not None and n_examples is None:
            n_examples = len(labels)
        if self.kind == "iid":
            return P.partition_iid(n_examples, self.n_clients, seed=self.seed)
        if self.kind == "pathological_noniid":
            return P.partition_pathological_noniid(
                labels, self.n_clients, self.shards_per_client, seed=self.seed
            )
        if self.kind == "unbalanced":
            return P.partition_unbalanced(
                n_examples, self.n_clients, seed=self.seed
            )
        if self.kind == "dirichlet":
            return P.partition_dirichlet(
                labels, self.n_clients, alpha=self.alpha, seed=self.seed
            )
        if self.kind == "natural":
            # Per-entity data that is ALREADY federated (Shakespeare roles):
            # nothing to build, the loader's grouping is the partition.
            raise ValueError(
                "'natural' partitions are defined by the dataset loader "
                "(one client per role/author); there is nothing to build"
            )
        raise ValueError(f"unknown partition kind {self.kind!r}")


@dataclasses.dataclass(frozen=True)
class CodecSpec:
    """Client-upload compression (docs/compression.md): ``identity`` |
    ``quantize`` (``bits``, ``chunk``) | ``mask`` / ``topk``
    (``keep_frac``) | ``lowrank`` (``rank``). ``None`` at the
    ExperimentSpec level means dense fp32 uploads (no codec path at
    all)."""

    kind: str
    bits: int = 8
    chunk: int = 512
    keep_frac: float = 0.1
    rank: int = 8

    def build(self):
        from repro.core import compression as C

        if self.kind == "identity":
            return C.identity_codec()
        if self.kind == "quantize":
            return C.quantize_codec(self.bits, chunk=self.chunk)
        if self.kind == "mask":
            return C.mask_codec(self.keep_frac)
        if self.kind == "topk":
            return C.topk_codec(self.keep_frac)
        if self.kind == "lowrank":
            return C.lowrank_codec(self.rank)
        raise ValueError(f"unknown codec kind {self.kind!r}")


@dataclasses.dataclass(frozen=True)
class TopologySpec:
    """The gossip communication graph (docs/topology.md): ``ring``
    (``degree``) | ``torus`` | ``smallworld`` (``degree``, ``rewire``,
    ``seed``) | ``random`` (``p``, ``seed``) | ``full``. ``None`` at the
    ExperimentSpec level means the star lane (centralized FedAvg — no
    gossip at all); a TopologySpec switches the engine to per-node
    replicas with Metropolis–Hastings neighbor mixing and requires
    ``fedavg.C == 1.0`` (every node gossips every round).

    Fields default to ``None`` = "use the kind's own default"; only
    explicitly-set fields reach the ``core.topology`` constructor, so a
    field foreign to the kind (e.g. ``p`` on a ring) fails loudly there
    instead of being silently dropped."""

    kind: str
    degree: Optional[int] = None
    rewire: Optional[float] = None
    p: Optional[float] = None
    seed: Optional[int] = None

    def build(self):
        from repro.core.topology import topology_from_json

        d: Dict[str, Any] = {"kind": self.kind}
        for f in ("degree", "rewire", "p", "seed"):
            v = getattr(self, f)
            if v is not None:
                d[f] = v
        return topology_from_json(d)


@dataclasses.dataclass(frozen=True)
class AsyncSpec:
    """The buffered-async axis (docs/engine.md "Asynchronous rounds"):
    the server applies an aggregate whenever ``buffer_k`` of
    ``concurrency`` in-flight updates arrive, under the straggler/dropout
    behavior of ``latency`` (a ``core.latency.LatencyModel``).
    ``concurrency=None`` uses the cohort size ``max(round(C*K), 1)``.
    ``buffer_k == concurrency`` with a zero LatencyModel is bit-for-bit
    the synchronous lane. Pair with ``strategy=FedAsync(...)`` for
    staleness-discounted aggregation; plain FedAvg ignores staleness
    (FedBuff-style uniform buffering)."""

    buffer_k: int = 4
    concurrency: Optional[int] = None
    latency: LatencyModel = LatencyModel()


@dataclasses.dataclass(frozen=True)
class ExecutionSpec:
    """HOW the experiment runs — the engine's execution lane, orthogonal to
    WHAT it computes. ``mesh_axes`` names the cohort-sharding client axis
    (None = unsharded; ``from_spec`` builds a one-axis mesh over all local
    devices, or accepts an explicit ``mesh=``); ``device_sampling`` +
    ``rounds_per_step`` select the superstep lane; ``interpret`` forces the
    Pallas interpreter (None auto-selects off-TPU); ``accum_dtype`` is the
    aggregation accumulator dtype as a numpy dtype string.

    Population backend (docs/engine.md "Population store & staging
    pipeline"): ``pool`` picks where the packed client population lives —
    ``"device"`` (the resident fast path), ``"streamed"`` (host/disk
    shards, cohorts staged per round), or ``"auto"`` (streamed only when
    the packed pool would exceed ``data.pool.device_pool_budget()``).
    ``pool_shard_clients`` is the streamed store's clients-per-shard;
    ``prefetch`` enables double-buffered staging (0 disables, 1 stages the
    next cohort/superstep chunk while the current one computes).

    ``clients_per_chunk`` (docs/engine.md "Chunked cohorts"): 1 runs the
    round's ClientUpdate one client at a time, keeping a running weighted
    sum of their deltas, for models whose m client copies do not fit at
    once (None: the whole cohort in one vmap). Dense uploads on the
    unsharded synchronous star lane only; ``ExperimentSpec`` refuses any
    other value or lane."""

    mesh_axes: Optional[str] = None
    device_sampling: bool = False
    rounds_per_step: Optional[int] = None
    interpret: Optional[bool] = None
    accum_dtype: str = "float32"
    pool: str = "auto"
    pool_shard_clients: int = 1024
    prefetch: int = 1
    clients_per_chunk: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """One cell of the paper grid, declaratively. See module docstring."""

    name: str
    model: ModelSpec
    partition: PartitionSpec
    fedavg: FedAvgConfig
    strategy: ServerStrategy = FedAvg()
    codec: Optional[CodecSpec] = None
    # None = star lane; a TopologySpec switches to the decentralized
    # gossip lane (per-node replicas + MH neighbor mixing).
    topology: Optional[TopologySpec] = None
    execution: ExecutionSpec = ExecutionSpec()
    # None = synchronous rounds; an AsyncSpec switches run() to the
    # buffered-async schedule (and carries the straggler model).
    async_spec: Optional[AsyncSpec] = None
    # Run-length defaults for scripts/benchmarks (run() args still win).
    rounds: int = 100
    target_acc: Optional[float] = None

    def __post_init__(self):
        c = self.execution.clients_per_chunk
        if c is None:
            return
        if c != 1:
            raise ValueError(
                f"spec {self.name!r} sets execution.clients_per_chunk={c}: "
                "only chunks of one client are implemented (1, or None for "
                "the whole cohort)"
            )
        taken = {
            "codec": self.codec,
            "topology": self.topology,
            "async_spec": self.async_spec,
            "execution.mesh_axes": self.execution.mesh_axes,
        }
        refused = sorted(k for k, v in taken.items() if v is not None)
        if refused:
            raise ValueError(
                f"spec {self.name!r} sets execution.clients_per_chunk={c} "
                f"with {refused}: the chunked cohort sums dense deltas of the "
                "unsharded synchronous star round — drop one or the other"
            )

    # -- builders ----------------------------------------------------------

    def build_model(self, **overrides):
        return self.model.build(**overrides)

    def build_partition(self, labels=None, n_examples: Optional[int] = None):
        return self.partition.build(labels=labels, n_examples=n_examples)

    def build_codec(self):
        return self.codec.build() if self.codec is not None else None

    def build_strategy(self) -> ServerStrategy:
        return self.strategy

    # -- json round-trip ---------------------------------------------------

    def to_json(self, indent: Optional[int] = None) -> str:
        if callable(self.fedavg.lr):
            raise ValueError(
                "ExperimentSpec.to_json cannot serialize a callable lr "
                "schedule — use a scalar lr (+ lr_decay), or keep schedule "
                "specs in code"
            )
        d = {
            "name": self.name,
            "model": dataclasses.asdict(self.model),
            "partition": dataclasses.asdict(self.partition),
            "fedavg": dataclasses.asdict(self.fedavg),
            "strategy": strategy_to_json(self.strategy),
            "codec": (
                dataclasses.asdict(self.codec)
                if self.codec is not None else None
            ),
            "topology": (
                dataclasses.asdict(self.topology)
                if self.topology is not None else None
            ),
            "execution": dataclasses.asdict(self.execution),
            "async_spec": (
                dataclasses.asdict(self.async_spec)
                if self.async_spec is not None else None
            ),
            "rounds": self.rounds,
            "target_acc": self.target_acc,
        }
        return json.dumps(d, indent=indent, sort_keys=True)

    @staticmethod
    def from_json(s: str) -> "ExperimentSpec":
        d = json.loads(s)
        model = ModelSpec(**d["model"])
        aspec = None
        if d.get("async_spec"):
            a = dict(d["async_spec"])
            aspec = AsyncSpec(
                latency=LatencyModel(**a.pop("latency", {})), **a
            )
        return ExperimentSpec(
            name=d["name"],
            model=model,
            partition=PartitionSpec(**d["partition"]),
            fedavg=FedAvgConfig(**d["fedavg"]),
            strategy=strategy_from_json(d["strategy"]),
            codec=CodecSpec(**d["codec"]) if d.get("codec") else None,
            topology=(
                TopologySpec(**d["topology"]) if d.get("topology") else None
            ),
            execution=ExecutionSpec(**d.get("execution", {})),
            async_spec=aspec,
            rounds=int(d.get("rounds", 100)),
            target_acc=d.get("target_acc"),
        )


def _models_registry() -> Dict[str, Any]:
    from repro.models import (
        char_lstm,
        cifar_cnn,
        mnist_2nn,
        mnist_cnn,
        word_lstm,
    )
    from repro.models.transformer import transformer

    return {
        "mnist_2nn": mnist_2nn,
        "mnist_cnn": mnist_cnn,
        "cifar_cnn": cifar_cnn,
        "char_lstm": char_lstm,
        "word_lstm": word_lstm,
        # A repo preset by name (repro.configs), with field overrides.
        "transformer": transformer,
    }


MODELS: Dict[str, Any] = _models_registry()
