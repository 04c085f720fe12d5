"""Unified RoundEngine: the statically-shaped FedAvg round pipeline.

One round, one executable::

      pack (once, host)          every round (device, jitted once)
    ┌──────────────────┐   ┌───────────────────────────────────────────┐
    │ pack_clients     │   │ sample/permute   per-(client, epoch) perm │
    │  (K, n_pad, F)   │──▶│ gather rows      x[ids*n_pad + perm]      │
    │  counts, steps,  │   │ batch            -> (m, E*spe, B, ...)    │
    │  shape buckets   │   │ vmapped ClientUpdate (masked SGD scan)    │
    │                  │   │ Pallas fedavg_aggregate over (m, N)       │
    └──────────────────┘   │ broadcast new global params               │
                           └───────────────────────────────────────────┘

Why: communication rounds are the paper's scarce resource, so the per-round
hot loop must not pay host-side batch assembly or shape-driven recompiles.
The legacy path rebuilt ragged numpy stacks every round with round-varying
``(max_steps, max_b)``, re-jitting ``fedavg_round`` whenever the sampled
cohort's shapes changed. Here the whole population is packed ONCE into
device-resident arrays (``data.batching.pack_clients``; power-of-two shape
buckets give the padding accounting) and each round is a pure on-device
gather + permutation, so ``run(n_rounds)`` reuses a single compiled
executable — verified by the jit-cache-stats test in tests/test_engine.py.

The server step routes through the Pallas ``fedavg_aggregate`` kernel via
the ``tree_ravel_stacked``/``tree_unravel`` adapters (fp32 accumulation;
``interpret=True`` fallback on non-TPU backends).

``round_step`` protocol
-----------------------
Both this engine (:func:`build_simulation_round_step`) and the production
mesh path (``core.local_sgd.as_round_step``) expose the same callable
shape::

    round_step(state: RoundState, batch: RoundBatch) -> (RoundState, metrics)

so benchmarks, examples and the compression codecs target one API instead
of two divergent ones. ``core.simulation.FederatedTrainer`` is now a thin
wrapper over :class:`RoundEngine` (see docs/engine.md for migration notes).

Cohort sharding
---------------
``RoundEngine(mesh=..., client_axis=...)`` runs the identical round body
inside a ``shard_map`` over a named client axis: m/D clients per device,
pools and params replicated, cohorts padded with zero-weight ghost clients
(``data.batching.pad_cohort``), and the Pallas aggregation in partial-sum
mode finished by one ``psum`` (``ops.sharded_fedavg_aggregate``). All
per-client randomness is keyed by GLOBAL cohort slot, so sharded and
unsharded runs match round for round (tests/test_engine_sharded.py).

Supersteps
----------
The third and final layer of the static-shape pipeline (PR 1 fused the
round body, PR 2 the codec, this fuses the LOOP): with
``device_sampling=True``, ``run(..., rounds_per_step=R)`` compiles a
``jax.lax.scan`` over R full rounds — on-device cohort draw
(``fedavg.sample_clients_device``), batch assembly, ClientUpdate,
aggregation — into ONE buffer-donating executable, so the host pays one
dispatch and one sync per R rounds instead of per round. The cohort PRNG
key rides in the scan carry and is persisted by ``save``/``restore``; the
lr schedule is precomputed as an (R,) array scanned alongside. Composes
with ``codec=`` (the scan wraps the compressed round step) and ``mesh=``
(the scan runs INSIDE the ``shard_map``, so aggregation stays psum-finished
per round). See docs/engine.md "Supersteps".
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import (
    Any,
    Callable,
    Dict,
    List,
    NamedTuple,
    Optional,
    Protocol,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fedavg import (
    FedAvgConfig,
    client_update,
    counter_sums,
    masked_weighted_loss,
    sample_clients,
    sample_clients_device,
    server_aggregate,
)
from repro.core.strategies import FedAvg, ServerStrategy, resolve_strategy
from repro.core.topology import resolve_topology
from repro.analysis.guards import sanctioned_staging
from repro.data.batching import (
    estimate_pool_nbytes,
    pack_clients,
    pad_cohort,
    pad_cohort_device,
)
from repro.data.pool import StreamedClientPool, device_pool_budget
from repro.kernels.gossip_mix import gossip_mix
from repro.kernels.ops import default_interpret
from repro.utils.tree import tree_ravel_stacked, tree_unravel


# ---------------------------------------------------------------------------
# round_step protocol
# ---------------------------------------------------------------------------

class RoundState(NamedTuple):
    """Everything a round mutates. Simulation uses only ``params``; the
    production path threads per-group inner optimizer state and the
    FedOpt/DiLoCo outer optimizer state."""

    params: Any
    inner_state: Any = None
    outer_state: Any = None


class RoundBatch(NamedTuple):
    """One round's worth of client data, implementation-layout pytree.

    data:           simulation: leaves (m, n_steps, B, ...);
                    production: leaves (H, G, ...).
    step_mask:      (m, n_steps) 0/1 — padded steps are no-ops (simulation
                    only; None on the production path).
    client_weights: (m,) or (G,) RAW example counts n_k. Normalization
                    happens exactly once, inside ``server_aggregate``.
    lr:             client learning rate for this round (None if the inner
                    optimizer owns it).
    key:            PRNG key for stochastic codecs (compression path).
    """

    data: Any
    step_mask: Optional[jnp.ndarray]
    client_weights: jnp.ndarray
    lr: Any = None
    key: Any = None


class RoundStep(Protocol):
    """The single per-round contract every FedAvg implementation exposes."""

    def __call__(
        self, state: RoundState, batch: RoundBatch
    ) -> Tuple[RoundState, Dict[str, jnp.ndarray]]: ...


def build_simulation_round_step(
    loss_fn: Callable,
    *,
    interpret: Optional[bool] = None,
    accum_dtype=jnp.float32,
    axis_name: Optional[str] = None,
    strategy: Optional[ServerStrategy] = None,
    clients_per_chunk: Optional[int] = None,
) -> RoundStep:
    """RoundStep over explicit (m, n_steps, B, ...) batches: vmapped
    ClientUpdate then the Pallas-backed server aggregation. This is the
    compiled core of :class:`RoundEngine` and the reference implementation
    of the protocol.

    ``axis_name``: when the round body runs inside a ``shard_map`` over a
    named client axis, each shard sees only its (m/D, ...) cohort slice;
    aggregation and the loss reduction then finish with a ``psum`` over
    that axis (``server_aggregate``'s partial-sum mode), so every shard
    returns the identical new global params.

    ``strategy``: a ``core.strategies.ServerStrategy``. When given, the
    round aggregates the fp32 client DELTAS (w_k - w_t) through the same
    Pallas kernel and hands the weighted-mean delta to ``strategy.apply``
    (state in ``RoundState.outer_state``) — applied after any psum, so the
    sharded and unsharded rounds step identically. ``None`` keeps the
    pre-strategy inline form (aggregate the client params directly; the
    identity update with no delta round-trip) — bit-for-bit the historical
    behavior, and the baseline for the ``round_engine_strategy`` overhead
    benchmark.

    ``clients_per_chunk=1``: the cohort runs one client at a time, a
    ``lax.scan`` over its m clients, for models whose m client copies do
    not fit at once (docs/engine.md "Chunked cohorts"). The carry holds a
    running ``accum_dtype`` sum of the example-share-weighted deltas per
    leaf, so no (m, N) stack is ever built; the mean delta then goes to
    ``strategy`` (FedAvg where None). ``ExperimentSpec`` admits it on the
    unsharded lane only.

    The metrics are ``{"loss"}`` plus, for a model whose loss reports
    ``"counters"`` in its aux dict, each counter's mean over the cohort's
    real steps."""
    interpret = default_interpret() if interpret is None else interpret
    if clients_per_chunk is not None:
        return partial(_chunked_round_step, loss_fn, strategy or FedAvg(),
                       accum_dtype)

    def round_step(state: RoundState, rb: RoundBatch):
        with jax.named_scope("fedavg.client_update"):
            upd = jax.vmap(
                lambda b, msk: client_update(loss_fn, state.params, b, msk,
                                             rb.lr, with_counters=True)
            )
            client_params, losses, counters = upd(rb.data, rb.step_mask)
            metrics = {"loss": masked_weighted_loss(
                losses, rb.step_mask, rb.client_weights, axis_name=axis_name)}
            if counters:
                metrics.update(_counter_means(
                    counter_sums(counters, rb.step_mask),
                    jnp.sum(rb.step_mask), axis_name))
        if strategy is None:
            with jax.named_scope("fedavg.aggregate"):
                new_params = server_aggregate(
                    client_params,
                    rb.client_weights,
                    interpret=interpret,
                    accum_dtype=accum_dtype,
                    axis_name=axis_name,
                )
            return state._replace(params=new_params), metrics
        with jax.named_scope("fedavg.aggregate"):
            deltas = jax.tree.map(
                lambda c, p: (c - p).astype(jnp.float32),
                client_params, state.params,
            )
            agg_delta = server_aggregate(
                deltas,
                rb.client_weights,
                interpret=interpret,
                accum_dtype=accum_dtype,
                axis_name=axis_name,
            )
        with jax.named_scope("fedavg.apply"):
            outer, new_params = strategy.apply(
                state.outer_state, state.params, agg_delta
            )
        return state._replace(params=new_params, outer_state=outer), metrics

    return round_step


def _counter_means(sums, n_steps, axis_name=None):
    """Counter sums over real steps -> means a step, finished over the
    client axis where the cohort is sharded."""
    if axis_name is not None:
        sums = jax.lax.psum(sums, axis_name)
        n_steps = jax.lax.psum(n_steps, axis_name)
    return jax.tree.map(lambda s: s / jnp.maximum(n_steps, 1.0), sums)


def _chunked_round_step(loss_fn, strategy, accum_dtype, state: RoundState,
                        rb: RoundBatch):
    """The round with the cohort run one client at a time (see
    :func:`build_simulation_round_step`). Step ``j`` of the scan trains
    cohort slot ``j`` on the batches assembled under that slot's keys, so
    the round trains on the same batches as the vmapped one. The client is
    unbatched: a model with layers that have no batching rule (grouped
    matmuls) runs too, and no stacked copy of the weights is made. The
    weighted mean delta is ``sum_k (n_k / n) * delta_k`` summed client by
    client in ``accum_dtype``; the loss metric is ``masked_weighted_loss``'s
    over the per-client losses."""
    share = rb.client_weights / jnp.sum(rb.client_weights)

    def client(acc, inp):
        data, mask, w = inp
        with jax.named_scope("fedavg.client_update"):
            cp, losses, counters = client_update(
                loss_fn, state.params, data, mask, rb.lr, with_counters=True)
            sums = counter_sums(
                jax.tree.map(lambda a: a[None], counters), mask[None])
        with jax.named_scope("fedavg.aggregate"):
            acc = jax.tree.map(
                lambda a, c, p: a + w.astype(accum_dtype)
                * (c - p).astype(accum_dtype),
                acc, cp, state.params)
        return acc, (losses, sums)

    acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype),
                        state.params)
    agg_delta, (losses, sums) = jax.lax.scan(
        client, acc0, (rb.data, rb.step_mask, share))
    with jax.named_scope("fedavg.client_update"):
        metrics = {"loss": masked_weighted_loss(
            losses, rb.step_mask, rb.client_weights)}
        if sums:
            metrics.update(_counter_means(
                jax.tree.map(lambda s: jnp.sum(s, axis=0), sums),
                jnp.sum(rb.step_mask)))
    with jax.named_scope("fedavg.apply"):
        outer, new_params = strategy.apply(
            state.outer_state, state.params, agg_delta
        )
    return state._replace(params=new_params, outer_state=outer), metrics


# ---------------------------------------------------------------------------
# history (moved from core.simulation; re-exported there for compatibility)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RoundRecord:
    round: int
    train_loss: float
    test_acc: Optional[float] = None
    test_loss: Optional[float] = None
    wall_s: float = 0.0
    # Simulated duration of this round/apply under the scheduler's
    # LatencyModel (0.0 when no straggler simulation is active): sync
    # rounds are charged the barrier (slowest observed arrival), async
    # applies the gap between consecutive buffer fills.
    sim_s: float = 0.0
    # Gossip lane only: post-mix consensus distance — the RMS over nodes
    # of each replica's L2 distance to the node-mean parameter vector
    # (docs/topology.md). None on the star lanes.
    consensus: Optional[float] = None
    # The model's counters (its loss's aux "counters"), each the cohort's
    # mean over its real steps; None for a model that reports none.
    counters: Optional[Dict[str, List[float]]] = None


def record_counters(metrics) -> Optional[Dict[str, List[float]]]:
    """A round's host metrics less the loss, as lists for the record."""
    out = {k: np.atleast_1d(np.asarray(v, np.float64)).tolist()
           for k, v in metrics.items() if k != "loss"}
    return out or None


def _host_metrics(metrics) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in jax.device_get(metrics).items()}


def _monotone_crossing(curve, target: float) -> Optional[float]:
    """First crossing of ``target`` on a best-so-far-monotone curve of
    (x, acc) points, linearly interpolated between evaluations. If the
    FIRST evaluated point already crosses there is nothing to interpolate
    from — return its x (interpolating from a fictitious (0, 0.0) point
    would under-report). Shared by rounds-to-target (x = round index) and
    sim-time-to-target (x = cumulative simulated seconds)."""
    if not curve:
        return None
    best = -np.inf
    mono = []
    for x, acc in curve:
        best = max(best, acc)
        mono.append((x, best))
    prev: Optional[Tuple[float, float]] = None
    for x, acc in mono:
        if acc >= target:
            if prev is None or acc == prev[1]:
                return float(x)
            prev_x, prev_a = prev
            frac = (target - prev_a) / (acc - prev_a)
            return float(prev_x + frac * (x - prev_x))
        prev = (x, acc)
    return None


@dataclasses.dataclass
class History:
    records: List[RoundRecord] = dataclasses.field(default_factory=list)

    def accuracy_curve(self) -> List[Tuple[int, float]]:
        return [(r.round, r.test_acc) for r in self.records if r.test_acc is not None]

    def rounds_to_target(self, target: float) -> Optional[float]:
        """Paper's metric: make the curve monotone (best-so-far), then find
        the first crossing of ``target`` with linear interpolation between
        evaluated rounds."""
        return _monotone_crossing(self.accuracy_curve(), target)

    def sim_time_to_target(self, target: float) -> Optional[float]:
        """Simulated wall-clock seconds to first cross ``target`` — the
        metric that separates sync from buffered-async under stragglers
        (rounds-to-target can prefer sync while every sync round waits on
        the cohort's slowest phone). x-axis: cumulative ``sim_s``."""
        t, curve = 0.0, []
        for r in self.records:
            t += r.sim_s
            if r.test_acc is not None:
                curve.append((t, r.test_acc))
        return _monotone_crossing(curve, target)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class RoundEngine:
    """Algorithm 1 over a packed client population, one executable per run.

    Construction packs ``client_data`` once (see module docstring); each
    ``round()`` samples a cohort host-side (cheap: m integers) and runs the
    fully on-device gather → permute → ClientUpdate → Pallas-aggregate
    pipeline under a single ``jax.jit``. ``num_compilations`` exposes the
    jit cache size so tests can assert the static-shape claim.

    ``codec=`` swaps the server step for the compressed-upload pipeline
    (``core.compression.build_compressed_round_step``) INSIDE the same
    single executable: vmapped encode over the raveled client deltas, fused
    decode+aggregate (the quantize codec's Pallas ``quantized_aggregate``
    kernel), per-round codec keys threaded from the engine RNG. The
    static-shape/compile-count guarantees are identical to the plain path —
    asserted by tests/test_compression.py's compile-count test.

    ``strategy=`` swaps the server update rule (``core.strategies``):
    the round aggregates the fp32 client deltas and the strategy consumes
    the weighted-mean delta inside the same executable — FedAvg (identity,
    the default), FedSGD (the named preset; vetoes non-E=1/B=None configs),
    FedAvgM (server momentum; its velocity tree rides in
    ``RoundState.outer_state``, the superstep scan carry, and
    ``save``/``restore``). Prefer constructing through
    :meth:`from_spec` — the declarative ``ExperimentSpec`` front door —
    over stacking constructor kwargs.

    Cost model: device memory is K x (pool of the LARGEST client) and each
    round scans the largest client's step count (smaller clients mask the
    tail). That trade buys zero recompiles and zero host assembly; for
    populations with extreme size skew (one client 50x the median) the
    padding dominates and the legacy host path
    (``simulation.build_round_batch_host`` + ``fedavg_round``) can be the
    better tool — ``packed.overhead()`` quantifies the ratio.

    Cohort sharding (``mesh=``): the paper's regime is many clients per
    round and cheap local compute, so the vmapped cohort is embarrassingly
    parallel over clients. Passing a 1-axis ``jax.sharding.Mesh`` (see
    ``launch.mesh.make_client_mesh``) wraps the identical round body in a
    ``shard_map`` over ``client_axis``: the packed population and global
    params replicate, the sampled cohort splits m/D clients per device, and
    the Pallas aggregation runs in partial-sum mode finished by one psum
    (``ops.sharded_fedavg_aggregate`` / the codec analogue). Cohorts are
    padded to a multiple of D with zero-weight ghost clients
    (``data.batching.pad_cohort``), and all per-client randomness is keyed
    by GLOBAL cohort slot, so a sharded run matches the unsharded run round
    for round to fp32 tolerance — still within the same single executable
    (see docs/engine.md).
    """

    def __init__(
        self,
        loss_fn: Callable,
        init_params,
        client_data: Sequence[Tuple[np.ndarray, Optional[np.ndarray]]],
        cfg: FedAvgConfig,
        eval_fn: Optional[Callable] = None,
        *,
        codec=None,
        strategy=None,
        topology=None,
        interpret: Optional[bool] = None,
        accum_dtype=jnp.float32,
        mesh=None,
        client_axis: str = "clients",
        device_sampling: bool = False,
        rounds_per_step: Optional[int] = None,
        latency=None,
        async_config=None,
        pool="auto",
        pool_shard_clients: int = 1024,
        pool_dir=None,
        prefetch: int = 1,
        clients_per_chunk: Optional[int] = None,
    ):
        self.loss_fn = loss_fn
        # Private copy: the round executables donate the params buffer
        # (in-place server update), which would otherwise delete the
        # caller's init_params array out from under them.
        self.params = jax.tree.map(jnp.array, init_params)
        self.cfg = cfg
        self.eval_fn = eval_fn
        self.rng = np.random.default_rng(cfg.seed)
        # The server update rule, pluggable (core.strategies). None/str
        # resolve to registry instances; FedSGD-style presets get to veto
        # an inconsistent client config before anything compiles.
        self.strategy = resolve_strategy(strategy)
        self.strategy.validate_cfg(cfg)
        self.outer_state = self.strategy.init_state(self.params)
        # -- decentralized gossip lane (core.topology, docs/topology.md) --
        # topology= switches the engine from the star reduce to per-node
        # replicas + a sparse neighbor-mixing step. The lane is its own
        # executable pair, so the star-only features are refused up front
        # with the fix named, matching the streamed lane's refusal style.
        self.topology = None if topology is None else resolve_topology(topology)
        if self.topology is not None:
            if codec is not None:
                raise ValueError(
                    "topology= is incompatible with codec=: gossip mixing "
                    "replaces the server aggregate entirely, so there is no "
                    "upload path to compress — drop the codec, or run the "
                    "star lane"
                )
            if mesh is not None or device_sampling:
                raise ValueError(
                    "topology= is incompatible with mesh=/device_sampling="
                    "True: the gossip lane runs every node every round (no "
                    "cohort draw to shard or fuse) — construct the engine "
                    "without them"
                )
            if latency is not None or async_config is not None:
                raise ValueError(
                    "topology= is incompatible with latency=/async_config=: "
                    "the straggler and buffered-async schedulers dispatch "
                    "against the star executables — gossip rounds are a "
                    "synchronous mixing schedule (ROADMAP follow-on)"
                )
            if not (isinstance(pool, str) and pool in ("auto", "device")):
                raise ValueError(
                    "topology= needs the device-resident pool: every node "
                    "trains every round, so streamed cohort staging would "
                    "re-stage the whole population each round — use "
                    "pool='device'"
                )
            pool = "device"
            if not isinstance(self.strategy, FedAvg):
                raise ValueError(
                    f"topology= is incompatible with the "
                    f"{self.strategy.kind!r} server strategy: there is no "
                    "server — the Metropolis–Hastings mixing step IS the "
                    "update rule. Use FedAvg/FedSGD (identity)"
                )
            if float(cfg.C) != 1.0:
                raise ValueError(
                    f"topology= requires cfg.C == 1.0 (every node gossips "
                    f"every round; there is no cohort sampling), got "
                    f"C={cfg.C}"
                )
        # from_spec threads execution.rounds_per_step here; run() uses it
        # whenever its own rounds_per_step argument is None.
        self.default_rounds_per_step = rounds_per_step
        # Cohort/stream state for the two sampling modes. The numpy rng is
        # the legacy per-round stream; sample_key seeds the on-device
        # stream (device_sampling=True and all superstep runs) — a NEW
        # stream: same distribution, different realizations for the same
        # seed (docs/engine.md). Both are persisted by save/restore.
        self.device_sampling = bool(device_sampling)
        self.sample_key = jax.random.PRNGKey(cfg.seed)
        self.round_idx = 0
        self.history = History()
        self.codec = codec
        self.interpret = default_interpret() if interpret is None else interpret
        self.accum_dtype = accum_dtype
        self.mesh = mesh
        self.client_axis = client_axis
        if mesh is not None and client_axis not in mesh.axis_names:
            raise ValueError(
                f"client_axis {client_axis!r} not in mesh axes {mesh.axis_names}"
            )
        self._shards = int(mesh.shape[client_axis]) if mesh is not None else 1

        # -- population backend (docs/engine.md "Population store") --------
        # "device" is the historical fast path: pack once, gather on
        # device. "streamed" keeps the population on host disk
        # (data.pool.StreamedClientPool) and stages each sampled cohort
        # host->device through sanctioned_staging, double-buffered so
        # cohort R+1 stages while R computes. "auto" picks by comparing the
        # packed-pool estimate against device_pool_budget().
        self._prefetch_depth = int(prefetch)
        if self._prefetch_depth < 0:
            raise ValueError(f"prefetch must be >= 0, got {prefetch}")
        self._prefetched = None
        spool = None
        if isinstance(pool, StreamedClientPool):
            spool, pool_kind = pool, "streamed"
        elif pool in ("auto", "device", "streamed"):
            pool_kind = pool
        else:
            raise ValueError(
                "pool must be 'auto', 'device', 'streamed', or a "
                f"StreamedClientPool instance, got {pool!r}"
            )
        if pool_kind == "auto":
            if not len(client_data):
                pool_kind = "device"  # pack_clients owns the empty error
            else:
                x0, y0 = client_data[0]
                est = estimate_pool_nbytes(
                    np.asarray([len(x) for x, _ in client_data], np.int64),
                    cfg.B, x0.shape[1:], x0.dtype.itemsize,
                    y0.shape[1:] if y0 is not None else None,
                    y0.dtype.itemsize if y0 is not None else 0,
                )
                pool_kind = (
                    "device" if est <= device_pool_budget() else "streamed"
                )
        self.pool_kind = pool_kind
        if pool_kind == "streamed":
            if mesh is not None:
                raise ValueError(
                    "pool='streamed' is incompatible with mesh= cohort "
                    "sharding: streamed cohorts are staged host->device "
                    "per round, while shard_map needs the device-resident "
                    "pool replicated across the mesh — shard with "
                    "pool='device', or stream unsharded"
                )
            if latency is not None or async_config is not None:
                raise ValueError(
                    "pool='streamed' supports the sync round and superstep "
                    "lanes only: the latency/async schedulers dispatch "
                    "against the device-resident pool directly"
                )
            if spool is None:
                spool = StreamedClientPool.build(
                    client_data, cfg.B,
                    shard_clients=pool_shard_clients, root=pool_dir,
                )
            elif spool.requested_batch_size != cfg.B:
                raise ValueError(
                    "streamed pool was built with batch_size="
                    f"{spool.requested_batch_size} but cfg.B={cfg.B} — its "
                    "step schedule would not match this engine's"
                )
            self.pool = spool
            self.packed = spool.meta
            self._x = self._y = self._counts = self._spe = None
            self._feature_shape = spool.feature_shape
            self._rep = None
            self._m = max(int(round(cfg.C * spool.num_clients)), 1)
            shape_kw = dict(
                E=cfg.E,
                spe=self.packed.max_real_steps_per_epoch,
                B=self.packed.batch_size,
                feature_shape=self._feature_shape,
                has_labels=spool.has_labels,
                codec=codec,
                strategy=self.strategy,
                interpret=self.interpret,
                accum_dtype=jnp.dtype(accum_dtype),
                clients_per_chunk=clients_per_chunk,
            )
            # Donate the params/strategy carries like the device lane.
            # (The staged cohort buffers are dead after their round too,
            # but no output shares their shape, so donating them buys
            # nothing — XLA frees them at the end of the executable.)
            self._staged_round_jit = jax.jit(
                partial(_engine_round_staged, loss_fn, **shape_kw),
                donate_argnums=(0, 1),
            )
            self._staged_superstep_jit = jax.jit(
                partial(_engine_superstep_staged, loss_fn, **shape_kw),
                donate_argnums=(0, 1),
            )
            self._executables = [
                self._staged_round_jit, self._staged_superstep_jit
            ]
            self.latency = None
            self.async_config = None
            return
        self.pool = None

        # Budget-guarded: a population too large for the device pool fails
        # HERE with a message naming pool='streamed', not as an opaque
        # XLA OOM after minutes of packing (REPRO_DEVICE_POOL_BUDGET
        # overrides the budget).
        packed = pack_clients(client_data, cfg.B,
                              max_bytes=device_pool_budget())
        # Stored as (K, n_pad, F) rows (docs/engine.md "Pool layout"); the
        # assembled batch gets its feature shape back.
        self._feature_shape = tuple(int(d) for d in packed.x.shape[2:])
        self._x = jnp.asarray(_as_rows(packed.x))
        self._y = jnp.asarray(packed.y) if packed.y is not None else None
        self._counts = jnp.asarray(packed.counts)
        self._spe = jnp.asarray(packed.steps_per_epoch)
        if mesh is not None:
            # Replicate the packed pools and the global params across the
            # client mesh up front. Without this the first round's inputs
            # are single-device and every later round's are mesh-replicated
            # (shard_map outputs), costing a second executable and a
            # first-round relayout.
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            rep = NamedSharding(mesh, P())
            self._rep = rep
            self.params = jax.device_put(self.params, rep)
            self.outer_state = jax.device_put(self.outer_state, rep)
            self.sample_key = jax.device_put(self.sample_key, rep)
            self._x = jax.device_put(self._x, rep)
            if self._y is not None:
                self._y = jax.device_put(self._y, rep)
            self._counts = jax.device_put(self._counts, rep)
            self._spe = jax.device_put(self._spe, rep)
        else:
            self._rep = None
        # Keep only the metadata; the numpy pool would otherwise double
        # peak memory for the whole run after its device upload.
        self.packed = packed._replace(x=None, y=None)
        # m is a pure function of (K, C), so cohort shapes are static; the
        # device sampler needs it as a Python int.
        self._m = max(int(round(cfg.C * packed.num_clients)), 1)
        shape_kw = dict(
            E=cfg.E,
            spe=packed.max_real_steps_per_epoch,
            B=packed.batch_size,
            feature_shape=self._feature_shape,
            has_labels=self._y is not None,
            codec=codec,
            strategy=self.strategy,
            interpret=self.interpret,
            accum_dtype=jnp.dtype(accum_dtype),
            axis_name=client_axis if mesh is not None else None,
            clients_per_chunk=clients_per_chunk,
        )
        body = partial(_engine_round, loss_fn, **shape_kw)
        sbody = partial(
            _engine_superstep, loss_fn,
            K=packed.num_clients, m=self._m, shards=self._shards, **shape_kw,
        )
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            # Everything replicates except the cohort: ids/valid split
            # m/D-per-device along the client axis; the psum-finished
            # aggregation makes the outputs replicated by construction
            # (check_vma can't see through pallas_call, so it's off). The
            # strategy state replicates like the params: strategy.apply
            # consumes the post-psum (already replicated) delta, so every
            # shard steps the identical outer state.
            body = jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), P(), P(), P(), P(), P(),
                          P(client_axis), P(client_axis), P(), P()),
                out_specs=(P(), P(), P()),
                check_vma=False,
            )
            # Supersteps scan INSIDE the shard_map: every input (pools,
            # params, strategy state, key, lr schedule) is replicated, each
            # shard slices its own m/D cohort chunk per round from the
            # replicated on-device draw, and the per-round psum keeps the
            # aggregation exactly as in the per-round path.
            sbody = jax.shard_map(
                sbody,
                mesh=mesh,
                in_specs=(P(),) * 8,
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            )
        # Buffer donation: params and the strategy state are dead the
        # moment a round returns their successors (same shapes/dtypes), so
        # the server update is in-place instead of allocating fresh trees
        # every round. The superstep additionally donates the scan carry's
        # PRNG key. The undonated bodies stay reachable for tests/benchmarks.
        self._round_body = body
        self._superstep_body = sbody
        self._round_jit = jax.jit(body, donate_argnums=(0, 1))
        self._superstep_jit = jax.jit(sbody, donate_argnums=(0, 1, 2))
        self._executables = [self._round_jit, self._superstep_jit]

        if self.topology is not None:
            # One node per packed client: build the static mixing plan
            # (the Topology validates its (kind, n_nodes) fit here, before
            # anything compiles) and broadcast the init params into the
            # (n_nodes, ...) replica stack — consensus distance 0 at round
            # 0. self.params IS the replica stack on this lane; use
            # consensus_params() for evaluation/analysis.
            n_nodes = packed.num_clients
            self.plan = self.topology.build(n_nodes)
            self._mix_idx = jnp.asarray(self.plan.idx)
            self._mix_w = jnp.asarray(self.plan.weight)
            self.params = jax.tree.map(
                lambda p: jnp.tile(p[None], (n_nodes,) + (1,) * p.ndim),
                self.params,
            )
            gkw = dict(
                E=cfg.E,
                spe=packed.max_real_steps_per_epoch,
                B=packed.batch_size,
                feature_shape=self._feature_shape,
                has_labels=self._y is not None,
                interpret=self.interpret,
                accum_dtype=jnp.dtype(accum_dtype),
            )
            # Same two-executable budget as the star lanes: one fused
            # round, one scan-of-R superstep (the eager round and the scan
            # body advance the key stream identically, so superstep(R) ==
            # R x round() — tests/test_engine_gossip.py).
            self._gossip_round_jit = jax.jit(
                partial(_engine_gossip_round, loss_fn, **gkw),
                donate_argnums=(0,),
            )
            self._gossip_superstep_jit = jax.jit(
                partial(_engine_gossip_superstep, loss_fn, **gkw),
                donate_argnums=(0, 1),
            )
            self._executables = [
                self._gossip_round_jit, self._gossip_superstep_jit
            ]

        # -- straggler simulation / buffered-async lane (core.scheduler) --
        # ``latency`` is a core.latency.LatencyModel driving the simulated
        # round clock (and dropout ghost-masking) in run(); ``async_config``
        # is a core.scheduler.AsyncConfig switching run() to the
        # buffered-async schedule. Both ride the per-round numpy-stream
        # lane: the fused superstep scan and the on-device cohort draw have
        # no per-round host hook for arrival masking, and the async client
        # phase returns dense raveled deltas (codec integration is a
        # documented non-goal for now).
        self.latency = latency
        self.async_config = async_config
        if latency is not None and (device_sampling or mesh is not None):
            raise ValueError(
                "latency simulation needs the per-round numpy-stream lane: "
                "construct the engine without device_sampling/mesh"
            )
        if async_config is not None:
            if codec is not None or mesh is not None or device_sampling:
                raise ValueError(
                    "async_config is incompatible with codec=/mesh=/"
                    "device_sampling=True: the buffered-async lane ships "
                    "dense fp32 deltas through the split client/apply "
                    "executables on the per-round numpy-stream lane"
                )
            if rounds_per_step not in (None, 1):
                raise ValueError(
                    "async_config replaces the round loop entirely; "
                    f"rounds_per_step={rounds_per_step} has no meaning there"
                )
            from repro.utils.tree import tree_ravel_stacked

            # Static unravel recipe for the aggregated (N,) delta; the
            # leading dim of the dummy stack is irrelevant to the spec.
            dummy = jax.tree.map(
                lambda p: jnp.zeros((1,) + jnp.shape(p), jnp.float32),
                self.params,
            )
            _, self._delta_spec = tree_ravel_stacked(dummy)
            cbody = partial(
                _engine_client_phase, loss_fn,
                E=cfg.E, spe=packed.max_real_steps_per_epoch,
                B=packed.batch_size, feature_shape=self._feature_shape,
                has_labels=self._y is not None,
            )
            abody = partial(
                _engine_apply_buffer, self.strategy, self._delta_spec,
                interpret=self.interpret,
                accum_dtype=jnp.dtype(accum_dtype),
            )
            # No donation on the client phase: its params argument must
            # survive for the other in-flight dispatches at the same server
            # version. The apply phase donates like the fused round.
            self._client_phase_jit = jax.jit(cbody)
            self._apply_jit = jax.jit(abody, donate_argnums=(0, 1))

    # -- declarative construction ------------------------------------------

    @classmethod
    def from_spec(
        cls,
        spec,
        client_data: Sequence[Tuple[np.ndarray, Optional[np.ndarray]]],
        *,
        loss_fn: Optional[Callable] = None,
        init_params=None,
        eval_fn: Optional[Callable] = None,
        mesh=None,
        model_kwargs: Optional[Dict[str, Any]] = None,
    ) -> "RoundEngine":
        """Construct an engine from a declarative ``repro.specs
        .ExperimentSpec`` — the composable front door: every knob that used
        to be a constructor kwarg (codec, strategy, mesh axis, device
        sampling, superstep width, interpret, accum dtype) is a spec field
        with a JSON round-trip, so examples, benchmarks, scripts and tests
        all construct engines the same way (docs/engine.md "Constructing
        engines").

        ``client_data`` stays an argument (specs describe experiments, not
        datasets); ``loss_fn``/``init_params`` default to building
        ``spec.model`` and initializing it from ``spec.fedavg.seed``
        (``model_kwargs`` override model fields resolved only at data time,
        e.g. a corpus vocab size). ``mesh`` defaults to a fresh one-axis
        client mesh over all local devices when ``spec.execution
        .mesh_axes`` names an axis."""
        if loss_fn is None or init_params is None:
            model = spec.build_model(**(model_kwargs or {}))
            loss_fn = loss_fn if loss_fn is not None else model.loss
            if init_params is None:
                init_params = model.init(
                    jax.random.PRNGKey(spec.fedavg.seed)
                )
        ex = spec.execution
        client_axis = "clients"
        if ex.mesh_axes is not None:
            client_axis = ex.mesh_axes
            if mesh is None:
                from repro.launch.mesh import make_client_mesh

                mesh = make_client_mesh(axis=ex.mesh_axes)
        latency, async_config = None, None
        aspec = getattr(spec, "async_spec", None)
        if aspec is not None:
            if spec.codec is not None:
                # Refused here at the SPEC level (naming the spec fields),
                # before the constructor's kwarg-level guard: a spec
                # carrying both claims compressed uploads while the async
                # lane ships dense fp32 deltas — it would misreport wire
                # bytes, not just run slower (ROADMAP follow-on: compose
                # the codec encode into the async client phase).
                raise ValueError(
                    f"spec {spec.name!r} sets both codec= and async_spec=: "
                    "the buffered-async lane has no codec path, so the run "
                    "would ship dense fp32 deltas while the spec claims "
                    f"{spec.codec.kind!r} compression — drop one of the two "
                    "fields"
                )
            from repro.core.scheduler import AsyncConfig

            async_config = AsyncConfig(
                buffer_k=aspec.buffer_k, concurrency=aspec.concurrency
            )
            latency = aspec.latency
        tspec = getattr(spec, "topology", None)
        return cls(
            loss_fn,
            init_params,
            client_data,
            spec.fedavg,
            eval_fn,
            codec=spec.build_codec(),
            strategy=spec.build_strategy(),
            topology=tspec.build() if tspec is not None else None,
            interpret=ex.interpret,
            accum_dtype=jnp.dtype(ex.accum_dtype),
            mesh=mesh,
            client_axis=client_axis,
            device_sampling=ex.device_sampling,
            rounds_per_step=ex.rounds_per_step,
            latency=latency,
            async_config=async_config,
            pool=getattr(ex, "pool", "auto"),
            pool_shard_clients=getattr(ex, "pool_shard_clients", 1024),
            prefetch=getattr(ex, "prefetch", 1),
            clients_per_chunk=getattr(ex, "clients_per_chunk", None),
        )

    # -- introspection ----------------------------------------------------

    @property
    def num_clients(self) -> int:
        return self.packed.num_clients

    @property
    def num_compilations(self) -> int:
        """Distinct executables behind the round loop — the jax.jit cache
        sizes of the per-round executable and the superstep (scan-of-R)
        executable combined (their staged twins on the streamed-pool
        lane). A run that mixes one superstep length with per-round calls
        stays at 2; a ragged final chunk (n_rounds not a multiple of R)
        adds one scan-of-remainder executable."""
        return sum(f._cache_size() for f in self._executables)

    def lower_round(self, rounds_per_step: int = 1):
        """The executable ``run`` dispatches for one round
        (``rounds_per_step=1``) or one R-round superstep, lowered for the
        engine's current state without running it or advancing any stream.
        Inspection only: ``.as_text()`` shows what the device runs — a
        Pallas kernel that lowered for the chip appears as a
        ``tpu_custom_call``, an interpreted one as plain XLA ops. Covers the
        star, streamed and gossip lanes; on the streamed lane one cohort (or
        chunk) is staged for the lowering and the sampling stream rewound."""
        if self.async_config is not None:
            raise ValueError(
                "lower_round covers the round and superstep executables; the "
                "async lane splits each round into a client and an apply "
                "phase"
            )
        R = int(rounds_per_step)
        if self.pool_kind == "streamed":
            b = (self._prepare_round(self.round_idx) if R == 1
                 else self._prepare_chunk(self.round_idx, R))
            self.rng.bit_generator.state, self.sample_key = b["rng"]
            fn = self._staged_round_jit if R == 1 else self._staged_superstep_jit
            return fn.lower(self.params, self.outer_state, *b["dev"])
        with sanctioned_staging():
            lr = jnp.float32(self.lr_at(self.round_idx))
            lrs = jnp.full((R,), lr)
            if self.topology is not None:
                pool = (self._x, self._y, self._counts, self._spe,
                        self._mix_idx, self._mix_w)
                if R == 1:
                    return self._gossip_round_jit.lower(
                        self.params, *pool, self.sample_key, lr
                    )
                return self._gossip_superstep_jit.lower(
                    self.params, self.sample_key, *pool, lrs
                )
            pool = (self._x, self._y, self._counts, self._spe)
            if R > 1:
                if self._rep is not None:
                    lrs = jax.device_put(lrs, self._rep)
                return self._superstep_jit.lower(
                    self.params, self.outer_state, self.sample_key, *pool,
                    lrs,
                )
            m_pad = self._m + (-self._m) % self._shards
            inputs = (jnp.zeros((m_pad,), jnp.int32),
                      jnp.ones((m_pad,), jnp.float32),
                      jax.random.PRNGKey(0), lr)
            if self._rep is not None:
                inputs = jax.device_put(inputs, self._rep)
            return self._round_jit.lower(
                self.params, self.outer_state, *pool, *inputs
            )

    def consensus_params(self) -> Any:
        """The node-mean parameter tree on the gossip lane (fp32 mean over
        the replica axis, cast back to storage dtype) — what evaluation and
        analysis should consume: mixing is doubly stochastic, so this mean
        is the conserved quantity the replicas contract toward. A star
        engine's params pass through unchanged, so callers can be
        lane-agnostic."""
        if self.topology is None:
            return self.params
        return jax.tree.map(
            lambda p: jnp.mean(p.astype(jnp.float32), axis=0).astype(p.dtype),
            self.params,
        )

    def lr_at(self, rnd: int) -> float:
        """Client lr for round ``rnd``. A callable ``cfg.lr`` is a complete
        round -> lr schedule and is used verbatim; ``lr_decay`` applies ONLY
        to a scalar ``cfg.lr`` (regression: decay used to multiply schedules
        too, so schedule+decay configs decayed twice)."""
        if callable(self.cfg.lr):
            return float(self.cfg.lr(rnd))
        return float(self.cfg.lr) * self.cfg.lr_decay**rnd

    # -- the round loop ---------------------------------------------------

    def _next_round_inputs(self):
        # The round loop's ONLY host->device staging lives here (and in
        # `_superstep`'s lr schedule), inside `sanctioned_staging` blocks,
        # so a `transfer_guard("disallow")` around `run()` proves nothing
        # else re-stages per round (tests/test_guards.py).
        with sanctioned_staging():
            lr = jnp.float32(self.lr_at(self.round_idx))
            if self._rep is not None:
                # Pre-commit to the mesh-replicated layout here, not at
                # dispatch: the shard_map executable would otherwise
                # re-stage the scalar implicitly every round.
                lr = jax.device_put(lr, self._rep)
        if self.device_sampling:
            # The on-device stream, advanced exactly as one iteration of
            # the superstep scan advances its carry — that identity is what
            # makes superstep(R) == R x round() hold round for round
            # (tests/test_engine_superstep.py).
            with jax.named_scope("fedavg.sample"):
                k_cohort, k_data, k_next = jax.random.split(
                    self.sample_key, 3
                )
                self.sample_key = k_next
                with sanctioned_staging():
                    # The draw itself is device compute, but
                    # jax.random.uniform eagerly stages its weak-typed
                    # minval/maxval scalars, and under a mesh those commit
                    # to the NamedSharding — a real (tiny, bounded)
                    # per-round transfer we own here.
                    ids = sample_clients_device(k_cohort, self.num_clients,
                                                self._m)
                    ids, valid = pad_cohort_device(ids, self._shards)
            return ids, valid, k_data, lr
        selected = sample_clients(self.rng, self.num_clients, self.cfg.C)
        # Pad to a multiple of the shard count with zero-weight ghosts
        # (no-op when unsharded: _shards == 1). m is fixed given (K, C), so
        # the padded cohort shape is static across rounds.
        ids, valid = pad_cohort(np.asarray(selected), self._shards)
        with sanctioned_staging():
            key = jax.random.PRNGKey(int(self.rng.integers(2**31)))
            ids = jnp.asarray(ids, jnp.int32)
            valid = jnp.asarray(valid)
            if self._rep is not None:
                ids, valid, key = jax.device_put((ids, valid, key), self._rep)
            return ids, valid, key, lr

    # -- streamed-pool staging pipeline ------------------------------------
    #
    # The streamed lane replaces the on-device pool gather with a host
    # shard read + an explicit, sanctioned host->device staging of just
    # the sampled cohort. Double buffering: after dispatching round R's
    # executable (async dispatch returns immediately), the host prepares
    # and stages round R+1's cohort while R computes. Preparing consumes
    # the sampling RNG ahead of the played rounds, so every prepared
    # bundle carries a snapshot of the stream state taken BEFORE its
    # draw; save()/restore() (and any shape mismatch) discard the pending
    # bundle and rewind to that snapshot, keeping checkpoints bit-for-bit
    # identical to an unprefetched — and to a device-pool — run.

    def _rng_snapshot(self):
        import copy

        return (copy.deepcopy(self.rng.bit_generator.state), self.sample_key)

    def _discard_prefetch(self):
        """Drop a staged-but-unplayed cohort and rewind the sampling
        stream to the state before it was drawn. Exact because prepares
        are sequential: nothing consumed the stream since the snapshot."""
        if self._prefetched is None:
            return
        state, key = self._prefetched["rng"]
        self.rng.bit_generator.state = state
        self.sample_key = key
        self._prefetched = None

    def _take_prefetch(self, kind: str, for_round: int, r=None):
        p = self._prefetched
        if (
            p is not None and p["kind"] == kind
            and p["for_round"] == for_round and p.get("r") == r
        ):
            self._prefetched = None
            return p
        self._discard_prefetch()
        return None

    def _sample_ids_host(self):
        """One cohort draw with host-visible ids, advancing whichever
        sampling stream this engine runs — the numpy stream verbatim, or
        the device stream by replaying the exact split/draw the
        device-pool lanes trace (same keys in, same uint32 ops, so the
        realized cohorts and data keys are bit-identical)."""
        if self.device_sampling:
            with jax.named_scope("fedavg.sample"):
                k_cohort, k_data, k_next = jax.random.split(
                    self.sample_key, 3
                )
                self.sample_key = k_next
                with sanctioned_staging():
                    # Same bounded staging as _next_round_inputs: uniform's
                    # weak-typed minval/maxval scalars.
                    ids_dev = sample_clients_device(
                        k_cohort, self.num_clients, self._m
                    )
            return np.asarray(jax.device_get(ids_dev)), k_data
        ids = np.asarray(
            sample_clients(self.rng, self.num_clients, self.cfg.C)
        )
        with sanctioned_staging():
            key = jax.random.PRNGKey(int(self.rng.integers(2**31)))
        return ids, key

    def _gather_cohort(self, ids):
        """One cohort's host arrays from the streamed pool: x as
        (m, n_pad, F) rows, the device pool's stored layout, so both
        backends assemble from the same bytes."""
        x, y = self.pool.gather(ids)
        return (_as_rows(x), y, self.pool.counts[ids],
                self.pool.steps_per_epoch[ids])

    def _prepare_round(self, for_round: int):
        """Draw, shard-read, and stage one round's cohort."""
        snap = self._rng_snapshot()
        ids, key = self._sample_ids_host()
        x, y, w, spe_k = self._gather_cohort(ids)
        with sanctioned_staging():
            dev = (
                jax.device_put(x),
                jax.device_put(y) if y is not None else None,
                jax.device_put(w),
                jax.device_put(spe_k),
                key,
                jnp.float32(self.lr_at(for_round)),
            )
        return {"kind": "round", "for_round": for_round, "dev": dev,
                "rng": snap}

    def _prepare_chunk(self, for_round: int, r: int):
        """Draw, shard-read, and stage a whole superstep's R cohorts —
        the scan seam: ids for all R rounds are sampled up front (the
        host replays the superstep carry's key-split chain), so one
        staging covers R rounds and overlaps the previous chunk's
        compute."""
        snap = self._rng_snapshot()
        xs, ys, ws, spes, keys = [], [], [], [], []
        for i in range(r):
            ids, key = self._sample_ids_host()
            x, y, w, spe_k = self._gather_cohort(ids)
            xs.append(x)
            ys.append(y)
            ws.append(w)
            spes.append(spe_k)
            keys.append(key)
        lrs = np.asarray(
            [self.lr_at(for_round + i) for i in range(r)], np.float32
        )
        with sanctioned_staging():
            dev = (
                jax.device_put(np.stack(xs)),
                jax.device_put(np.stack(ys)) if ys[0] is not None else None,
                jax.device_put(np.stack(ws)),
                jax.device_put(np.stack(spes)),
                jnp.stack(keys),
                jax.device_put(lrs),
            )
        return {"kind": "chunk", "for_round": for_round, "r": r, "dev": dev,
                "rng": snap}

    def _round_streamed(self) -> Dict[str, float]:
        with jax.profiler.TraceAnnotation("fedavg.prepare"):
            b = (
                self._take_prefetch("round", self.round_idx)
                or self._prepare_round(self.round_idx)
            )
        x, y, w, spe_k, key, lr = b["dev"]
        with jax.profiler.TraceAnnotation("fedavg.dispatch"):
            self.params, self.outer_state, metrics = self._staged_round_jit(
                self.params, self.outer_state, x, y, w, spe_k, key, lr
            )
        self.round_idx += 1
        if self._prefetch_depth > 0:
            # Double buffer: the dispatch above returned without syncing,
            # so this shard read + staging overlaps the round's compute.
            with jax.profiler.TraceAnnotation("fedavg.prepare"):
                self._prefetched = self._prepare_round(self.round_idx)
        return metrics

    def _superstep_streamed(self, r: int) -> np.ndarray:
        with jax.profiler.TraceAnnotation("fedavg.prepare"):
            b = (
                self._take_prefetch("chunk", self.round_idx, r)
                or self._prepare_chunk(self.round_idx, r)
            )
        xs, ys, ws, spes, keys, lrs = b["dev"]
        with jax.profiler.TraceAnnotation("fedavg.dispatch"):
            self.params, self.outer_state, metrics = (
                self._staged_superstep_jit(
                    self.params, self.outer_state, xs, ys, ws, spes, keys,
                    lrs,
                )
            )
        self.round_idx += r
        if self._prefetch_depth > 0:
            # Stage the next chunk (same R — _run_supersteps' steady
            # state; a ragged final chunk just discards and rewinds)
            # while this one computes, then sync on this chunk's losses.
            with jax.profiler.TraceAnnotation("fedavg.prepare"):
                self._prefetched = self._prepare_chunk(self.round_idx, r)
        with jax.profiler.TraceAnnotation("fedavg.sync"):
            return _host_metrics(metrics)

    def round(self) -> Dict[str, float]:
        """One synchronous round; returns {'loss': ...} (plus the model's
        counters, or 'consensus' on the gossip lane), on the device."""
        if self.topology is not None:
            return self._round_gossip()
        if self.pool_kind == "streamed":
            return self._round_streamed()
        with jax.profiler.TraceAnnotation("fedavg.prepare"):
            ids, valid, key, lr = self._next_round_inputs()
        with jax.profiler.TraceAnnotation("fedavg.dispatch"):
            self.params, self.outer_state, metrics = self._round_jit(
                self.params, self.outer_state, self._x, self._y,
                self._counts, self._spe, ids, valid, key, lr,
            )
        self.round_idx += 1
        return metrics

    def _round_gossip(self) -> Dict[str, float]:
        """One gossip round: every node runs its local-SGD phase on its
        own shard, then one neighbor-mixing step — a single donated
        executable. The data key comes off the device PRNG stream with the
        exact split the superstep scan carry uses, so superstep(R) ==
        R x round() holds here as on the star lane."""
        k_data, k_next = jax.random.split(self.sample_key)
        with sanctioned_staging():
            lr = jnp.float32(self.lr_at(self.round_idx))
        self.params, loss, consensus = self._gossip_round_jit(
            self.params, self._x, self._y, self._counts, self._spe,
            self._mix_idx, self._mix_w, k_data, lr,
        )
        self.sample_key = k_next
        self.round_idx += 1
        return {"loss": loss, "consensus": consensus}

    def _resolve_rounds_per_step(
        self, rounds_per_step, n_rounds: int, eval_every: int
    ) -> int:
        """``None`` auto-selects: legacy numpy-stream engines stay
        per-round; device-sampling engines superstep at the evaluation
        granularity (``eval_every``, the most often the host needs control
        back), or the whole run when there is nothing to evaluate. An
        engine-level default (``RoundEngine(rounds_per_step=...)`` — the
        ``ExperimentSpec.execution`` path) fills in before auto-selection."""
        if rounds_per_step is None:
            rounds_per_step = self.default_rounds_per_step
        if rounds_per_step is None:
            if not self.device_sampling:
                return 1
            return max(1, int(eval_every)) if self.eval_fn is not None \
                else max(1, int(n_rounds))
        R = int(rounds_per_step)
        if R < 1:
            raise ValueError(f"rounds_per_step must be >= 1, got {rounds_per_step}")
        if R > 1 and not self.device_sampling:
            raise ValueError(
                "rounds_per_step > 1 needs RoundEngine(device_sampling=True): "
                "the fused multi-round executable draws cohorts on device "
                "from the jax PRNG stream, which this engine's legacy numpy "
                "stream cannot feed without a per-round host sync"
            )
        return R

    def _superstep(self, r: int) -> Dict[str, np.ndarray]:
        """Advance r rounds in ONE dispatch; returns the per-round metrics
        (``"loss"``: (r,)), synced. The lr schedule is precomputed host-side (handles
        both scalar-decay and callable cfg.lr), the cohort key rides in the
        scan carry, and params + key buffers are donated. On the streamed
        lane the scan consumes pre-staged cohorts instead (the host
        replays the key chain and stages all R cohorts up front)."""
        if self.pool_kind == "streamed":
            return self._superstep_streamed(r)
        with jax.profiler.TraceAnnotation("fedavg.prepare"), \
                sanctioned_staging():
            lrs = jnp.asarray(
                [self.lr_at(self.round_idx + i) for i in range(r)], jnp.float32
            )
            if self._rep is not None:
                lrs = jax.device_put(lrs, self._rep)
        with jax.profiler.TraceAnnotation("fedavg.dispatch"):
            self.params, self.outer_state, self.sample_key, metrics = (
                self._superstep_jit(
                    self.params, self.outer_state, self.sample_key, self._x,
                    self._y, self._counts, self._spe, lrs,
                )
            )
        # Explicit D2H (device_get also syncs): the chunk boundary is a
        # sanctioned transfer, and explicitness keeps it legal under
        # transfer_guard("disallow") on guarded backends.
        with jax.profiler.TraceAnnotation("fedavg.sync"):
            metrics = _host_metrics(metrics)
        self.round_idx += r
        return metrics

    def run(
        self,
        n_rounds: int,
        eval_every: int = 1,
        target_acc: Optional[float] = None,
        verbose: bool = False,
        rounds_per_step: Optional[int] = None,
    ) -> History:
        """Run ``n_rounds`` of Algorithm 1.

        ``rounds_per_step=R`` (device-sampling engines) fuses R rounds per
        host dispatch via the superstep executable; evaluation and
        ``target_acc`` early-stopping then happen at R-round granularity
        (chunk boundaries), and each round's ``wall_s`` is the amortized
        chunk time / R. ``None`` auto-selects (see
        :meth:`_resolve_rounds_per_step`).

        The per-round lane itself lives in ``core.scheduler``: a plain
        engine gets the degenerate (bit-for-bit historical) schedule, an
        engine with ``latency=`` gets straggler-simulated sync rounds, and
        an engine with ``async_config=`` gets the buffered-async schedule
        where ``n_rounds`` counts server APPLIES."""
        if int(eval_every) < 1:
            # Validated up front for BOTH lanes: eval_every reaches a
            # modulo in the per-round loop and a floor-division in the
            # superstep crossed-an-eval-point check, so 0 used to surface
            # as a ZeroDivisionError only after the first round had
            # already run.
            raise ValueError(
                f"eval_every must be >= 1, got {eval_every} (use a large "
                "eval_every, not 0, to evaluate only at the end)"
            )
        if target_acc is not None and self.eval_fn is None:
            raise ValueError(
                "run(target_acc=...) needs an eval_fn to measure accuracy — "
                "without one the target can never trigger and the run would "
                "silently do all n_rounds"
            )
        from repro.core.scheduler import RoundScheduler

        if self.topology is not None:
            return self._run_gossip(
                n_rounds, eval_every, target_acc, verbose, rounds_per_step
            )
        if self.async_config is not None:
            return RoundScheduler(self).run_async(
                n_rounds, eval_every, target_acc, verbose
            )
        R = self._resolve_rounds_per_step(rounds_per_step, n_rounds, eval_every)
        if R > 1:
            return self._run_supersteps(
                n_rounds, R, eval_every, target_acc, verbose
            )
        return RoundScheduler(self).run_sync(
            n_rounds, eval_every, target_acc, verbose
        )

    def _run_supersteps(
        self, n_rounds, R, eval_every, target_acc, verbose
    ) -> History:
        done = 0
        while done < n_rounds:
            r = min(R, n_rounds - done)
            t0 = time.perf_counter()
            with jax.profiler.StepTraceAnnotation(
                "fedavg.superstep", step_num=self.round_idx
            ):
                metrics = self._superstep(r)  # blocks on the chunk's outputs
            chunk_s = time.perf_counter() - t0
            done += r
            for j in range(r):
                self.history.records.append(RoundRecord(
                    round=self.round_idx - r + j + 1,
                    train_loss=float(metrics["loss"][j]),
                    # Amortized accounting: the host observes one synced
                    # chunk, so each round is charged chunk_time / r.
                    wall_s=chunk_s / r,
                    counters=record_counters(
                        {k: v[j] for k, v in metrics.items()}),
                ))
            rec = self.history.records[-1]
            # Evaluate whenever this chunk CROSSED an eval point (not only
            # when it lands exactly on a multiple): with R misaligned to
            # eval_every — or round_idx starting non-aligned after a prior
            # run()/restore() — the exact-multiple check would skip every
            # mid-run eval and target_acc could overshoot unboundedly
            # instead of by at most R-1 rounds.
            crossed = (
                self.round_idx // eval_every > (self.round_idx - r) // eval_every
            )
            if self.eval_fn is not None and (crossed or done >= n_rounds):
                ev = self.eval_fn(self.params)
                rec.test_acc = float(ev["acc"])
                rec.test_loss = float(ev.get("loss", np.nan))
                if verbose:
                    print(
                        f"round {self.round_idx:5d} loss {rec.train_loss:.4f} "
                        f"test_acc {rec.test_acc:.4f}"
                    )
                if target_acc is not None and rec.test_acc >= target_acc:
                    break
        return self.history

    def _run_gossip(
        self, n_rounds, eval_every, target_acc, verbose, rounds_per_step
    ) -> History:
        """The gossip round loop, mirroring :meth:`_run_supersteps`: chunks
        of R rounds through the scan-fused gossip superstep (R=1 by
        default — there is no cohort draw, so superstepping is purely a
        dispatch amortization), per-round consensus distance recorded in
        the history, evaluation on :meth:`consensus_params` whenever a
        chunk crosses an eval point."""
        R = rounds_per_step
        if R is None:
            R = self.default_rounds_per_step
        R = 1 if R is None else int(R)
        if R < 1:
            raise ValueError(f"rounds_per_step must be >= 1, got {R}")
        done = 0
        while done < n_rounds:
            r = min(R, n_rounds - done)
            t0 = time.perf_counter()
            with sanctioned_staging():
                lrs = jnp.asarray(
                    [self.lr_at(self.round_idx + i) for i in range(r)],
                    jnp.float32,
                )
            self.params, self.sample_key, losses, cons = (
                self._gossip_superstep_jit(
                    self.params, self.sample_key, self._x, self._y,
                    self._counts, self._spe, self._mix_idx, self._mix_w, lrs,
                )
            )
            losses = np.asarray(jax.device_get(losses))
            cons = np.asarray(jax.device_get(cons))
            chunk_s = time.perf_counter() - t0
            self.round_idx += r
            done += r
            for j in range(r):
                self.history.records.append(RoundRecord(
                    round=self.round_idx - r + j + 1,
                    train_loss=float(losses[j]),
                    wall_s=chunk_s / r,
                    consensus=float(cons[j]),
                ))
            rec = self.history.records[-1]
            crossed = (
                self.round_idx // eval_every
                > (self.round_idx - r) // eval_every
            )
            if self.eval_fn is not None and (crossed or done >= n_rounds):
                ev = self.eval_fn(self.consensus_params())
                rec.test_acc = float(ev["acc"])
                rec.test_loss = float(ev.get("loss", np.nan))
                if verbose:
                    print(
                        f"round {self.round_idx:5d} loss {rec.train_loss:.4f} "
                        f"consensus {rec.consensus:.2e} "
                        f"test_acc {rec.test_acc:.4f}"
                    )
                if target_acc is not None and rec.test_acc >= target_acc:
                    break
        return self.history

    # -- checkpoint / resume ----------------------------------------------

    def save(self, ckpt_dir) -> str:
        """Checkpoint (params, strategy state, round_idx, client-sampling
        RNG state) via ``checkpoint.io``. The numpy bit-generator state
        rides in the msgpack metadata as JSON (its 128-bit PCG integers
        overflow msgpack's int range); the on-device sampling key (the
        superstep scan carry) rides as its raw uint32 words. Restoring both
        means a resumed engine reproduces the uninterrupted run's cohort
        stream bit-for-bit in either sampling mode — including resuming at
        a superstep boundary mid-run. The server strategy's state tree
        (e.g. FedAvgM's velocity) checkpoints alongside the params, and the
        strategy's serialized identity is recorded so ``restore`` can
        refuse a mismatched engine.

        The run history rides in the metadata too: without it, a resumed
        engine's ``rounds_to_target``/``accuracy_curve`` silently ignored
        every pre-restore round — the curves claimed bit-for-bit resume
        while starting from an empty history."""
        import json

        from repro.checkpoint.io import save_checkpoint

        # A staged-but-unplayed prefetched cohort has consumed sampling
        # randomness the checkpoint must NOT record as spent: discard it
        # and rewind, so the saved stream state matches an unprefetched
        # (and a device-pool) run bit-for-bit.
        self._discard_prefetch()
        return save_checkpoint(
            ckpt_dir,
            {"params": self.params, "strategy_state": self.outer_state},
            step=self.round_idx,
            metadata={
                "round_idx": self.round_idx,
                "rng_state": json.dumps(self.rng.bit_generator.state),
                "sample_key": [int(v) for v in np.asarray(self.sample_key)],
                "device_sampling": self.device_sampling,
                "strategy": self.strategy.name,
                # Gossip lane: the serialized topology identity (None on
                # star engines). The params tree above is then the full
                # (n_nodes, ...) replica stack — restore refuses a
                # mismatched graph, which would silently mix with
                # different weights (or a different node count) from
                # round_idx on.
                "topology": (
                    self.topology.name if self.topology is not None else None
                ),
                "history": [
                    dataclasses.asdict(r) for r in self.history.records
                ],
            },
        )

    def restore(self, ckpt_dir, step: Optional[int] = None) -> int:
        """Restore params + round counter + RNG stream saved by :meth:`save`
        into this engine (constructed with the same population/config).
        Returns the restored round index."""
        import json

        from repro.checkpoint.io import (
            latest_step,
            peek_metadata,
            restore_checkpoint,
        )

        # The pending prefetch (if any) was drawn for the PRE-restore
        # stream position; discard and rewind before any state changes.
        self._discard_prefetch()
        # Pin the step ONCE: with step=None, letting peek_metadata and
        # restore_checkpoint each resolve "latest" independently races a
        # concurrent saver — the guards could validate step N while the
        # arrays load from a just-written N+1.
        if step is None:
            step = latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
        # Guards run against the metadata alone, BEFORE any array restore
        # mutates engine state: a half-applied restore would be worse than
        # a refused one.
        meta = peek_metadata(ckpt_dir, step=step)
        if "device_sampling" in meta and (
            bool(meta["device_sampling"]) != self.device_sampling
        ):
            raise ValueError(
                f"checkpoint was written by a device_sampling="
                f"{bool(meta['device_sampling'])} engine but this engine has "
                f"device_sampling={self.device_sampling} — resuming across "
                "sampling modes would silently continue with a different "
                "cohort stream and break bit-for-bit resume"
            )
        rec_topo = meta.get("topology")
        eng_topo = self.topology.name if self.topology is not None else None
        if rec_topo != eng_topo:
            # Same pattern as the sampling-mode/strategy guards: the
            # replica stack only means something under the graph that
            # produced it, and a star<->gossip mismatch would not even
            # shape-check — refuse with the identities named.
            raise ValueError(
                f"checkpoint was written by a topology={rec_topo} engine "
                f"but this engine has topology={eng_topo} — restoring "
                "across communication graphs would silently continue a "
                "different mixing process"
            )
        recorded = meta.get("strategy")
        if recorded is not None and recorded != self.strategy.name:
            # Same pattern as the sampling-mode guard: resuming FedAvgM
            # velocity into a FedAvg engine (or vice versa, or across
            # hyper-parameters) would silently continue a DIFFERENT
            # algorithm from round round_idx on.
            raise ValueError(
                f"checkpoint was written by a {recorded} engine but this "
                f"engine runs {self.strategy.name} — restoring across server "
                "strategies would silently continue a different algorithm"
            )
        if recorded is None:
            # Pre-strategy checkpoint (params-only tree): only an identity
            # strategy can resume it — there is no recorded state for a
            # stateful one to pick up.
            if jax.tree.leaves(self.outer_state):
                raise ValueError(
                    "checkpoint predates server strategies (no recorded "
                    f"strategy state) but this engine runs "
                    f"{self.strategy.name}, which carries state — resume it "
                    "with a FedAvg/FedSGD engine instead"
                )
            restored, meta = restore_checkpoint(
                ckpt_dir, self.params, step=step
            )
        else:
            tree, meta = restore_checkpoint(
                ckpt_dir,
                {"params": self.params, "strategy_state": self.outer_state},
                step=step,
            )
            restored = tree["params"]
            self.outer_state = tree["strategy_state"]
        self.params = restored
        self.round_idx = int(meta["round_idx"])
        self.rng.bit_generator.state = json.loads(meta["rng_state"])
        if "history" in meta:
            # Resume the RECORDED curves too, so rounds_to_target /
            # accuracy_curve on a resumed run see the pre-restore rounds.
            # Absent in pre-PR7 checkpoints: those resume with an empty
            # history exactly as before.
            self.history = History(
                [RoundRecord(**dict(d)) for d in meta["history"]]
            )
        if "sample_key" in meta:  # absent in pre-superstep checkpoints
            self.sample_key = jnp.asarray(
                np.asarray(meta["sample_key"], np.uint32)
            )
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            rep = NamedSharding(self.mesh, P())
            self.params = jax.device_put(self.params, rep)
            self.outer_state = jax.device_put(self.outer_state, rep)
            self.sample_key = jax.device_put(self.sample_key, rep)
        return self.round_idx

    # -- testing hooks -----------------------------------------------------

    def materialize_round_batch(self, ids, key):
        """Assemble (batches, step_mask, weights) exactly as the jitted round
        does — for equivalence tests and the legacy-vs-engine benchmark.
        Always the UNSHARDED view (global slot 0 onward)."""
        if self.pool_kind == "streamed":
            x, y, w, spe_k = self._gather_cohort(np.asarray(ids))
            with sanctioned_staging():
                return _assemble_cohort_batches(
                    jnp.asarray(x),
                    jnp.asarray(y) if y is not None else None,
                    jnp.arange(len(x), dtype=jnp.int32),
                    jnp.asarray(w), jnp.asarray(spe_k), key,
                    E=self.cfg.E, spe=self.packed.max_real_steps_per_epoch,
                    B=self.packed.batch_size,
                    feature_shape=self._feature_shape,
                    has_labels=y is not None,
                )
        return _assemble_batches(
            self._x, self._y, self._counts, self._spe,
            jnp.asarray(ids, jnp.int32), key,
            E=self.cfg.E, spe=self.packed.max_real_steps_per_epoch,
            B=self.packed.batch_size, feature_shape=self._feature_shape,
            has_labels=self._y is not None,
        )


def _as_rows(x):
    """(K, n_pad, *feature_shape) -> (K, n_pad, F): each example one row.
    On the TPU an example stored as, say, a 28x28x1 tile puts the example
    index in the lanes, and the per-client gather then moves it element by
    element; as a row it is one lane-dense copy. Rank-1 features are rows
    already. A view of a contiguous numpy array."""
    return x.reshape(x.shape[:2] + (-1,))


# The round body lives at module level so the jit cache key is stable and
# introspectable; everything shape-like is a closed-over Python int.

def _assemble_batches(px, py, counts, spe_arr, ids, key, *, E, spe, B,
                      feature_shape, has_labels, slot0=0):
    """Device-pool batch assembly: the cohort's weights and step counts,
    then the shared half below on the whole (K, n_pad, F) row pool
    (:func:`_as_rows`). The streamed lane stages its cohort as such a pool
    of m clients and enters at :func:`_assemble_cohort_batches` with ids
    0..m-1 — the seam that makes the two backends bit-for-bit identical: a
    gather copies rows exactly, so both lanes run the same ops on the same
    bytes."""
    w = jnp.take(counts, ids)                            # (m,)
    spe_k = jnp.take(spe_arr, ids)                       # (m,) real steps/epoch
    return _assemble_cohort_batches(
        px, py, ids, w, spe_k, key, E=E, spe=spe, B=B,
        feature_shape=feature_shape, has_labels=has_labels, slot0=slot0,
    )


def _assemble_cohort_batches(px, py, ids, w, spe_k, key, *, E, spe, B,
                             feature_shape, has_labels, slot0=0):
    """The cohort half of assembly: clients ``ids`` of a (K, n_pad, F) row
    pool in, per-(client, epoch) permuted minibatches out as (m, E*spe, B,
    *feature_shape). One gather of whole rows by pool row index
    ``ids * n_pad + perm``; the model's input shape is restored only on the
    gathered batch."""
    m = ids.shape[0]
    n_pad = px.shape[1]
    # One fresh draw order per (client, epoch), the on-device analogue of
    # per-epoch reshuffling in ClientUpdate. Keying the sort by u + 2*[row
    # is padding] puts a uniform permutation of the client's n_k REAL rows
    # first and the tiled padding rows (in random order) after, so a
    # client's active steps (spe_k = ceil(n_k / B)) train every one of its
    # examples exactly once per epoch WITHOUT replacement, and the ragged
    # final step fills its remaining slots with randomly-ordered tiled
    # duplicates — the within-client resample fill the legacy host path
    # (client_epoch_batches) promises. Only the first spe*B positions feed
    # the scan; ``spe`` is the largest REAL per-client step count.
    #
    # Keys derive from the client's GLOBAL cohort slot (``slot0`` + local
    # index), not from one split over however many clients this call sees:
    # under cohort sharding each shard assembles only its m/D slice, and
    # slot-keyed fold_in makes its permutations identical to the ones the
    # unsharded engine draws for the same clients — the bedrock of the
    # sharded-vs-unsharded equivalence guarantee.
    slots = slot0 + jnp.arange(m, dtype=jnp.int32)
    epochs = jnp.arange(E, dtype=jnp.int32)
    keys = jax.vmap(
        lambda s: jax.vmap(
            lambda e: jax.random.fold_in(jax.random.fold_in(key, s), e)
        )(epochs)
    )(slots)                                             # (m, E) keys
    n_real = w.astype(jnp.int32)                         # (m,) == counts[ids]

    def draw_order(k, nk):
        u = jax.random.uniform(k, (n_pad,))
        return jnp.argsort(u + 2.0 * (jnp.arange(n_pad) >= nk))

    perm = jax.vmap(jax.vmap(draw_order, in_axes=(0, None)))(keys, n_real)
    perm = perm[:, :, : spe * B].reshape(m, E * spe * B)
    rows = (ids[:, None] * n_pad + perm).reshape(-1)

    def gather(pool):
        flat = pool.reshape((-1,) + pool.shape[2:])      # (K*n_pad, ...)
        return jnp.take(flat, rows, axis=0).reshape(
            (m, E * spe, B) + pool.shape[2:])

    bx = gather(px).reshape((m, E * spe, B) + feature_shape)
    by = gather(py) if has_labels else None
    # Step s is real iff its epoch-local index is below the client's own
    # steps_per_epoch; padded steps are masked no-ops in client_update.
    step_in_epoch = jnp.arange(E * spe, dtype=jnp.int32) % spe
    mask = (step_in_epoch[None, :] < spe_k[:, None]).astype(jnp.float32)
    batch = (bx, by) if has_labels else (bx,)
    return batch, mask, w


def _engine_round(
    loss_fn, params, outer, px, py, counts, spe_arr, ids, valid, key, lr,
    *, E, spe, B, feature_shape, has_labels, codec, strategy, interpret,
    accum_dtype, axis_name=None, clients_per_chunk=None,
):
    # Under shard_map ``ids``/``valid`` are this shard's (m/D,) cohort
    # slice; the shard's global slot offset keys all per-client randomness
    # so the sharded round replays the unsharded one exactly.
    with jax.named_scope("fedavg.assemble"):
        m_local = ids.shape[0]
        slot0 = (0 if axis_name is None
                 else jax.lax.axis_index(axis_name) * m_local)
        batch, mask, w = _assemble_batches(
            px, py, counts, spe_arr, ids, key, E=E, spe=spe, B=B,
            feature_shape=feature_shape, has_labels=has_labels, slot0=slot0,
        )
        # Ghost cohort-padding clients (valid == 0) keep a real row gather
        # (id 0) but zero weight, so they vanish from the aggregate and the
        # loss.
        w = w * valid
    return _apply_round_step(
        loss_fn, params, outer, batch, mask, w, key, lr, codec=codec,
        strategy=strategy, interpret=interpret, accum_dtype=accum_dtype,
        axis_name=axis_name, clients_per_chunk=clients_per_chunk,
    )


def _apply_round_step(
    loss_fn, params, outer, batch, mask, w, key, lr,
    *, codec, strategy, interpret, accum_dtype, axis_name=None,
    clients_per_chunk=None,
):
    """The server half every lane shares from the assembled cohort on:
    plain, chunked or compressed round step, strategy threading, the
    round's metrics (``{"loss"}`` and any model counters). One definition
    so the device and streamed pool backends cannot drift."""
    if codec is None:
        step = build_simulation_round_step(
            loss_fn, interpret=interpret, accum_dtype=accum_dtype,
            axis_name=axis_name, strategy=strategy,
            clients_per_chunk=clients_per_chunk,
        )
        codec_key = None
    else:
        from repro.core.compression import build_compressed_round_step

        step = build_compressed_round_step(
            loss_fn, codec, interpret=interpret, accum_dtype=accum_dtype,
            axis_name=axis_name, strategy=strategy,
        )
        # Decorrelate the codec stream from the batch-permutation stream
        # (whose keys fold in global cohort slots above).
        with jax.named_scope("fedavg.encode"):
            codec_key = jax.random.fold_in(key, 0x5EED)
    state, metrics = step(
        RoundState(params, outer_state=outer),
        RoundBatch(batch, mask, w, lr=lr, key=codec_key),
    )
    return state.params, state.outer_state, metrics


def _engine_round_staged(
    loss_fn, params, outer, cx, cy, w, spe_k, key, lr,
    *, E, spe, B, feature_shape, has_labels, codec, strategy, interpret,
    accum_dtype, clients_per_chunk=None,
):
    """The streamed-pool round body: identical to :func:`_engine_round`
    but the cohort's (m, n_pad, F) rows arrive pre-staged (host shard reads,
    ``sanctioned_staging``) and assembly gathers from them as from a pool of
    m clients — the population never touches device memory.
    No ``valid`` mask: the streamed lane is unsharded, so cohorts are never
    ghost-padded (and the device lane's ``w * 1.0`` is bitwise ``w``)."""
    with jax.named_scope("fedavg.assemble"):
        batch, mask, w = _assemble_cohort_batches(
            cx, cy, jnp.arange(cx.shape[0], dtype=jnp.int32), w, spe_k, key,
            E=E, spe=spe, B=B, feature_shape=feature_shape,
            has_labels=has_labels,
        )
    return _apply_round_step(
        loss_fn, params, outer, batch, mask, w, key, lr, codec=codec,
        strategy=strategy, interpret=interpret, accum_dtype=accum_dtype,
        clients_per_chunk=clients_per_chunk,
    )


def _engine_superstep_staged(
    loss_fn, params, outer, cxs, cys, ws, spes, keys, lrs,
    *, E, spe, B, feature_shape, has_labels, codec, strategy, interpret,
    accum_dtype, clients_per_chunk=None,
):
    """The streamed twin of :func:`_engine_superstep`: R pre-staged cohorts
    scanned in one donated executable. The cohort draw already happened on
    the host (``_prepare_chunk`` replays the superstep carry's exact
    key-split chain eagerly), so the scan consumes (R, m, ...) staged
    arrays and (R, 2) per-round data keys instead of drawing ids inside
    the scan — same keys, same cohort bytes, same per-round body, hence
    bit-for-bit the device superstep's results."""

    def one_round(carry, inp):
        p, o = carry
        cx, cy, w, spe_k, key, lr = inp
        new_p, new_o, metrics = _engine_round_staged(
            loss_fn, p, o, cx, cy, w, spe_k, key, lr,
            E=E, spe=spe, B=B, feature_shape=feature_shape,
            has_labels=has_labels, codec=codec,
            strategy=strategy, interpret=interpret, accum_dtype=accum_dtype,
            clients_per_chunk=clients_per_chunk,
        )
        return (new_p, new_o), metrics

    (params, outer), metrics = jax.lax.scan(
        one_round, (params, outer), (cxs, cys, ws, spes, keys, lrs)
    )
    return params, outer, metrics


def _engine_superstep(
    loss_fn, params, outer, key, px, py, counts, spe_arr, lrs,
    *, K, m, shards, E, spe, B, feature_shape, has_labels, codec, strategy,
    interpret, accum_dtype, axis_name=None, clients_per_chunk=None,
):
    """R = len(lrs) full rounds fused into one ``lax.scan``: per round, the
    carry key splits into (cohort draw, data/codec key, next carry) exactly
    as the eager ``_next_round_inputs`` device branch does, the cohort is
    drawn on device (``sample_clients_device`` + static ghost padding), and
    ``_engine_round`` — the identical per-round body, codec, server
    strategy and all — runs on it. The strategy state rides in the scan
    carry next to the params. Returns (params, strategy state, advanced
    key, per-round metrics: each an (R, ...) stack).

    Under cohort sharding this whole function sits INSIDE the shard_map:
    every shard replays the (replicated) cohort draw and slices its own
    m/D chunk, so the per-round psum-finished aggregation and the
    global-slot randomness keying are untouched — sharded supersteps match
    unsharded supersteps for the same reason sharded rounds match
    unsharded rounds."""
    m_pad = m + (-m) % shards
    m_local = m_pad // shards

    def one_round(carry, lr):
        p, o, k = carry
        with jax.named_scope("fedavg.sample"):
            k_cohort, k_data, k_next = jax.random.split(k, 3)
            ids = sample_clients_device(k_cohort, K, m)
            ids, valid = pad_cohort_device(ids, shards)
            if axis_name is not None:
                d = jax.lax.axis_index(axis_name)
                ids = jax.lax.dynamic_slice_in_dim(ids, d * m_local, m_local)
                valid = jax.lax.dynamic_slice_in_dim(valid, d * m_local,
                                                     m_local)
        new_p, new_o, metrics = _engine_round(
            loss_fn, p, o, px, py, counts, spe_arr, ids, valid, k_data, lr,
            E=E, spe=spe, B=B, feature_shape=feature_shape,
            has_labels=has_labels, codec=codec,
            strategy=strategy, interpret=interpret, accum_dtype=accum_dtype,
            axis_name=axis_name, clients_per_chunk=clients_per_chunk,
        )
        return (new_p, new_o, k_next), metrics

    (params, outer, key), metrics = jax.lax.scan(
        one_round, (params, outer, key), lrs
    )
    return params, outer, key, metrics


# -- gossip executables (core.topology, docs/topology.md) -------------------
#
# The decentralized lane's round: no server, no cohort draw — every node
# runs the SAME local-SGD phase as the star lane's ClientUpdate on its own
# client shard (node k <-> packed client k, so batch permutation keys fold
# in slot k exactly as a star round over ids = arange(K) would — the hinge
# of the full-graph == FedAvg equivalence), then one Metropolis–Hastings
# neighbor-mixing step through the Pallas gossip_mix kernel replaces the
# aggregate+broadcast.

def _engine_gossip_round(
    loss_fn, stacked, px, py, counts, spe_arr, mix_idx, mix_w, key, lr,
    *, E, spe, B, feature_shape, has_labels, interpret, accum_dtype,
):
    """One fused gossip round over the (n_nodes, ...) replica stack.
    Returns (mixed replica stack, cohort train loss, consensus distance).

    The mix inlines ``ops.tree_gossip_mix`` so the raveled (n_nodes, N)
    matrix is shared with the consensus-distance metric — the RMS over
    nodes of each post-mix replica's L2 distance to the node mean, the
    scalar that measures how far the swarm is from agreeing on one model
    (0 exactly when all replicas are equal; one full-graph mix drives it
    to ~0 in a single step)."""
    n_nodes = counts.shape[0]
    ids = jnp.arange(n_nodes, dtype=jnp.int32)
    batch, mask, w = _assemble_batches(
        px, py, counts, spe_arr, ids, key, E=E, spe=spe, B=B,
        feature_shape=feature_shape, has_labels=has_labels,
    )
    upd = jax.vmap(
        lambda p, b, msk: client_update(loss_fn, p, b, msk, lr)
    )
    node_params, losses = upd(stacked, batch, mask)
    loss = masked_weighted_loss(losses, mask, w)
    flat, spec = tree_ravel_stacked(node_params)
    mixed = gossip_mix(
        flat, mix_idx, mix_w, interpret=interpret, accum_dtype=accum_dtype
    )
    mf = mixed.astype(jnp.float32)
    center = jnp.mean(mf, axis=0, keepdims=True)
    consensus = jnp.sqrt(jnp.mean(jnp.sum((mf - center) ** 2, axis=1)))
    new_stacked = jax.vmap(lambda row: tree_unravel(spec, row))(mixed)
    return new_stacked, loss, consensus


def _engine_gossip_superstep(
    loss_fn, stacked, key, px, py, counts, spe_arr, mix_idx, mix_w, lrs,
    *, E, spe, B, feature_shape, has_labels, interpret, accum_dtype,
):
    """R = len(lrs) gossip rounds fused into one ``lax.scan``. The carry
    key splits into (data key, next carry) exactly as the eager
    ``_round_gossip`` does — same stream, so superstep(R) == R x round()
    round for round. Returns (replicas, advanced key, (R,) losses,
    (R,) consensus distances)."""

    def one_round(carry, lr):
        p, k = carry
        k_data, k_next = jax.random.split(k)
        new_p, loss, cons = _engine_gossip_round(
            loss_fn, p, px, py, counts, spe_arr, mix_idx, mix_w, k_data, lr,
            E=E, spe=spe, B=B, feature_shape=feature_shape,
            has_labels=has_labels, interpret=interpret,
            accum_dtype=accum_dtype,
        )
        return (new_p, k_next), (loss, cons)

    (stacked, key), (losses, conss) = jax.lax.scan(
        one_round, (stacked, key), lrs
    )
    return stacked, key, losses, conss


# -- buffered-async executables (core.scheduler) ----------------------------
#
# The async lane splits the fused round into two jitted phases so the
# server can aggregate a buffer that mixes updates from different dispatch
# groups. The split preserves every op and association of the fused round —
# _assemble_batches with the same slot keying, the same vmapped
# client_update, masked_weighted_loss's exact per-client/normalize/sum
# phrasing, the same Pallas aggregate — so the degenerate schedule
# (buffer_k == m, zero latency, staleness 0) reproduces _engine_round
# bit-for-bit (tests/test_scheduler_async.py).

def _engine_client_phase(
    loss_fn, params, px, py, counts, spe_arr, ids, valid, key, lr,
    *, E, spe, B, feature_shape, has_labels,
):
    """Dispatch half of a round: run ClientUpdate for a cohort against the
    CURRENT params and return the raw ingredients the server buffers —
    (width, N) raveled fp32 deltas, (width,) per-client mean losses, and
    (width,) raw example weights (ghost-masked by ``valid``)."""
    from repro.utils.tree import tree_ravel_stacked

    batch, mask, w = _assemble_batches(
        px, py, counts, spe_arr, ids, key, E=E, spe=spe, B=B,
        feature_shape=feature_shape, has_labels=has_labels,
    )
    w = w * valid
    upd = jax.vmap(
        lambda b, msk: client_update(loss_fn, params, b, msk, lr)
    )
    client_params, losses = upd(batch, mask)
    deltas = jax.tree.map(
        lambda c, p: (c - p).astype(jnp.float32), client_params, params
    )
    flat, _ = tree_ravel_stacked(deltas)
    # Identical phrasing to masked_weighted_loss's per-client half; the
    # apply phase finishes the weighted sum once the buffer's weights are
    # known.
    per_client = jnp.sum(losses * mask, axis=1) / jnp.maximum(
        jnp.sum(mask, axis=1), 1.0
    )
    return flat, per_client, w


def _engine_apply_buffer(
    strategy, spec, params, outer, flat, per_loss, w, stale,
    *, interpret, accum_dtype,
):
    """Server half: staleness-discount the buffered weights through the
    strategy protocol, normalize ONCE, aggregate via the Pallas kernel, and
    step the server strategy. ``stale`` is the (K,) server-version gap per
    update; a synchronous buffer passes zeros, and the base strategy's
    all-ones ``staleness_scale`` makes the discount an exact no-op there.
    Ghost rows (forced partial applies) carry w == 0 and vanish from both
    the aggregate and the loss, exactly like pad_cohort ghosts."""
    from repro.kernels.fedavg_agg import fedavg_aggregate
    from repro.utils.tree import tree_unravel

    w = w * strategy.staleness_scale(stale)
    wn = w / jnp.sum(w)
    avg = fedavg_aggregate(
        flat, wn, interpret=interpret, accum_dtype=accum_dtype
    )
    agg_delta = tree_unravel(spec, avg)
    outer, new_params = strategy.apply(outer, params, agg_delta)
    loss = jnp.sum(wn * per_loss)
    return new_params, outer, loss
