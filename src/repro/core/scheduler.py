"""Event-driven round scheduler: sync, straggler-simulated, and
buffered-async schedules over the RoundEngine's compiled executables.

``RoundEngine.run()`` used to BE the round loop; it now delegates the
per-round lane here so that "when does the server apply an aggregate"
becomes a scheduling policy instead of a hard-coded barrier. Three
schedules share the machinery:

- **sync** (no latency model): the degenerate schedule — dispatch a
  cohort, wait for everything, apply. Exactly the historical loop, same
  executables, same RNG consumption, bit-for-bit the same results.
- **sync + LatencyModel**: same barrier, but each round's simulated
  duration is the slowest observed arrival (capped by the deadline), and
  dropped/late clients are ghost-masked through the zero-weight ``valid``
  input the round executable already has for shard padding. Records gain
  ``sim_s`` so rounds-to-target can be re-read as wall-clock-to-target.
- **buffered-async** (``AsyncConfig``): FedBuff-style semi-asynchrony
  (Nguyen et al. 2021) with FedAsync-style staleness discounting (Xie et
  al. 2019) riding the ServerStrategy protocol. The server keeps ``m``
  updates in flight; whenever ``buffer_k`` of them arrive it applies their
  staleness-weighted aggregate and refills the in-flight pool. Stragglers
  stop gating progress — the K-th arrival does, which is the entire
  wall-clock argument for async FL (gated by benchmarks/async_rounds.py).

The async lane splits the fused round executable into two jitted phases —
client phase (gather → permute → vmapped ClientUpdate → raveled deltas)
and apply phase (staleness scale → normalize → Pallas ``fedavg_aggregate``
→ ``strategy.apply``) — because a buffer may mix updates from different
dispatch groups. The split preserves the fused round's ops and
association, so the degenerate schedule (``buffer_k == m``, zero-latency
model) reproduces the sync lane's model state — params, outer strategy
state, and the client-sampling RNG stream — bit-for-bit, round for round
(asserted by tests/test_scheduler_async.py; the reason sync users pay
nothing for this machinery existing). The one scalar outside the
guarantee is the recorded train-loss METRIC, which can differ by 1 ulp on
some rounds: the same ``sum(w/Σw · per_client_loss)`` reduction is
scheduled by XLA independently in the two executables.

Event semantics: a heap of ``(t_arrival, seq)`` orders arrivals; ``seq``
(dispatch order) breaks ties, so simultaneous arrivals — the whole
degenerate schedule — resolve deterministically. Simulated time is
bookkeeping only; real compute happens eagerly at dispatch (the simulation
models WHEN results become visible, not how long jit takes). All latency
randomness comes from the LatencyModel's own stream, never the engine's
client-sampling RNG — toggling the simulation cannot change which cohorts
are drawn.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.latency import LatencyModel


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """The buffered-async lane's two knobs.

    buffer_k:    apply the server update whenever this many updates have
                 arrived (K in FedBuff). ``buffer_k == concurrency`` plus a
                 zero LatencyModel is the degenerate sync schedule.
    concurrency: updates kept in flight (m). ``None`` uses the engine's
                 cohort size ``max(round(C*K), 1)`` — the same client
                 budget per unit time as the sync lane, just not barriered.
    """

    buffer_k: int
    concurrency: Optional[int] = None

    def __post_init__(self):
        if self.buffer_k < 1:
            raise ValueError(f"buffer_k must be >= 1, got {self.buffer_k}")
        if self.concurrency is not None and self.concurrency < self.buffer_k:
            raise ValueError(
                f"concurrency ({self.concurrency}) must be >= buffer_k "
                f"({self.buffer_k}): the buffer could never fill"
            )


class RoundScheduler:
    """Drives one ``run()`` call. Holds no cross-run state — the engine
    owns params/RNG/history; the scheduler owns the event clock."""

    def __init__(self, engine):
        # Defense in depth behind RoundEngine's constructor guard (and
        # from_spec's spec-level one): engine attributes are plain-mutable
        # after construction, and a codec+async engine reaching this far
        # would silently ship dense fp32 deltas while claiming compressed
        # uploads — the scheduler's client phase has no codec path
        # (ROADMAP follow-on: compose encode into the dispatch phase).
        if engine.async_config is not None and engine.codec is not None:
            raise ValueError(
                "RoundScheduler cannot run a codec= engine on the "
                "buffered-async schedule: the async client phase ships "
                "dense fp32 deltas, so the codec would be silently ignored "
                "— drop codec= or async_config="
            )
        if getattr(engine, "topology", None) is not None:
            raise ValueError(
                "RoundScheduler drives the star lanes only: gossip engines "
                "(topology=) run their own mixing schedule — use "
                "RoundEngine.run() directly"
            )
        self.engine = engine
        self.model: Optional[LatencyModel] = engine.latency
        self.acfg: Optional[AsyncConfig] = engine.async_config

    # ------------------------------------------------------------------
    # sync schedule (with optional straggler simulation)
    # ------------------------------------------------------------------

    def run_sync(self, n_rounds, eval_every, target_acc, verbose):
        """The per-round barrier loop, verbatim from the pre-scheduler
        ``RoundEngine.run`` — plus, when a LatencyModel is present,
        per-round simulated duration and dropout ghost-masking."""
        from repro.core.engine import RoundRecord, record_counters

        eng = self.engine
        lat_rng = self.model.init_rng() if self.model is not None else None
        speed = (
            self.model.client_speed(eng.num_clients)
            if self.model is not None else None
        )
        for i in range(n_rounds):
            t0 = time.perf_counter()
            sim_s = 0.0
            counters = None
            with jax.profiler.StepTraceAnnotation(
                "fedavg.round", step_num=eng.round_idx
            ):
                if self.model is None:
                    metrics = eng.round()
                    # Honest per-round timing: stop the clock only after
                    # the round's outputs are synced — once dispatch is
                    # async, the un-synced time would be a dispatch
                    # latency, not a round time. device_get both syncs and
                    # keeps the D2H read explicit, so the loop stays legal
                    # under transfer_guard("disallow") on guarded backends.
                    with jax.profiler.TraceAnnotation("fedavg.sync"):
                        host = jax.device_get(metrics)
                    loss = float(host["loss"])
                    counters = record_counters(host)
                else:
                    loss, sim_s = self._latency_round(lat_rng, speed)
            rec = RoundRecord(
                round=eng.round_idx,
                train_loss=loss,
                wall_s=time.perf_counter() - t0,
                sim_s=sim_s,
                counters=counters,
            )
            # i, not round_idx, for the last-round check: round_idx is
            # cumulative across run() calls, so a second run(n) would never
            # hit its own final-round evaluation.
            if eng.eval_fn is not None and (
                eng.round_idx % eval_every == 0 or i == n_rounds - 1
            ):
                ev = eng.eval_fn(eng.params)
                rec.test_acc = float(ev["acc"])
                rec.test_loss = float(ev.get("loss", np.nan))
                if verbose:
                    print(
                        f"round {eng.round_idx:5d} loss {rec.train_loss:.4f} "
                        f"test_acc {rec.test_acc:.4f}"
                    )
                eng.history.records.append(rec)
                if target_acc is not None and rec.test_acc >= target_acc:
                    break
            else:
                eng.history.records.append(rec)
        return eng.history

    def _latency_round(self, lat_rng, speed) -> Tuple[float, float]:
        """One barriered round under the straggler model: draw observed
        arrival times for the cohort, ghost-mask failures into ``valid``,
        and charge the round the barrier time (slowest observed arrival).
        """
        eng = self.engine
        with jax.profiler.TraceAnnotation("fedavg.prepare"):
            ids, valid, key, lr = eng._next_round_inputs()
            m = eng._m  # real clients lead the (possibly shard-padded) cohort
            ids_np = np.asarray(ids)[:m]
            t_obs, ok = self.model.draw(lat_rng, ids_np, speed)
            sim_s = float(t_obs.max()) if len(t_obs) else 0.0
            if not ok.all():
                arrival = np.ones(np.asarray(valid).shape[0], np.float32)
                arrival[:m] = ok.astype(np.float32)
                valid = valid * jnp.asarray(arrival)
        if not ok.any():
            # Every client failed: no update this round (an all-zero weight
            # vector would 0/0 in the normalizer). The round still happened
            # — it cost sim_s and produced nothing.
            eng.round_idx += 1
            return float("nan"), sim_s
        with jax.profiler.TraceAnnotation("fedavg.dispatch"):
            eng.params, eng.outer_state, metrics = eng._round_jit(
                eng.params, eng.outer_state, eng._x, eng._y, eng._counts,
                eng._spe, ids, valid, key, lr,
            )
        eng.round_idx += 1
        with jax.profiler.TraceAnnotation("fedavg.sync"):
            return float(jax.device_get(metrics["loss"])), sim_s

    # ------------------------------------------------------------------
    # buffered-async schedule
    # ------------------------------------------------------------------

    def run_async(self, n_rounds, eval_every, target_acc, verbose):
        """FedBuff-style loop: ``n_rounds`` server APPLIES (the async unit
        of progress, recorded in the same History), each triggered by the
        ``buffer_k``-th arrival among ``concurrency`` in-flight updates."""
        from repro.core.engine import RoundRecord

        eng = self.engine
        model = self.model if self.model is not None else LatencyModel()
        K = self.acfg.buffer_k
        m = self.acfg.concurrency or eng._m
        if m > eng.num_clients:
            raise ValueError(
                f"async concurrency {m} exceeds the population "
                f"({eng.num_clients} clients)"
            )
        lat_rng = model.init_rng()
        speed = model.client_speed(eng.num_clients)

        heap: List[Tuple[float, int, int, int, bool]] = []
        groups = {}  # gid -> {flat, loss, w, version, live}
        buffer: List[Tuple[int, int]] = []
        state = {"seq": 0, "gid": 0, "in_flight": 0, "now": 0.0}

        def dispatch(width: int):
            """Sample ``width`` fresh clients, run their client phase NOW
            against the CURRENT params, and schedule their arrivals. When
            ``width == eng._m`` the cohort draw consumes the engine RNG
            exactly as the sync lane's ``_next_round_inputs`` does — the
            degenerate schedule only ever dispatches at that width, so its
            client-sampling stream is the sync lane's, call for call."""
            if width <= 0:
                return
            from repro.core.fedavg import sample_clients

            if width == eng._m:
                ids_np = sample_clients(eng.rng, eng.num_clients, eng.cfg.C)
            else:
                ids_np = eng.rng.choice(
                    eng.num_clients, size=width, replace=False
                )
            ids_np = np.asarray(ids_np)
            key = jax.random.PRNGKey(int(eng.rng.integers(2**31)))
            lr = jnp.float32(eng.lr_at(eng.round_idx))
            flat, per_loss, w = eng._client_phase_jit(
                eng.params, eng._x, eng._y, eng._counts, eng._spe,
                jnp.asarray(ids_np, jnp.int32),
                jnp.ones(width, jnp.float32), key, lr,
            )
            t_obs, ok = model.draw(lat_rng, ids_np, speed)
            gid = state["gid"]
            state["gid"] += 1
            groups[gid] = {
                "flat": flat, "loss": per_loss, "w": w,
                "version": eng.round_idx, "live": width,
            }
            for r in range(width):
                heapq.heappush(
                    heap,
                    (state["now"] + float(t_obs[r]), state["seq"], gid, r,
                     bool(ok[r])),
                )
                state["seq"] += 1
            state["in_flight"] += width

        def release(gid: int):
            groups[gid]["live"] -= 1
            if groups[gid]["live"] == 0:
                del groups[gid]

        def apply_buffer(entries) -> float:
            """Aggregate ≤K buffered updates (zero-weight ghost rows pad a
            forced partial apply to the static width K) and step the
            server. Returns the buffer's weighted train loss."""
            rows = [
                (groups[g]["flat"][r], groups[g]["loss"][r], groups[g]["w"][r],
                 eng.round_idx - groups[g]["version"])
                for g, r in entries
            ]
            pad = K - len(rows)
            flat = jnp.stack([r[0] for r in rows])
            per_loss = jnp.stack([r[1] for r in rows])
            w = jnp.stack([r[2] for r in rows])
            stale = jnp.asarray([float(r[3]) for r in rows], jnp.float32)
            if pad:
                flat = jnp.concatenate([flat, jnp.zeros((pad,) + flat.shape[1:], flat.dtype)])
                per_loss = jnp.concatenate([per_loss, jnp.zeros(pad, per_loss.dtype)])
                w = jnp.concatenate([w, jnp.zeros(pad, w.dtype)])
                stale = jnp.concatenate([stale, jnp.zeros(pad, jnp.float32)])
            eng.params, eng.outer_state, loss = eng._apply_jit(
                eng.params, eng.outer_state, flat, per_loss, w, stale,
            )
            for g, r in entries:
                release(g)
            eng.round_idx += 1
            return float(jax.block_until_ready(loss))

        applies = 0
        last_sim = 0.0
        t0 = time.perf_counter()
        dispatch(m)
        while applies < n_rounds:
            forced_partial = False
            if not heap:
                if buffer:
                    # Everyone else failed and the buffer can never fill:
                    # apply what arrived rather than deadlock.
                    forced_partial = True
                else:
                    dispatch(m - state["in_flight"])
                    continue
            if not forced_partial:
                t, _, gid, row, ok = heapq.heappop(heap)
                state["now"] = t
                state["in_flight"] -= 1
                if ok:
                    buffer.append((gid, row))
                else:
                    release(gid)
                if len(buffer) < K:
                    continue
            entries, buffer = buffer[:K], []
            loss = apply_buffer(entries)
            applies += 1
            rec = RoundRecord(
                round=eng.round_idx,
                train_loss=loss,
                wall_s=time.perf_counter() - t0,
                sim_s=state["now"] - last_sim,
            )
            t0 = time.perf_counter()
            last_sim = state["now"]
            if eng.eval_fn is not None and (
                eng.round_idx % eval_every == 0 or applies == n_rounds
            ):
                ev = eng.eval_fn(eng.params)
                rec.test_acc = float(ev["acc"])
                rec.test_loss = float(ev.get("loss", np.nan))
                if verbose:
                    print(
                        f"apply {eng.round_idx:5d} (sim t={state['now']:.1f}s) "
                        f"loss {rec.train_loss:.4f} "
                        f"test_acc {rec.test_acc:.4f}"
                    )
                eng.history.records.append(rec)
                if target_acc is not None and rec.test_acc >= target_acc:
                    break
            else:
                eng.history.records.append(rec)
            # Refill only while more applies remain: a trailing dispatch
            # after the last apply would consume the engine's sampling RNG
            # (and a client-phase execution) for a group nobody ever
            # aggregates, desyncing the degenerate lane from sync on any
            # later run() call.
            if applies < n_rounds:
                dispatch(m - state["in_flight"] - len(buffer))
        return eng.history
