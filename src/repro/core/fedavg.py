"""FederatedAveraging — Algorithm 1 of McMahan et al. (AISTATS 2017).

The three pieces of the algorithm, as composable jit-able functions:

- ``client_update``      ClientUpdate(k, w): E epochs of minibatch SGD on the
                         client's local data, starting from the global model.
- ``server_aggregate``   w_{t+1} = sum_k (n_k / n) w^k_{t+1}.
- ``sample_clients``     S_t = random set of m = max(C*K, 1) clients.

``FedAvgConfig(E=1, B=None)`` is exactly FedSGD (one full-batch gradient step
per round), the paper's baseline — tests assert this equivalence to machine
precision.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.tree import tree_weighted_mean  # noqa: F401 (reference impl)


@dataclasses.dataclass(frozen=True)
class FedAvgConfig:
    """Paper hyper-parameters (Section 2).

    C: fraction of clients per round; the server samples m = max(C*K, 1).
    E: local epochs per round.
    B: local minibatch size; None means B = inf (full local batch).
    lr: client SGD learning rate (float or step->lr schedule over ROUNDS).
    lr_decay: optional per-round multiplicative decay (CIFAR experiments).
    """

    C: float = 0.1
    E: int = 1
    B: Optional[int] = 10
    lr: float = 0.1
    lr_decay: float = 1.0
    seed: int = 0

    def expected_updates_per_round(self, n: int, K: int) -> float:
        """u = E * n / (K * B) — Table 2's ordering statistic."""
        b = self.B if self.B is not None else n / K
        return self.E * n / (K * b)


def sample_clients(rng: np.random.Generator, n_clients: int, C: float) -> np.ndarray:
    """S_t <- random set of m clients, m = max(C*K, 1)."""
    m = max(int(round(C * n_clients)), 1)
    return rng.choice(n_clients, size=m, replace=False)


def sample_clients_device(key, n_clients: int, m: int) -> jnp.ndarray:
    """On-device S_t draw: m distinct client ids, uniform without
    replacement — argsort of keyed uniforms over the K clients, keep the
    first m. Pure and traceable, so the whole cohort draw lives inside the
    round executable (``RoundEngine`` supersteps scan it over R rounds with
    the key threaded through the carry).

    This is a DIFFERENT stream from :func:`sample_clients`' numpy draw:
    same distribution, different realizations for the same seed (see
    docs/engine.md "Supersteps" for the seed-compatibility notes). ``m`` is
    static — compute it host-side as ``max(round(C * K), 1)``, exactly as
    the numpy sampler does."""
    u = jax.random.uniform(key, (n_clients,))
    return jnp.argsort(u)[:m].astype(jnp.int32)


def client_update(
    loss_fn: Callable,
    params,
    batches,
    step_mask,
    lr,
    *,
    with_counters: bool = False,
) -> Any:
    """ClientUpdate(k, w) — Algorithm 1, right column.

    ``batches``: pytree of arrays with leading (n_steps, B, ...) axis holding
    the client's full E-epoch batch schedule. ``step_mask``: (n_steps,) 0/1
    float — padded steps (for vmap-ing ragged clients together) are no-ops.
    Plain SGD with fixed per-round lr, as in the paper.

    Returns ``(params, losses)``; with ``with_counters`` also the per-step
    counters the model reports under ``"counters"`` in its loss's aux dict
    (leaves (n_steps, ...); ``{}`` for a model that reports none).
    """

    def one_step(w, inp):
        batch, mask = inp
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(w, batch)
        w = jax.tree.map(lambda p, g: p - lr * mask * g, w, grads)
        if with_counters:
            counters = aux.get("counters", {}) if isinstance(aux, dict) else {}
            return w, (loss, counters)
        return w, loss

    params, out = jax.lax.scan(one_step, params, (batches, step_mask))
    if with_counters:
        losses, counters = out
        return params, losses, counters
    return params, out


def counter_sums(counters, step_mask):
    """Each counter summed over the real (unmasked) steps of every client:
    ``counters`` leaves are (m, n_steps, ...), ``step_mask`` (m, n_steps).
    Divided by ``step_mask.sum()`` this is the cohort's mean a step."""
    def one(c):
        mask = step_mask.reshape(step_mask.shape + (1,) * (c.ndim - 2))
        return jnp.sum(c * mask, axis=(0, 1))
    return jax.tree.map(one, counters)


def masked_weighted_loss(losses, step_mask, client_weights, *, axis_name=None):
    """Round train-loss metric shared by every round_step implementation:
    mean loss over each client's REAL (unmasked) steps, weighted by client
    example count. One definition — the identity-codec equivalence tests
    require the plain, compressed, and legacy-loop paths to agree
    bit-for-bit on it.

    ``axis_name``: inside a ``shard_map`` over a client axis, each shard
    holds only its cohort slice; the numerator/denominator then finish with
    a ``psum`` so every shard reports the same global loss. Ghost (padding)
    clients carry weight 0 and drop out of both sums. The unsharded branch
    keeps the original normalize-then-sum association bit-for-bit."""
    per_client = jnp.sum(losses * step_mask, axis=1) / jnp.maximum(
        jnp.sum(step_mask, axis=1), 1.0
    )
    if axis_name is None:
        w = client_weights / jnp.sum(client_weights)
        return jnp.sum(w * per_client)
    num = jax.lax.psum(jnp.sum(client_weights * per_client), axis_name)
    den = jax.lax.psum(jnp.sum(client_weights), axis_name)
    return num / den


def server_aggregate(stacked_params, client_weights, *, interpret=None,
                     accum_dtype=jnp.float32, axis_name=None):
    """w_{t+1} <- sum_k (n_k/n) w^k_{t+1} — Algorithm 1's server line.

    ``client_weights`` are RAW example counts n_k; this is the ONE place on
    the hot path where they get normalized (inside the
    ``tree_fedavg_aggregate`` adapter, whose Pallas kernel asserts the
    normalized contract). The pure-jnp ``tree_weighted_mean`` remains the
    reference oracle in tests. ``interpret=None`` auto-selects the Pallas
    interpreter off-TPU (kernels do not lower on the CPU backend).

    ``axis_name``: cohort-sharded mode. Each shard sees the (m/D, ...) local
    slice of the stacked client params; the Pallas kernel then runs in
    partial-sum mode (UNnormalized weights) and a ``psum`` over the named
    client axis finishes both the weighted sum and the weight total before
    the single division — see ``ops.sharded_fedavg_aggregate``."""
    from repro.kernels.ops import (
        default_interpret,
        sharded_fedavg_aggregate,
        tree_fedavg_aggregate,
    )

    if interpret is None:
        interpret = default_interpret()
    if axis_name is not None:
        return sharded_fedavg_aggregate(
            stacked_params, client_weights, axis_name=axis_name,
            interpret=interpret, accum_dtype=accum_dtype,
        )
    return tree_fedavg_aggregate(
        stacked_params, client_weights, interpret=interpret,
        accum_dtype=accum_dtype,
    )


@partial(jax.jit, static_argnums=(0,), static_argnames=("interpret",))
def fedavg_round(loss_fn, params, batches, step_mask, client_weights, lr,
                 *, interpret=None):
    """One synchronous round over the m sampled clients (vmapped).

    batches leaves: (m, n_steps, B, ...); step_mask: (m, n_steps);
    client_weights: (m,) raw example counts n_k (normalized once, inside
    ``server_aggregate``). Returns (new_global_params, mean_train_loss).
    """
    upd = jax.vmap(lambda b, msk: client_update(loss_fn, params, b, msk, lr))
    client_params, losses = upd(batches, step_mask)
    new_params = server_aggregate(client_params, client_weights,
                                  interpret=interpret)
    return new_params, masked_weighted_loss(losses, step_mask, client_weights)


def one_shot_average(loss_fn, params, client_batches, client_masks, weights, lr):
    """The degenerate endpoint discussed in Related Work: train each client
    to convergence locally once, average once. Provided as a baseline."""
    return fedavg_round(loss_fn, params, client_batches, client_masks, weights, lr)
