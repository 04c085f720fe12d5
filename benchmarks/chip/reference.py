"""Plain reference of a FedAvg round, independent of the program.

Algorithm 1 of McMahan et al. in straightforward ``jax.numpy``: the server
draws a cohort, each client runs E epochs of minibatch SGD from the global
weights on its own data, the server averages the clients' changes weighted
by their example counts and adds the average to the global weights. The
upload codec, where the traffic names one, is the paper-era stochastic
uniform quantizer applied to each client's raveled change.

Which cohort, which example order and which rounding noise a round uses
are part of the traffic: they are drawn from the run's seed by the schemes
written out below (a numpy cohort stream for per-round dispatch, a jax key
chain for on-device sampling, one uniform permutation per client and
epoch), so the reference trains on the same batches as the system under
test without reading anything the system made.

``dtype``/``precision`` select the arithmetic: float32 at ``HIGHEST`` is the
reference; bfloat16 everywhere is the lower-precision control. ``fault=
"half_batch"`` plants a fault the comparison must catch: every minibatch
trains on its first half, the mean taken over it.

Labels are class ids of any shape: one per example, or one per position of
a token sequence. ``clients_per_block`` runs the cohort that many clients
at a time, for a cohort whose copies of the weights do not fit at once:
each client's draws stay keyed by its slot in the whole cohort, and a
float32 running sum holds the count-weighted changes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CODEC_KEY_SALT = 0x5EED


def cohort_size(fedavg: dict, n_clients: int) -> int:
    return max(int(round(float(fedavg["C"]) * n_clients)), 1)


def cohort_schedule(traffic_spec: dict, n_clients: int, seed: int,
                    n_rounds: int):
    """``[(ids, key)]`` per round: the cohort's client ids and the round's
    data key (per-client permutations and codec noise derive from it)."""
    m = cohort_size(traffic_spec["fedavg"], n_clients)
    out = []
    if traffic_spec.get("execution", {}).get("device_sampling", False):
        key = jax.random.PRNGKey(seed)
        for _ in range(n_rounds):
            k_cohort, k_data, key = jax.random.split(key, 3)
            u = jax.random.uniform(k_cohort, (n_clients,))
            out.append((np.asarray(jnp.argsort(u)[:m]), k_data))
        return out
    rng = np.random.default_rng(seed)
    for _ in range(n_rounds):
        ids = rng.choice(n_clients, size=m, replace=False)
        out.append((ids, jax.random.PRNGKey(int(rng.integers(2**31)))))
    return out


def _client_orders(key, slots, n, epochs):
    """``(len(slots), epochs * n)`` row order: one uniform permutation of
    each client's n rows per epoch, keyed by the client's slot in the cohort
    and the epoch."""
    def one(slot):
        ck = jax.random.fold_in(key, slot)
        return jax.vmap(lambda e: jnp.argsort(
            jax.random.uniform(jax.random.fold_in(ck, e), (n,))
        ))(jnp.arange(epochs, dtype=jnp.int32)).reshape(-1)
    return jax.vmap(one)(slots)


def _quantize_roundtrip(flat, key, bits, chunk):
    """Stochastic uniform quantization of a raveled change and its decode:
    chunks of ``chunk`` values (the tail repeats the last value), each coded
    in ``2**bits`` levels between its own min and max."""
    levels = 2**bits - 1
    n = flat.shape[0]
    v = jnp.pad(flat.astype(jnp.float32), (0, (-n) % chunk), mode="edge")
    v = v.reshape(-1, chunk)
    lo = jnp.min(v, axis=1)
    scale = jnp.max(v, axis=1) - lo
    x = (v - lo[:, None]) / jnp.maximum(scale, 1e-12)[:, None] * levels
    q = jnp.clip(jnp.floor(x + jax.random.uniform(key, v.shape)), 0, levels)
    return (q * (scale / levels)[:, None] + lo[:, None]).reshape(-1)[:n]


def cross_entropy(logits, y):
    """Mean softmax cross-entropy of float32 ``logits`` ``(..., classes)``
    against class ids ``y`` of the leading shape."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _clients(apply, params, xs, ys, slots, key, lr, *, epochs, batch,
             precision, codec, fault):
    """Local training of the clients in cohort ``slots`` from the global
    weights, on their rows ``xs`` ``(c, n, ...)``. Returns each client's
    change (after the upload codec) and its mean loss over its steps."""
    m, n = xs.shape[:2]
    b = n if batch is None else int(batch)
    steps = epochs * (n // b)
    order = _client_orders(key, slots, n, epochs).reshape(m, steps, b)
    if fault == "half_batch":
        half = order[:, :, : b // 2]
        order = jnp.concatenate([half, half], axis=2)[:, :, :b]
    bx = jax.vmap(lambda rows, o: rows[o])(xs, order)        # (m, S, b, ...)
    by = jax.vmap(lambda rows, o: rows[o])(ys, order)
    dtype = jax.tree.leaves(params)[0].dtype

    def loss_fn(p, x, y):
        return cross_entropy(apply(p, x, precision).astype(jnp.float32), y)

    def client(x_steps, y_steps):
        def sgd(w, xy):
            loss, g = jax.value_and_grad(loss_fn)(w, *xy)
            w = jax.tree.map(lambda a, d: (a - lr.astype(dtype) * d), w, g)
            return w, loss
        return jax.lax.scan(sgd, params, (x_steps, y_steps))

    client_params, losses = jax.vmap(client)(bx, by)
    deltas = jax.tree.map(lambda c, p: c - p, client_params, params)
    if codec is not None:
        leaves, treedef = jax.tree.flatten(deltas)
        flat = jnp.concatenate([l.reshape(m, -1) for l in leaves], axis=1)
        ckey = jax.random.fold_in(key, CODEC_KEY_SALT)
        keys = jax.vmap(lambda s: jax.random.fold_in(ckey, s))(slots)
        flat = jax.vmap(partial(
            _quantize_roundtrip, bits=int(codec["bits"]),
            chunk=int(codec["chunk"]),
        ))(flat, keys).astype(dtype)
        out, off = [], 0
        for l in leaves:
            size = int(np.prod(l.shape[1:]))
            out.append(flat[:, off:off + size].reshape(l.shape))
            off += size
        deltas = jax.tree.unflatten(treedef, out)
    return deltas, jnp.mean(losses, axis=1)


def _weighted_sum(wn, deltas, loss, precision):
    """The count-weighted sum of the clients' changes and losses; ``wn``
    are the clients' shares of the whole cohort's examples."""
    wd = wn.astype(jax.tree.leaves(deltas)[0].dtype)
    avg = jax.tree.map(
        lambda d: jnp.tensordot(wd, d, axes=1, precision=precision), deltas
    )
    return avg, jnp.sum(wn * loss)


def _round(apply, params, xs, ys, weights, key, lr, *, epochs, batch,
           precision, codec, fault):
    """One round over the whole cohort's rows ``xs`` ``(m, n, ...)`` at
    once. Returns the new global weights and the round's train loss (the
    count-weighted mean over clients of each client's mean loss over its
    steps)."""
    slots = jnp.arange(xs.shape[0], dtype=jnp.int32)
    deltas, loss = _clients(apply, params, xs, ys, slots, key, lr,
                            epochs=epochs, batch=batch, precision=precision,
                            codec=codec, fault=fault)
    avg, loss = _weighted_sum(weights / jnp.sum(weights), deltas, loss,
                              precision)
    return jax.tree.map(lambda p, a: p + a, params, avg), loss


def _block(apply, params, xs, ys, slots, wn, key, lr, acc, loss, *,
           precision, **kw):
    """The clients in ``slots`` added to the running float32 sums ``acc``
    (weighted changes) and ``loss``."""
    deltas, losses = _clients(apply, params, xs, ys, slots, key, lr,
                              precision=precision, **kw)
    avg, part = _weighted_sum(wn, deltas, losses, precision)
    acc = jax.tree.map(lambda a, d: a + d.astype(jnp.float32), acc, avg)
    return acc, loss + part


def run_reference(apply, clients, init_params, traffic_spec, seed, n_rounds,
                  snapshot_at, *, dtype=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST, fault=None,
                  clients_per_block=None):
    """Train ``n_rounds`` reference rounds from ``init_params``. Returns
    ``{"losses": [...], "params": {r: host tree after round r}}`` for each
    ``r`` in ``snapshot_at``. ``clients_per_block``: run the cohort that
    many clients at a time (``None``: all at once)."""
    sizes = {len(x) for x, _ in clients}
    if len(sizes) != 1:
        raise ValueError("the reference round needs clients of equal size")
    fed = traffic_spec["fedavg"]
    epochs, batch = int(fed["E"]), fed.get("B")
    n = sizes.pop()
    if batch is not None and n % int(batch):
        raise ValueError(f"{n} examples per client is not a multiple of B")
    codec = traffic_spec.get("codec")
    if codec is not None and codec["kind"] != "quantize":
        raise ValueError(f"the reference has no {codec['kind']!r} codec")
    xs_all = jnp.asarray(np.stack([x for x, _ in clients]))
    ys_all = jnp.asarray(np.stack([y for _, y in clients]))
    weights_all = jnp.asarray([len(x) for x, _ in clients], jnp.float32)
    kw = dict(epochs=epochs, batch=batch, precision=precision, codec=codec,
              fault=fault)
    if clients_per_block is None:
        step = jax.jit(partial(_round, apply, **kw))
    else:
        block = jax.jit(partial(_block, apply, **kw), donate_argnums=(7,))
        step = partial(_blocked_round, block, int(clients_per_block))
    lr0 = float(fed["lr"])
    decay = float(fed.get("lr_decay", 1.0))
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), init_params)
    losses, snaps = [], {}
    schedule = cohort_schedule(traffic_spec, len(clients), seed, n_rounds)
    for r, (ids, key) in enumerate(schedule):
        ids = jnp.asarray(ids)
        params, loss = step(params, xs_all[ids], ys_all[ids],
                            weights_all[ids], key,
                            jnp.float32(lr0 * decay**r))
        losses.append(float(loss))
        if r + 1 in snapshot_at:
            snaps[r + 1] = jax.tree.map(
                lambda a: np.array(a, np.float32), params
            )
    return {"losses": losses, "params": snaps}


def _blocked_round(block, c, params, xs, ys, weights, key, lr):
    """:func:`_round` with the cohort run ``c`` clients at a time by the
    jitted :func:`_block`."""
    m = xs.shape[0]
    wn = weights / jnp.sum(weights)
    acc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    loss = jnp.float32(0)
    for s in range(0, m, c):
        slots = jnp.arange(s, min(s + c, m), dtype=jnp.int32)
        acc, loss = block(params, xs[s:s + c], ys[s:s + c], slots, wn[s:s + c],
                          key, lr, acc, loss)
    return jax.tree.map(lambda p, a: p + a.astype(p.dtype), params, acc), loss
