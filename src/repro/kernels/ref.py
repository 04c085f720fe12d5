"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` mirrors its kernel's exact signature/semantics; tests sweep
shapes and dtypes asserting allclose between kernel (interpret=True on CPU)
and oracle. ``densify_ref`` is the oracle of the top-k lane's XLA
scatter-add aggregate, which has no Pallas kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention_core import naive_attention


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    """(BH, S, D) single-head layout -> naive softmax attention."""
    out = naive_attention(
        q[:, :, None, :], k[:, :, None, :], v[:, :, None, :],
        causal=causal, window=window,
    )
    return out[:, :, 0, :]


def fedavg_aggregate_ref(stacked, weights):
    w = weights.astype(jnp.float32)
    return jnp.sum(
        stacked.astype(jnp.float32) * w[:, None], axis=0
    ).astype(stacked.dtype)


def densify_ref(idx, vals, n: int):
    """(K, k) sparse top-k payloads -> dense (K, n) fp32, the oracle of
    ``ops.sparse_fedavg_aggregate``. Additive on duplicate indices (top-k
    indices are unique per client, where add == set)."""
    def one(i, v):
        return jnp.zeros((n,), jnp.float32).at[i].add(v.astype(jnp.float32))

    return jax.vmap(one)(idx, vals)


def ssm_scan_ref(dt, Bm, Cm, x, A, h0):
    """Sequential selective scan (same math as models/ssm.py)."""

    def step(h, inp):
        dt_t, b_t, c_t, x_t = inp
        dA = jnp.exp(dt_t[..., None] * A[None])
        h = dA * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    sw = lambda a: jnp.swapaxes(a, 0, 1)
    h, ys = jax.lax.scan(
        step,
        h0.astype(jnp.float32),
        (sw(dt.astype(jnp.float32)), sw(Bm.astype(jnp.float32)),
         sw(Cm.astype(jnp.float32)), sw(x.astype(jnp.float32))),
    )
    return jnp.swapaxes(ys, 0, 1).astype(x.dtype), h


def ce_loss_ref(hidden, head, labels):
    logits = (hidden @ head).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
