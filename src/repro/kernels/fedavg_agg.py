"""Pallas TPU kernel for the FedAvg server aggregation (Algorithm 1's
``w <- sum_k (n_k/n) w_k``) — the per-round hot loop of the paper.

The K client models arrive stacked as (K, N) over the flattened parameter
vector; weights (K,) are **pre-normalized to sum to 1**. Normalization
happens in exactly one place — ``repro.core.fedavg.server_aggregate`` (and
its pytree adapter ``repro.kernels.ops.tree_fedavg_aggregate``), which is
the only sanctioned entry point for raw example counts n_k. This module
asserts the contract on concrete (non-traced) weights and documents it for
traced ones, where a value check is impossible.

The kernel tiles N into VMEM-sized blocks (grid dim 1) and reduces over K
in VMEM with an ``accum_dtype`` accumulator (float32 by default) regardless
of the storage dtype — averaging bf16 client deltas in bf16 loses ~3
decimal digits per 2x clients, which materially hurts FedAvg convergence.
``accum_dtype`` is exposed (and threaded through ``ops.py``) so tests can
demonstrate exactly that precision cliff; production code should leave the
default.

``interpret=True`` executes the kernel body in Python via the Pallas
interpreter — the CPU-test fallback (Pallas does not lower on the CPU SPMD
backend). On real TPU hardware leave ``interpret=False``.

On a pod this same kernel implements the local all-reduce combiner; across
pods the mesh all-reduce handles the final combine (see core/local_sgd.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# Bytes a kernel's VMEM tiles may take: double-buffered input/output blocks
# plus in-kernel temporaries. Half of the 16 MiB default scoped-VMEM limit
# of a TPU v5e core, leaving the compiler room for its own scratch.
VMEM_TILE_BUDGET = 8 << 20


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def lane_block(per_lane_bytes: int, cap: int,
               budget: int = VMEM_TILE_BUDGET) -> int:
    """Largest power-of-two lane width in [128, ``cap``] whose tiles
    (``per_lane_bytes`` per column) fit ``budget`` bytes. Powers of two
    from 128 up are multiples of 128, which the TPU tiling rule needs for
    any block that does not span its whole axis."""
    b = 128
    while b * 2 <= cap and b * 2 * per_lane_bytes <= budget:
        b *= 2
    return b


def interpret_block_n(n: int) -> int:
    """Block width for INTERPRET mode: one block covering all ``n``
    columns (capped at 1M to bound the emulated tile).

    The emulated grid is an XLA while loop with per-step
    slice/dispatch overhead that dwarfs the block math at simulation
    sizes — a single (K, N) step runs ~10x faster than the hardware
    default's N/16384 steps on the CPU CI box. Block width within the
    single-step regime is irrelevant (``_aggregate_impl`` clamps to N
    anyway); only the step COUNT matters."""
    return min(max(n, 1), 1 << 20)


def hardware_block_n(k: int) -> int:
    """Block width on the chip for a K-client cohort: the widest
    power-of-two column tile (at most 16384) whose double-buffered
    ``(K, bn)`` input, fp32 working copy and ``(1, bn)`` output fit
    :data:`VMEM_TILE_BUDGET`. K rows pad to the 8-row sublane tile; the
    input is priced at 4 bytes per element whatever its storage dtype,
    which also covers a bf16 input plus its fp32 cast. 16384 for K <= 16,
    4096 at K = 100, 1024 at K = 512, 128 from K ~ 2700 on."""
    kp = round_up(max(k, 1), 8)
    return lane_block(4 * (3 * kp + 3 * 8), 16384)


def _agg_kernel(w_ref, params_ref, o_ref, *, accum_dtype):
    # params_ref: (K, bn); w_ref: (K, 1); o_ref: (1, bn). A weighted
    # broadcast-multiply and a reduction over the client (sublane) axis,
    # both in accum_dtype on the vector unit: exact fp32 arithmetic, and the
    # kernel is bound by reading (K, bn) from HBM, not by these K FLOPs per
    # column.
    p = params_ref[...].astype(accum_dtype)          # (K, bn)
    w = w_ref[...].astype(accum_dtype)               # (K, 1)
    acc = jnp.sum(p * w, axis=0, keepdims=True)      # (1, bn)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "interpret", "accum_dtype")
)
def _aggregate_impl(stacked, weights, *, block_n, interpret, accum_dtype):
    K, N = stacked.shape
    block_n = min(block_n, N)
    pad = (-N) % block_n
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    nb = stacked.shape[1] // block_n
    w2 = weights.reshape(K, 1).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_agg_kernel, accum_dtype=accum_dtype),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, nb * block_n), stacked.dtype),
        interpret=interpret,
    )(w2, stacked)
    return out[0, :N]


def fedavg_aggregate(
    stacked: jnp.ndarray,   # (K, N) flattened client parameters
    weights: jnp.ndarray,   # (K,) normalized (sum to 1) — see module docstring
    *,
    block_n=None,
    interpret: bool = False,
    accum_dtype=jnp.float32,
) -> jnp.ndarray:
    """Weighted sum over the client axis: (K, N), (K,) -> (N,).

    ``block_n=None`` picks the backend policy: a VMEM-sized tile chosen
    from K on hardware (:func:`hardware_block_n`), one grid step
    (:func:`interpret_block_n`) in interpret mode. Block choice never
    changes numerics — each output coordinate reduces over K inside its
    own block.

    Contract: ``weights`` must already sum to 1 (normalize raw n_k in
    ``server_aggregate``, nowhere else). Checked eagerly when ``weights``
    is concrete; under a surrounding jit trace the check is skipped and the
    caller's contract applies.

    Sanctioned exception — partial-sum mode: the cohort-sharded adapters
    (``ops.sharded_fedavg_aggregate`` and the quantized analogue) call this
    kernel per shard with UNnormalized weights, because sum==1 is a
    property of the full cohort and cannot hold for an (m/D,) slice; they
    restore the contract globally by psum-ming the partial sums and the
    weight total before a single division. The kernel body is a plain
    weighted sum either way. If this check is ever strengthened to run
    under trace (e.g. checkify), it must exempt — or gain a flag for —
    that partial-sum mode.
    """
    if not isinstance(weights, jax.core.Tracer):
        s = float(jnp.sum(jnp.asarray(weights, jnp.float32)))
        if abs(s - 1.0) > 1e-3:
            raise ValueError(
                "fedavg_aggregate requires pre-normalized weights (sum==1); "
                f"got sum={s:.6f}. Pass raw counts to server_aggregate / "
                "tree_fedavg_aggregate instead — normalization lives there."
            )
    if block_n is None:
        block_n = (
            interpret_block_n(stacked.shape[1]) if interpret
            else hardware_block_n(stacked.shape[0])
        )
    return _aggregate_impl(
        stacked,
        weights,
        block_n=block_n,
        interpret=interpret,
        accum_dtype=jnp.dtype(accum_dtype),
    )
