"""Pallas TPU kernel for the gossip neighbor-mixing step ``X <- W @ X``.

The decentralized lane's hot loop: every node replaces its parameter
vector with the Metropolis–Hastings-weighted average of its graph
neighborhood (see ``core/topology.py``). Replicas arrive stacked as
``(n_nodes, N)`` over the flattened parameter vector; the topology is the
static padded pair ``idx``/``weight`` of shape ``(n_nodes, max_slots)``
from ``MixingPlan`` — padded slots carry weight 0, so the contraction is
exact for ragged degrees while every shape stays static for jit.

Kernel shape regime: where ``fedavg_agg`` reduces ``cohort x params`` down
to one row, this kernel maps ``(n_nodes, N) -> (n_nodes, N)`` — a sparse
row-mix. Per grid step it takes a block of nodes and a block of columns,
expands that block's neighbor ids into a one-hot ``(block_nodes, n_nodes)``
row-slice of W (weights scattered by compare-with-iota — the standard TPU
reformulation of a dynamic row gather into an MXU matmul, which Mosaic
lowers well where per-row dynamic gathers do not), and contracts it
against the full node axis of the column block in ``accum_dtype`` fp32
(``preferred_element_type``; bf16 storage supported). Duplicate neighbor
ids accumulate — the one-hot rows add — matching the dense oracle
:func:`gossip_mix_ref` (``W @ X``) that tests pin the kernel against.

``interpret=True`` is the CPU-CI fallback; the interpret block policy is
ONE grid step (the emulated grid's per-step overhead dwarfs the block math
at simulation sizes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fedavg_agg import (
    VMEM_TILE_BUDGET,
    interpret_block_n,
    lane_block,
    round_up,
)


def gossip_blocks(n: int, slots: int) -> tuple:
    """Hardware ``(block_nodes, block_n)`` for ``n`` nodes of ``slots``
    neighbor slots. Up to 128 output nodes per block, halved (in multiples
    of 8) until the one-hot ``(block_nodes, slots, n)`` expansion and the
    dense ``(block_nodes, n)`` row slice of W take at most half of
    :data:`~repro.kernels.fedavg_agg.VMEM_TILE_BUDGET`; then the widest
    column tile whose double-buffered full-node ``(n, bn)`` input, its
    fp32 copy, the double-buffered output and the accumulator fit the
    rest. Every input is priced at 4 bytes per element. At n = 100 ring
    nodes this is (100, 2048)."""
    lanes = round_up(n, 128)

    def rows_bytes(bn_):
        return bn_ * (round_up(slots, 8) + 8) * lanes * 4

    block_nodes = min(n, 128)
    while block_nodes > 8 and rows_bytes(block_nodes) > VMEM_TILE_BUDGET // 2:
        block_nodes = max(8, round_up(block_nodes // 2, 8))
    per_lane = 4 * (3 * round_up(n, 8) + 3 * round_up(block_nodes, 8))
    return block_nodes, lane_block(
        per_lane, 16384, VMEM_TILE_BUDGET - rows_bytes(block_nodes)
    )


def _mix_kernel(idx_ref, w_ref, x_ref, o_ref, *, accum_dtype):
    # idx_ref/w_ref: (bn, D); x_ref: (n_all, bc) — the FULL node axis for
    # this column block, because a node's neighbors can live anywhere.
    idx = idx_ref[...]                                   # (bn, D) int32
    w = w_ref[...].astype(accum_dtype)                   # (bn, D)
    x = x_ref[...].astype(accum_dtype)                   # (n_all, bc)
    n_all = x.shape[0]
    # Scatter the padded neighbor weights into a dense (bn, n_all) row
    # slice of W: one-hot(idx) weighted by w, summed over the slot axis.
    # Duplicate ids in a row accumulate (sum over D), which is exactly
    # W @ X semantics for a multigraph row.
    node_ids = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_all), 2)
    onehot = (idx[:, :, None] == node_ids).astype(accum_dtype)
    w_rows = jnp.sum(w[:, :, None] * onehot, axis=1)     # (bn, n_all)
    # Explicit fp32 contract precision, so the MXU cannot round the mixing
    # weights or the parameters to bf16.
    acc = jax.lax.dot_general(
        w_rows, x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=accum_dtype,
    )                                                    # (bn, bc)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_nodes", "block_n", "interpret", "accum_dtype"),
)
def _mix_impl(x, idx, weight, *, block_nodes, block_n, interpret,
              accum_dtype):
    n, N = x.shape
    D = idx.shape[1]
    block_nodes = min(block_nodes, n)
    block_n = min(block_n, N)
    pad_n = (-n) % block_nodes
    pad_c = (-N) % block_n
    if pad_c:
        x = jnp.pad(x, ((0, 0), (0, pad_c)))
    if pad_n:
        # Ghost nodes: idx 0 with weight 0 — they read row 0 and write a
        # zero row that the final slice drops. x keeps its true node axis;
        # only the per-block idx/weight/output grids are padded.
        idx = jnp.pad(idx, ((0, pad_n), (0, 0)))
        weight = jnp.pad(weight, ((0, pad_n), (0, 0)))
    gn = (n + pad_n) // block_nodes
    gc = (N + pad_c) // block_n
    out = pl.pallas_call(
        functools.partial(_mix_kernel, accum_dtype=accum_dtype),
        grid=(gn, gc),
        in_specs=[
            pl.BlockSpec((block_nodes, D), lambda i, j: (i, 0)),
            pl.BlockSpec((block_nodes, D), lambda i, j: (i, 0)),
            pl.BlockSpec((n, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_nodes, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (n + pad_n, N + pad_c), x.dtype
        ),
        interpret=interpret,
    )(idx, weight.astype(jnp.float32), x)
    return out[:n, :N]


def gossip_mix(
    x: jnp.ndarray,       # (n_nodes, N) stacked per-node parameter vectors
    idx: jnp.ndarray,     # (n_nodes, max_slots) int32 neighbor slots
    weight: jnp.ndarray,  # (n_nodes, max_slots) fp32, rows sum to 1
    *,
    block_nodes=None,
    block_n=None,
    interpret: bool = False,
    accum_dtype=jnp.float32,
) -> jnp.ndarray:
    """One neighbor-mixing step: ``out[i] = sum_s weight[i,s] * x[idx[i,s]]``.

    Equivalent to ``W @ x`` for the dense mixing matrix ``W`` the padded
    slots encode (:func:`gossip_mix_ref` is that oracle) — padded slots
    have weight 0 and contribute nothing; duplicate ids accumulate.

    ``block_nodes=None`` / ``block_n=None`` pick the backend policy:
    VMEM-sized tiles chosen from n on hardware (:func:`gossip_blocks`),
    one grid step in interpret mode. Block choice never changes
    numerics — every output row contracts the full slot axis in
    ``accum_dtype`` inside its own block.

    Contract: each ``weight`` row sums to 1 (a ``MixingPlan`` guarantees
    it — Metropolis–Hastings rows are stochastic by construction). Checked
    eagerly on concrete weights; under a surrounding trace the caller's
    contract applies.
    """
    if not isinstance(weight, jax.core.Tracer):
        rows = jnp.sum(jnp.asarray(weight, jnp.float32), axis=1)
        err = float(jnp.max(jnp.abs(rows - 1.0)))
        if err > 1e-3:
            raise ValueError(
                "gossip_mix requires row-stochastic weights (each row sums "
                f"to 1); worst row off by {err:.6f}. Build them with "
                "Topology.build() — the MH construction lives there."
            )
    n, N = x.shape
    if idx.shape != weight.shape or idx.shape[0] != n:
        raise ValueError(
            f"idx/weight must both be (n_nodes, max_slots) = ({n}, D); "
            f"got idx {idx.shape}, weight {weight.shape}"
        )
    hw_nodes, hw_n = gossip_blocks(n, idx.shape[1])
    if block_nodes is None:
        block_nodes = n if interpret else hw_nodes
    if block_n is None:
        block_n = interpret_block_n(N) if interpret else hw_n
    return _mix_impl(
        x, jnp.asarray(idx, jnp.int32), weight,
        block_nodes=block_nodes, block_n=block_n,
        interpret=interpret, accum_dtype=accum_dtype,
    )


def gossip_mix_ref(x, idx, weight, *, accum_dtype=jnp.float32):
    """Dense oracle: materialize W from the padded slots and do ``W @ X``
    in plain jnp. Duplicate ids accumulate via the one-hot sum, exactly
    like the kernel. Tests pin ``gossip_mix == gossip_mix_ref``."""
    n = x.shape[0]
    onehot = (idx[:, :, None] == jnp.arange(n)[None, None, :]).astype(
        accum_dtype
    )
    W = jnp.einsum(
        "nd,ndm->nm", weight.astype(accum_dtype), onehot,
        preferred_element_type=accum_dtype,
    )
    out = jnp.einsum(
        "nm,mc->nc", W, x.astype(accum_dtype),
        preferred_element_type=accum_dtype,
    )
    return out.astype(x.dtype)
