"""jit'd model-facing wrappers around the Pallas kernels.

These adapt model-layout tensors to kernel layouts (fold batch/heads,
broadcast GQA KV, flatten parameter pytrees) and expose ``interpret`` so the
CPU test environment executes the kernel bodies in Python. On real TPU
hardware, set interpret=False (the default) and these become the hot path;
the pure-JAX implementations in models/ remain the lowering used by the
dry-run (kernels do not lower on the CPU SPMD backend).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ce_loss import fused_cross_entropy
from repro.kernels.fedavg_agg import fedavg_aggregate
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gossip_mix import gossip_mix, gossip_mix_ref  # noqa: F401
from repro.kernels.quantized_agg import (
    packed_quantized_aggregate,
    quantized_aggregate,
)
from repro.kernels.ssm_scan import ssm_scan
from repro.utils.tree import tree_ravel_stacked, tree_unravel


def default_interpret() -> bool:
    """Single home for the backend policy: Pallas kernels only lower on
    TPU; everywhere else run the kernel body in the Pallas interpreter
    (slow but exact — the CPU test path)."""
    return jax.default_backend() != "tpu"


def mha_flash(q, k, v, *, causal=True, window=0, block_q=128, block_k=128,
              interpret=False):
    """(B, S, H, D) x (B, S, K, D) GQA attention via the flash kernel."""
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, -1, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, -1, D)
    out = flash_attention(
        qf, kf, vf, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


def tree_fedavg_aggregate(stacked_params, weights, *, interpret=False,
                          accum_dtype=jnp.float32, block_n=None):
    """Weighted-average a pytree whose leaves are (K, ...) stacked client
    params — Algorithm 1's server line, flattened through the Pallas kernel.

    ``weights`` are RAW example counts n_k; this adapter is the single place
    on the kernel path that normalizes them to sum to 1 (the kernel asserts
    that contract). ``accum_dtype`` is the in-kernel reduction dtype — fp32
    by default regardless of storage dtype (see kernels/fedavg_agg.py)."""
    # block_n=None lets the kernel pick the backend policy: 16k VMEM tiles
    # on hardware, a single grid step under the per-grid-cell-cost
    # interpreter (see kernels.fedavg_agg.interpret_block_n).
    flat, spec = tree_ravel_stacked(stacked_params)
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    avg = fedavg_aggregate(flat, w, interpret=interpret,
                           accum_dtype=accum_dtype, block_n=block_n)
    return tree_unravel(spec, avg)


def tree_gossip_mix(stacked_params, idx, weight, *, interpret=False,
                    accum_dtype=jnp.float32, block_nodes=None, block_n=None):
    """Gossip-mix a pytree whose leaves are (n_nodes, ...) stacked per-node
    replicas — the decentralized lane's ``X <- W @ X`` step, flattened
    through the Pallas :func:`gossip_mix` kernel.

    ``idx``/``weight`` are a ``MixingPlan``'s static padded arrays (see
    core/topology.py); the mixing contraction runs in ``accum_dtype`` fp32
    regardless of storage dtype, and each leaf round-trips back to its
    storage dtype through the recorded spec (bf16 replicas supported)."""
    flat, spec = tree_ravel_stacked(stacked_params)
    mixed = gossip_mix(
        flat, idx, weight, interpret=interpret, accum_dtype=accum_dtype,
        block_nodes=block_nodes, block_n=block_n,
    )
    return jax.vmap(lambda row: tree_unravel(spec, row))(mixed)


def sharded_fedavg_aggregate(stacked_params, weights, *, axis_name,
                             interpret=False, accum_dtype=jnp.float32,
                             block_n=None):
    """Cohort-sharded server aggregation: the partial-sum mode of
    :func:`tree_fedavg_aggregate` for use INSIDE a ``shard_map`` over a
    named client axis.

    Each shard holds the local (m/D, ...) slice of the stacked client
    params and its (m/D,) slice of the RAW example counts n_k. The Pallas
    kernel runs unchanged over the local slice with UNnormalized weights —
    a deliberate use of its partial-sum mode (see the note in
    kernels/fedavg_agg.py): the sum==1 contract is a property of the FULL
    cohort and cannot hold per shard, so here the kernel computes the
    plain weighted partial sum, a single ``jax.lax.psum`` finishes both
    that sum and the weight total across shards, and one division by the
    global total yields the weighted mean — identical to the unsharded
    result up to fp32 reassociation.

    The local partial sums are kept in ``accum_dtype`` (fp32 by default)
    until after the psum — summing partial results in bf16 storage dtype
    would lose exactly the precision the kernel's fp32 accumulator exists
    to protect; ``tree_unravel`` casts back to each leaf's storage dtype
    only at the very end. Ghost (cohort-padding) clients carry weight 0
    and vanish from both sums.
    """
    flat, spec = tree_ravel_stacked(stacked_params)
    w = jnp.asarray(weights, jnp.float32)
    partial = fedavg_aggregate(
        flat.astype(accum_dtype), w, interpret=interpret,
        accum_dtype=accum_dtype, block_n=block_n,
    )
    num = jax.lax.psum(partial, axis_name)
    den = jax.lax.psum(jnp.sum(w), axis_name)
    return tree_unravel(spec, num / den)


def quantized_fedavg_aggregate(codes, lo, scale, weights, *, chunk, levels,
                               interpret=False, accum_dtype=jnp.float32,
                               block_chunks=None):
    """Fused dequantize + weighted-average of uint8/uint16 client payloads
    — the compressed-upload server line, through the Pallas
    ``quantized_aggregate`` kernel.

    ``weights`` are RAW example counts n_k, normalized here (the kernel
    asserts the normalized contract, mirroring ``tree_fedavg_aggregate``).
    Returns the (N_pad,) fp32 averaged delta; callers slice to the real N.
    """
    # block_chunks=None defers to the kernel's backend policy (VMEM tiles
    # on hardware, one right-sized block under the interpreter).
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    return quantized_aggregate(
        codes, lo, scale, w, chunk=chunk, levels=levels,
        block_chunks=block_chunks, interpret=interpret,
        accum_dtype=accum_dtype,
    )


def sharded_quantized_fedavg_aggregate(codes, lo, scale, weights, *, chunk,
                                       levels, axis_name, interpret=False,
                                       accum_dtype=jnp.float32,
                                       block_chunks=None):
    """Partial-sum mode of :func:`quantized_fedavg_aggregate` for cohort
    sharding: inside a ``shard_map`` over ``axis_name``, each shard fuses
    dequantize + weighted accumulation over its local (m/D, N_pad) slice of
    the client codes with UNnormalized weights (the Pallas kernel runs
    unchanged), then one ``psum`` finishes the weighted sum and the weight
    total before the single division. The kernel already emits
    ``accum_dtype`` output, so nothing is lost crossing shards."""
    w = jnp.asarray(weights, jnp.float32)
    partial = quantized_aggregate(
        codes, lo, scale, w, chunk=chunk, levels=levels,
        block_chunks=block_chunks, interpret=interpret,
        accum_dtype=accum_dtype,
    )
    num = jax.lax.psum(partial, axis_name)
    den = jax.lax.psum(jnp.sum(w), axis_name)
    return num / den


def packed_quantized_fedavg_aggregate(words, lo, scale, weights, *, bits,
                                      chunk, levels, interpret=False,
                                      accum_dtype=jnp.float32,
                                      block_chunks=None):
    """Sub-byte twin of :func:`quantized_fedavg_aggregate`: the payload is
    the bit-packed uint32 wire words themselves (``utils.bitpack`` chunk
    framing) and the Pallas kernel unpacks + dequantizes + accumulates in
    one fused body. RAW counts normalized here, same contract."""
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    return packed_quantized_aggregate(
        words, lo, scale, w, bits=bits, chunk=chunk, levels=levels,
        block_chunks=block_chunks, interpret=interpret,
        accum_dtype=accum_dtype,
    )


def sharded_packed_quantized_fedavg_aggregate(words, lo, scale, weights, *,
                                              bits, chunk, levels, axis_name,
                                              interpret=False,
                                              accum_dtype=jnp.float32,
                                              block_chunks=None):
    """Partial-sum mode of :func:`packed_quantized_fedavg_aggregate` —
    identical psum-finished pattern to
    :func:`sharded_quantized_fedavg_aggregate`."""
    w = jnp.asarray(weights, jnp.float32)
    partial = packed_quantized_aggregate(
        words, lo, scale, w, bits=bits, chunk=chunk, levels=levels,
        block_chunks=block_chunks, interpret=interpret,
        accum_dtype=accum_dtype,
    )
    num = jax.lax.psum(partial, axis_name)
    den = jax.lax.psum(jnp.sum(w), axis_name)
    return num / den


def _sparse_weighted_sum(idx, values, weights, n, accum_dtype):
    """sum_k weights[k] * densify(idx[k], values[k]) as ONE XLA scatter-add
    of the K*k weighted values into an (n,) ``accum_dtype`` accumulator —
    O(K*k) work plus one dense output, never the (K, n) dense deltas.
    Duplicate indices accumulate, matching ``ref.densify_ref``."""
    if idx.ndim != 2 or idx.shape != values.shape:
        raise ValueError(
            f"idx and values must share a (K, k) shape; got idx "
            f"{idx.shape}, values {values.shape}"
        )
    if weights.shape != (idx.shape[0],):
        raise ValueError(
            f"weights must be ({idx.shape[0]},), got {weights.shape}"
        )
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    contrib = values.astype(accum_dtype) * weights.astype(accum_dtype)[:, None]
    return jnp.zeros((n,), accum_dtype).at[idx.reshape(-1)].add(
        contrib.reshape(-1)
    )


def sparse_fedavg_aggregate(idx, values, weights, n, *,
                            accum_dtype=jnp.float32):
    """Weighted-average K sparse top-k client payloads into a dense (n,)
    delta. The server never materializes dense per-client deltas: the
    aggregate is an XLA scatter-add of the weighted (idx, value) pairs
    (Mosaic has no scatter, so this lane has no Pallas kernel).

    ``weights`` are RAW example counts n_k, normalized here, mirroring
    ``tree_fedavg_aggregate``.
    """
    w = jnp.asarray(weights, jnp.float32)
    return _sparse_weighted_sum(idx, values, w / jnp.sum(w), n, accum_dtype)


def sharded_sparse_fedavg_aggregate(idx, values, weights, n, *, axis_name,
                                    accum_dtype=jnp.float32):
    """Partial-sum mode of :func:`sparse_fedavg_aggregate` for cohort
    sharding: each shard scatter-accumulates its local (m/D, k) payload
    slice with UNnormalized weights, then one ``psum`` finishes the
    weighted sum and the weight total before the single division. Ghost
    (cohort-padding) clients carry weight 0 and vanish from both sums."""
    w = jnp.asarray(weights, jnp.float32)
    partial = _sparse_weighted_sum(idx, values, w, n, accum_dtype)
    num = jax.lax.psum(partial, axis_name)
    den = jax.lax.psum(jnp.sum(w), axis_name)
    return num / den


def mamba_ssm_scan(dt, Bm, Cm, x, A, h0, *, chunk=0, interpret=False):
    """Selective scan with optional sequence chunking (keeps (T, block_d)
    tiles VMEM-sized for long sequences)."""
    if not chunk or dt.shape[1] <= chunk:
        return ssm_scan(dt, Bm, Cm, x, A, h0, interpret=interpret)
    T = dt.shape[1]
    n = T // chunk

    def body(h, sl):
        dt_c, b_c, c_c, x_c = sl
        y, h = ssm_scan(dt_c, b_c, c_c, x_c, A, h, interpret=interpret)
        return h, y

    resh = lambda a: a[:, : n * chunk].reshape(
        (a.shape[0], n, chunk) + a.shape[2:]
    ).swapaxes(0, 1)
    h, ys = jax.lax.scan(body, h0, (resh(dt), resh(Bm), resh(Cm), resh(x)))
    y = ys.swapaxes(0, 1).reshape(dt.shape[0], n * chunk, -1)
    if n * chunk < T:
        y_t, h = ssm_scan(
            dt[:, n * chunk :], Bm[:, n * chunk :], Cm[:, n * chunk :],
            x[:, n * chunk :], A, h, interpret=interpret,
        )
        y = jnp.concatenate([y, y_t], axis=1)
    return y, h


def ce_loss_mean(hidden, head, labels, *, interpret=False):
    """(B, S, d) -> scalar mean CE via the fused kernel."""
    B, S, d = hidden.shape
    losses = fused_cross_entropy(
        hidden.reshape(B * S, d), head, labels.reshape(B * S), interpret=interpret
    )
    return jnp.mean(losses)
