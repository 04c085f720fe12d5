"""Client-update compression — the paper's explicit follow-up direction
(footnote 7: Konečný et al., "Federated Learning: Strategies for Improving
Communication Efficiency", NIPS-W 2016), as statically-shaped codec
transforms over the raveled client delta  Δ_k = w_k - w_t.

FedAvg reduces the NUMBER of rounds; these codecs reduce BYTES PER ROUND —
the two multiply. Every codec is a pair of pure, vmappable functions over
the (N,) delta VECTOR (``utils.tree.tree_ravel_stacked`` adapts model
pytrees), so the whole compressed round —

    vmap(ClientUpdate) -> vmap(encode) -> decode+aggregate -> apply

— traces into ONE jitted executable (``build_compressed_round_step``),
exactly like the plain :func:`repro.core.engine.build_simulation_round_step`
path. The legacy implementation looped over clients in Python with
per-leaf host loops inside each codec; it recompiled per cohort and
dispatched eagerly per client. It survives only as
:func:`build_compressed_round_step_loop`, the benchmark baseline
(``benchmarks/compression.py`` measures both).

Codec API (see docs/compression.md)::

    codec = quantize_codec(bits=8)        # or identity/mask/topk_codec
    payload = codec.encode(key, flat)     # flat: (N,) delta; static shapes
    delta_hat = codec.decode(payload, n)  # (n,) fp32
    codec.wire_bytes(n)                   # static expected upload bytes
    codec.payload_bytes(payload)          # realized bytes (host-side)

Aggregation: ``decode_aggregate(codec, payloads, weights, n)`` averages the
m stacked payloads. Codecs may fuse it — the quantize codec routes through
the Pallas ``quantized_aggregate`` kernel, which dequantizes uint8 codes
and accumulates the weighted mean in fp32 in one pass, so the server never
materializes the dense (m, N) fp32 deltas.

The payloads are the WIRE, not a simulation stand-in: sub-byte quantize
codes travel bit-packed in uint32 words (``utils.bitpack``) and byte-wide
stores are truncated to the true ``n``, so for every codec except ``mask``
(which keeps a dense masked vector as a simulation convenience, documented
there) the device-resident payload is byte-for-byte what ``wire_bytes``
claims — ``realized_device_bytes`` measures it, tests pin the equality.

Codecs:
- ``identity_codec()``       fp32 passthrough (the equivalence baseline).
- ``quantize_codec(bits)``   stochastic uniform quantization, per-``chunk``
                             fp32 (lo, scale): 4-16x fewer bytes, unbiased;
                             bits < 8 ships bit-packed uint32 words.
- ``mask_codec(keep_frac)``  random-mask subsampling with 1/p rescaling;
                             the mask regenerates from a shared seed, so
                             only kept values + 1 seed upload. Unbiased.
- ``topk_codec(keep_frac)``  magnitude top-k with int32 indices (biased but
                             norm-preserving; flagged ``unbiased=False``);
                             aggregates through the sparse scatter kernel.
- ``lowrank_codec(rank)``    the low-rank structured update of Konečný et
                             al. (arxiv 1610.02527): ship B = A^T M for a
                             seed-regrown Gaussian A; unbiased sketch whose
                             decode is a small matmul fused into
                             aggregation.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fedavg_agg import fedavg_aggregate
from repro.kernels.ops import (
    default_interpret,
    packed_quantized_fedavg_aggregate,
    quantized_fedavg_aggregate,
    sharded_packed_quantized_fedavg_aggregate,
    sharded_quantized_fedavg_aggregate,
    sharded_sparse_fedavg_aggregate,
    sparse_fedavg_aggregate,
)
from repro.utils.bitpack import pack_codes, packed_size, unpack_codes, words_per_chunk
from repro.utils.tree import tree_ravel, tree_ravel_stacked, tree_size, tree_unravel

# Charged once per upload by codecs whose SERVER-side decode must regrow
# client randomness from a shared seed (the mask codec: kept values + seed
# travel, indices are reconstructed; the low-rank codec: B + the seed that
# regrows A). Codecs whose randomness stays client-local (quantize's
# stochastic rounding) have nothing to ship.
SEED_BYTES = 8


class Codec(NamedTuple):
    """A statically-shaped update codec over raveled (N,) delta vectors.

    ``encode(key, flat)`` returns a payload dict of fixed-shape arrays (so
    it vmaps over clients and traces into the round executable);
    ``decode(payload, n)`` rebuilds the (n,) fp32 delta estimate — ``n`` is
    the STATIC true size, since padded codecs store a multiple of their
    chunk. ``wire_bytes(n)`` is the static expected upload size from shape
    metadata alone; ``payload_bytes(payload)`` is the realized size of one
    concrete payload (host-side — for the mask codec these differ, see its
    docstring). ``aggregate`` optionally fuses decode into the weighted
    server mean (payloads stacked with a leading client axis, RAW count
    weights; an ``axis_name`` kwarg selects the cohort-sharded partial-sum
    mode — see ``decode_aggregate``, the sanctioned entry point).
    """

    name: str
    encode: Callable
    decode: Callable
    wire_bytes: Callable
    payload_bytes: Callable
    unbiased: bool
    aggregate: Optional[Callable] = None


def identity_codec() -> Codec:
    """fp32 passthrough: compressed pipeline == plain pipeline, bit-for-bit
    modulo fp32 accumulation order. The equivalence-test baseline."""

    def encode(key, flat):
        return {"values": flat.astype(jnp.float32)}

    def decode(payload, n):
        return payload["values"][:n]

    return Codec(
        name="identity",
        encode=encode,
        decode=decode,
        wire_bytes=lambda n: 4 * n,
        payload_bytes=lambda p: int(np.asarray(p["values"]).size) * 4,
        unbiased=True,
    )


def quantize_codec(bits: int = 8, chunk: int = 512) -> Codec:
    """Stochastic uniform quantization to 2^bits levels.

    The flat vector is zero-padded to a multiple of ``chunk`` and split
    into (C, chunk) rows; each row quantizes against its own fp32
    (lo, scale) range, so one outlier coordinate only costs its own chunk's
    resolution (the per-leaf ranges of the legacy codec, made static).
    Stochastic rounding keeps E[decode(encode(x))] = x per coordinate;
    constant chunks (hi == lo, scale 0) decode EXACTLY to lo.

    The payload IS the wire: every width that does not fill a whole number
    of bytes (bits % 8 != 0 — sub-byte AND the odd 9..15 widths) ships
    bit-packed uint32 words (``utils.bitpack`` chunk framing — codes never
    straddle a word, widths that do not divide 32 pay their slack bits
    honestly), while bits == 8/16 ship exact uint8/uint16 stores truncated
    to the true ``n`` codes. Either way the device-resident byte count
    equals ``wire_bytes(n)`` for EVERY width 1..16 — the honesty contract
    the ``roofline_wire`` gate enforces. (The odd 9..15 widths used to
    price ideal packing while shipping a uint16 store, silently
    under-reporting their upload bytes.)

    Aggregation fuses into the Pallas ``quantized_aggregate`` kernel (or
    its ``packed_quantized_aggregate`` twin, which unpacks the packed words
    inside the kernel body): the server reads the wire codes directly and
    never expands per-client fp32.
    """
    if bits < 1 or bits > 16:
        raise ValueError(f"quantize_codec supports 1..16 bits, got {bits}")
    levels = 2**bits - 1
    packed = bits % 8 != 0
    store_dtype = jnp.uint8 if bits <= 8 else jnp.uint16
    wpc = words_per_chunk(chunk, bits) if packed else None

    def encode(key, flat):
        n = flat.shape[0]
        pad = (-n) % chunk
        # Edge-pad, not zero-pad: a padded 0 would join the tail chunk's
        # min/max and widen its range (coarser codes for the REAL tail
        # coordinates); repeating the last real value leaves it untouched.
        v = jnp.pad(flat.astype(jnp.float32), (0, pad), mode="edge").reshape(
            -1, chunk
        )
        lo = jnp.min(v, axis=1)
        scale = jnp.max(v, axis=1) - lo
        safe = jnp.maximum(scale, 1e-12)
        x = (v - lo[:, None]) / safe[:, None] * levels
        # floor(x + U[0,1)) realizes stochastic rounding: E[q] = x.
        q = jnp.clip(jnp.floor(x + jax.random.uniform(key, v.shape)),
                     0, levels)
        if packed:
            # The exact wire words: full chunks at wpc words each, the tail
            # chunk truncated to its own ceil(tail/ppw) words (decode and
            # the kernel re-pad to the chunk-aligned frame).
            wire = pack_codes(q.astype(jnp.uint32), bits, chunk)
            wire = wire[: packed_size(n, chunk, bits)]
        else:
            # Truncate the chunk-padded store to the true n codes; pad
            # codes are repeats of the tail value and carry no information.
            wire = q.astype(store_dtype).reshape(-1)[:n]
        return {
            "q": wire,
            "lo": lo,
            "scale": scale,
            # true (unpadded) size — sim-side metadata, not wire payload
            "n": jnp.int32(n),
        }

    def decode(payload, n):
        n_chunks = -(-n // chunk)
        if packed:
            words = jnp.pad(
                payload["q"], (0, n_chunks * wpc - payload["q"].shape[0])
            )
            q = unpack_codes(words, bits, chunk, n_chunks).astype(jnp.float32)
        else:
            q = jnp.pad(payload["q"], (0, n_chunks * chunk - n))
            q = q.reshape(n_chunks, chunk).astype(jnp.float32)
        x = q * (payload["scale"] / levels)[:, None] + payload["lo"][:, None]
        return x.reshape(-1)[:n]

    def aggregate(payloads, weights, n, *, interpret, accum_dtype,
                  axis_name=None):
        q = payloads["q"]                     # (m, wire) exact wire arrays
        n_chunks = -(-n // chunk)
        kw = dict(chunk=chunk, levels=levels, interpret=interpret,
                  accum_dtype=accum_dtype)
        if packed:
            # Re-pad the truncated tail frame with zero words (code 0; the
            # output is sliced to n below, so tail pad codes are inert).
            words = jnp.pad(q, ((0, 0), (0, n_chunks * wpc - q.shape[1])))
            if axis_name is not None:
                # Cohort-sharded: local partial sum over this shard's
                # clients with raw weights, psum-finished across the axis.
                out = sharded_packed_quantized_fedavg_aggregate(
                    words, payloads["lo"], payloads["scale"], weights,
                    bits=bits, axis_name=axis_name, **kw,
                )
            else:
                out = packed_quantized_fedavg_aggregate(
                    words, payloads["lo"], payloads["scale"], weights,
                    bits=bits, **kw,
                )
            return out[:n]
        codes = jnp.pad(q, ((0, 0), (0, n_chunks * chunk - q.shape[1])))
        if axis_name is not None:
            out = sharded_quantized_fedavg_aggregate(
                codes, payloads["lo"], payloads["scale"], weights,
                axis_name=axis_name, **kw,
            )
        else:
            out = quantized_fedavg_aggregate(
                codes, payloads["lo"], payloads["scale"], weights, **kw,
            )
        return out[:n]

    def wire_bytes(n: int) -> int:
        # Codes at their true (word-framed) width plus 8 bytes of
        # (lo, scale) per chunk. The stochastic-rounding key is
        # client-local — decode needs only codes + ranges, so no seed
        # ships. This is now also the PHYSICAL payload size (see encode).
        n_chunks = -(-n // chunk)
        if packed:
            return 4 * packed_size(n, chunk, bits) + 8 * n_chunks
        # bits == 8/16: the truncated uint8/uint16 store IS the wire.
        return -(-n * bits // 8) + 8 * n_chunks

    def payload_bytes(payload) -> int:
        return wire_bytes(int(np.asarray(payload["n"])))

    return Codec(
        name=f"q{bits}",
        encode=encode,
        decode=decode,
        wire_bytes=wire_bytes,
        payload_bytes=payload_bytes,
        unbiased=True,
        aggregate=aggregate,
    )


def mask_codec(keep_frac: float = 0.1) -> Codec:
    """Random-mask subsampling: keep each coordinate w.p. p, rescale kept
    values by 1/p (unbiased). The mask is a pure function of the shared
    seed, so the wire carries only the kept VALUES plus that seed; the
    payload keeps the dense masked vector (simulation convenience) plus the
    realized kept-coordinate count.

    Byte accounting is the REALIZED count: a Bernoulli(p) mask over n
    coordinates keeps Binomial(n, p) of them, not exactly p*n — the legacy
    ``bytes_fn`` reported the expectation and could misstate a concrete
    upload by O(sqrt(n)) values. ``payload_bytes`` now charges
    4 * kept + SEED_BYTES from the payload's own mask draw;
    ``wire_bytes`` remains the static expectation.
    """
    if not 0.0 < keep_frac <= 1.0:
        raise ValueError(f"keep_frac must be in (0, 1], got {keep_frac}")

    def encode(key, flat):
        m = jax.random.bernoulli(key, keep_frac, flat.shape)
        vals = jnp.where(m, flat.astype(jnp.float32) / keep_frac, 0.0)
        return {"values": vals, "kept": jnp.sum(m).astype(jnp.int32)}

    def decode(payload, n):
        return payload["values"][:n]

    return Codec(
        name=f"mask{keep_frac:g}",
        encode=encode,
        decode=decode,
        wire_bytes=lambda n: 4 * int(round(keep_frac * n)) + SEED_BYTES,
        payload_bytes=lambda p: 4 * int(np.asarray(p["kept"])) + SEED_BYTES,
        unbiased=True,
    )


def topk_codec(keep_frac: float = 0.05) -> Codec:
    """Magnitude top-k (+int32 indices on the wire). Biased — the standard
    norm-preserving heuristic; k = max(floor(p * n), 1) is static.

    Aggregation is one XLA scatter-add (``ops.sparse_fedavg_aggregate``):
    the server accumulates the weighted (idx, values) pairs straight into
    the fp32 accumulator — the dense (m, N) per-client deltas of the
    generic vmap-decode path are never materialized."""
    if not 0.0 < keep_frac <= 1.0:
        raise ValueError(f"keep_frac must be in (0, 1], got {keep_frac}")

    # floor(keep_frac * n) in INTEGER arithmetic: the float product can
    # land one ulp below the true value (100 * 0.29 -> 28.999...999, whose
    # int() is 28, not the documented floor(p*n) = 29). Scaling keep_frac
    # to an exact parts-per-billion numerator first makes the floor exact
    # for every keep_frac a caller can plausibly write.
    _frac_ppb = round(keep_frac * 10**9)

    def k_of(n: int) -> int:
        return max(n * _frac_ppb // 10**9, 1)

    def encode(key, flat):
        k = k_of(flat.shape[0])
        _, idx = jax.lax.top_k(jnp.abs(flat.astype(jnp.float32)), k)
        return {
            "idx": idx.astype(jnp.int32),
            "values": jnp.take(flat, idx).astype(jnp.float32),
        }

    def decode(payload, n):
        out = jnp.zeros((n,), jnp.float32)
        return out.at[payload["idx"]].set(payload["values"])

    def aggregate(payloads, weights, n, *, interpret, accum_dtype,
                  axis_name=None):
        if axis_name is not None:
            return sharded_sparse_fedavg_aggregate(
                payloads["idx"], payloads["values"], weights, n,
                axis_name=axis_name, accum_dtype=accum_dtype,
            )
        return sparse_fedavg_aggregate(
            payloads["idx"], payloads["values"], weights, n,
            accum_dtype=accum_dtype,
        )

    return Codec(
        name=f"top{keep_frac:g}",
        encode=encode,
        decode=decode,
        wire_bytes=lambda n: 8 * k_of(n),
        payload_bytes=lambda p: 8 * int(np.asarray(p["idx"]).size),
        unbiased=False,
        aggregate=aggregate,
    )


def lowrank_codec(rank: int = 8) -> Codec:
    """Low-rank structured update (Konečný et al., arxiv 1610.02527).

    The raveled delta is viewed as an (d1, d2) matrix M (d1 = ceil(sqrt(n)),
    zero-padded), each client draws a Gaussian sketch A ~ N(0,1) of shape
    (d1, rank) from its codec key, and the wire carries B = A^T M —
    ``4 * rank * d2`` bytes plus the seed that regrows A server-side
    (compression when rank << d1). Decode is Â = A B / rank: since
    E[A A^T] = rank * I, the estimate is unbiased, the random-projection
    analogue of the paper's low-rank updates (those optimize B given a
    fixed A; the sketch form keeps encode a single matmul and stays
    unbiased).

    Aggregation never materializes per-client dense deltas: the weighted
    mean  Σ_k w_k A_k B_k / rank  is ONE batched ``dot_general``
    contracting the (client, rank) axes — a small matmul fused into the
    server reduce, with the same psum-finished partial-sum mode as the
    Pallas kernels for the cohort-sharded lane."""
    if rank < 1:
        raise ValueError(f"rank must be >= 1, got {rank}")

    def dims(n: int):
        d1 = math.isqrt(n)
        if d1 * d1 < n:
            d1 += 1
        d2 = -(-n // max(d1, 1))
        return max(d1, 1), d2

    def regrow(key, d1):
        return jax.random.normal(key, (d1, rank), jnp.float32)

    def encode(key, flat):
        n = flat.shape[0]
        d1, d2 = dims(n)
        m = jnp.pad(flat.astype(jnp.float32), (0, d1 * d2 - n))
        a = regrow(key, d1)
        return {
            "b": jnp.dot(a.T, m.reshape(d1, d2),
                         preferred_element_type=jnp.float32),
            "key": key,
        }

    def decode(payload, n):
        d1, d2 = dims(n)
        a = regrow(payload["key"], d1)
        m = jnp.dot(a, payload["b"], preferred_element_type=jnp.float32)
        return m.reshape(-1)[:n] / rank

    def aggregate(payloads, weights, n, *, interpret, accum_dtype,
                  axis_name=None):
        d1, d2 = dims(n)
        a = jax.vmap(lambda k: regrow(k, d1))(payloads["key"])  # (m, d1, r)
        b = payloads["b"]                                       # (m, r, d2)
        w = jnp.asarray(weights, jnp.float32)
        if axis_name is None:
            w = w / jnp.sum(w)
        # Σ_k w_k A_k B_k in one contraction over (client, rank).
        m = jax.lax.dot_general(
            a * w[:, None, None], b, (((0, 2), (0, 1)), ((), ())),
            preferred_element_type=jnp.dtype(accum_dtype),
        )
        out = m.reshape(-1)[:n] / rank
        if axis_name is not None:
            num = jax.lax.psum(out, axis_name)
            den = jax.lax.psum(jnp.sum(w), axis_name)
            return num / den
        return out

    def wire_bytes(n: int) -> int:
        return 4 * rank * dims(n)[1] + SEED_BYTES

    return Codec(
        name=f"lowrank{rank}",
        encode=encode,
        decode=decode,
        wire_bytes=wire_bytes,
        payload_bytes=lambda p: 4 * int(np.asarray(p["b"]).size) + SEED_BYTES,
        unbiased=True,
        aggregate=aggregate,
    )


# ---------------------------------------------------------------------------
# server side: decode + aggregate
# ---------------------------------------------------------------------------

def decode_aggregate(codec: Codec, payloads, weights, n: int, *,
                     interpret: Optional[bool] = None,
                     accum_dtype=jnp.float32, axis_name=None):
    """Weighted-average m stacked payloads into one (n,) fp32 delta.

    ``payloads``: the pytree returned by ``vmap(codec.encode)`` (every leaf
    carries a leading client axis); ``weights``: (m,) RAW example counts
    n_k — like ``server_aggregate``, this is the one sanctioned entry point
    that normalizes them. Fused codecs (quantize) go straight to their
    Pallas kernel; the generic path vmaps ``decode`` and reduces through
    ``fedavg_aggregate``.

    ``axis_name``: cohort-sharded mode (inside a ``shard_map`` over the
    client axis). Each shard decodes and partially aggregates only its
    local payload slice with UNnormalized weights; a ``psum`` finishes the
    weighted sum and the weight total before the single division, so every
    shard returns the same global delta (see docs/compression.md).
    """
    interpret = default_interpret() if interpret is None else interpret
    if codec.aggregate is not None:
        return codec.aggregate(payloads, weights, n, interpret=interpret,
                               accum_dtype=accum_dtype, axis_name=axis_name)
    flat = jax.vmap(lambda p: codec.decode(p, n))(payloads)      # (m, n)
    w = jnp.asarray(weights, jnp.float32)
    if axis_name is not None:
        partial = fedavg_aggregate(flat, w, interpret=interpret,
                                   accum_dtype=accum_dtype)
        num = jax.lax.psum(partial, axis_name)
        den = jax.lax.psum(jnp.sum(w), axis_name)
        return num / den
    w = w / jnp.sum(w)
    return fedavg_aggregate(flat, w, interpret=interpret,
                            accum_dtype=accum_dtype)


# ---------------------------------------------------------------------------
# the compressed round, compiled
# ---------------------------------------------------------------------------

def build_compressed_round_step(loss_fn, codec: Codec, *,
                                interpret: Optional[bool] = None,
                                accum_dtype=jnp.float32, axis_name=None,
                                strategy=None):
    """Compressed FedAvg as a unified ``round_step`` (``core.engine``
    protocol), tracing to ONE executable: vmapped ClientUpdate, vmapped
    ``codec.encode`` over the raveled deltas, fused decode+aggregate, apply.

    ``batch.key`` seeds the per-client codecs — each client's key is
    ``fold_in(key, global_slot)`` where ``global_slot`` is the client's
    position in the FULL round cohort. Keying by global slot (not local
    index) makes the codec stream invariant to cohort sharding: under
    ``axis_name`` a shard holding slots [s, s + m/D) derives exactly the
    keys the unsharded run would, so sharded and unsharded runs encode
    identical payloads. ``batch.client_weights`` are raw counts (normalized
    exactly once, in :func:`decode_aggregate`, which in sharded mode
    finishes with a psum over ``axis_name``). Losses are reduced with the
    same masked, count-weighted formula as ``build_simulation_round_step``,
    so an identity codec reproduces the plain pipeline to fp32 tolerance.

    Supersteps compose from OUTSIDE: ``RoundEngine``'s ``lax.scan``-fused
    multi-round executable calls this round step once per scan iteration
    with a fresh ``batch.key`` split from the scan carry, so nothing here
    is loop-aware — the codec stream stays per-round keyed (and
    superstep(R) == R per-round calls, see tests/test_engine_superstep.py).

    ``strategy`` (``core.strategies.ServerStrategy``) consumes the decoded
    weighted-mean delta; the default ``FedAvg()`` IS the historical
    ``params + avg_delta`` apply, bit for bit, so pre-strategy callers see
    no change. Stateful strategies thread ``RoundState.outer_state``.
    """
    from repro.core.fedavg import client_update, masked_weighted_loss
    from repro.core.strategies import resolve_strategy

    strategy = resolve_strategy(strategy)
    interpret = default_interpret() if interpret is None else interpret

    def round_step(state, rb):
        params = state.params
        with jax.named_scope("fedavg.client_update"):
            upd = jax.vmap(
                lambda b, msk: client_update(loss_fn, params, b, msk, rb.lr)
            )
            client_params, losses = upd(rb.data, rb.step_mask)
        with jax.named_scope("fedavg.encode"):
            deltas = jax.tree.map(
                lambda c, p: (c - p).astype(jnp.float32), client_params,
                params,
            )
            flat, spec = tree_ravel_stacked(deltas)              # (m, N)
            m = flat.shape[0]
            slot0 = (0 if axis_name is None
                     else jax.lax.axis_index(axis_name) * m)
            keys = jax.vmap(lambda s: jax.random.fold_in(rb.key, s))(
                slot0 + jnp.arange(m, dtype=jnp.int32)
            )
            payloads = jax.vmap(codec.encode)(keys, flat)
        with jax.named_scope("fedavg.aggregate"):
            avg_flat = decode_aggregate(
                codec, payloads, rb.client_weights, spec.total_size,
                interpret=interpret, accum_dtype=accum_dtype,
                axis_name=axis_name,
            )
            avg_delta = tree_unravel(spec, avg_flat)
        with jax.named_scope("fedavg.apply"):
            outer, new_params = strategy.apply(
                state.outer_state, params, avg_delta
            )
        with jax.named_scope("fedavg.client_update"):
            loss = masked_weighted_loss(losses, rb.step_mask,
                                        rb.client_weights,
                                        axis_name=axis_name)
        return state._replace(params=new_params, outer_state=outer), {
            "loss": loss
        }

    return round_step


def build_compressed_round_step_loop(loss_fn, codec: Codec):
    """LEGACY per-client Python loop — the pre-compiled-pipeline shape
    (eager dispatch per client, host-side stacking, no fused aggregate).
    Kept ONLY as the baseline for ``benchmarks/compression.py``, like
    ``simulation.build_round_batch_host``; new code uses
    :func:`build_compressed_round_step`.
    """
    from repro.core.fedavg import client_update, masked_weighted_loss
    from repro.utils.tree import tree_weighted_mean

    def round_step(state, rb):
        params = state.params
        m = jax.tree.leaves(rb.data)[0].shape[0]
        decoded, losses = [], []
        for i in range(m):
            b = jax.tree.map(lambda a: a[i], rb.data)
            w_k, l = client_update(loss_fn, params, b, rb.step_mask[i], rb.lr)
            delta = jax.tree.map(
                lambda a, p: (a - p).astype(jnp.float32), w_k, params
            )
            flat, spec = tree_ravel(delta)
            payload = codec.encode(jax.random.fold_in(rb.key, i), flat)
            decoded.append(codec.decode(payload, spec.total_size))
            losses.append(l)
        stacked = jnp.stack(decoded)
        avg_flat = jnp.asarray(tree_weighted_mean(stacked, rb.client_weights))
        avg_delta = tree_unravel(spec, avg_flat)
        new_params = jax.tree.map(
            lambda p, d: (p + d).astype(p.dtype), params, avg_delta
        )
        loss = masked_weighted_loss(
            jnp.stack(losses), rb.step_mask, rb.client_weights
        )
        return state._replace(params=new_params), {"loss": loss}

    return round_step


def compressed_round(loss_fn, params, batches, step_mask, weights, lr, codec,
                     key):
    """One FedAvg round where each client uploads codec(Δ_k) instead of w_k.

    Equivalent to ``fedavg_round`` when codec is the identity; with an
    unbiased codec, E[new_params] equals the uncompressed round's result.
    Thin positional-arg shim over :func:`build_compressed_round_step`."""
    from repro.core.engine import RoundBatch, RoundState

    step = build_compressed_round_step(loss_fn, codec)
    state, metrics = step(
        RoundState(params), RoundBatch(batches, step_mask, weights, lr=lr, key=key)
    )
    return state.params, metrics["loss"]


# ---------------------------------------------------------------------------
# wire accounting
# ---------------------------------------------------------------------------

def wire_bytes(codec: Codec, params) -> int:
    """Expected upload bytes for ONE client's update of this model under
    this codec — pure static shape metadata (no encode, no device work),
    so benchmark sweeps can price a codec grid for free. The dense fp32
    baseline is ``4 * tree_size(params)``."""
    return int(codec.wire_bytes(tree_size(params)))


def upload_bytes_per_round(codec: Codec, params) -> int:
    """Back-compat alias of :func:`wire_bytes` (pre-PR-2 name)."""
    return wire_bytes(codec, params)


def realized_device_bytes(payload) -> int:
    """PHYSICAL nbytes of one payload's wire arrays, measured on the
    device buffers themselves — the ground truth that :func:`wire_bytes`
    claims to predict (tests and the roofline gate pin the equality for
    every codec except ``mask``, whose dense masked store is a documented
    simulation convenience).

    Sim-side metadata leaves are excluded: ``n`` (static true size) and
    ``kept`` (realized mask count) never travel; a ``key`` leaf stands for
    the shipped seed and is charged at ``SEED_BYTES``."""
    total = 0
    for name, leaf in payload.items():
        if name in ("n", "kept"):
            continue
        if name == "key":
            total += SEED_BYTES
            continue
        total += int(np.asarray(leaf).nbytes)
    return total
