"""Pallas TPU kernel fusing dequantization into the FedAvg server
aggregation — the compressed-upload analogue of ``fedavg_agg``.

Under the quantize codec (``core.compression.quantize_codec``) each client
uploads its delta as integer codes plus per-chunk fp32 (lo, scale) range
metadata. The naive server decodes every client to a dense fp32 vector and
then averages — materializing K x N fp32 (4-32x the wire size) in HBM just
to immediately reduce it away. This kernel never does: each grid cell
streams a (K, block) tile of code WORDS into VMEM, unpacks, dequantizes and
weighted-accumulates in ``accum_dtype`` (fp32 by default), and writes only
the block's averaged slice. Peak server memory for the aggregation stays at
the compressed payload size + one dense output.

One kernel serves every width. Sub-byte and odd widths arrive bit-packed
(``utils.bitpack`` chunk framing, ``ppw = 32 // bits`` codes per uint32
word); uint8 and uint16 codes are viewed as uint32 words of 4 or 2 codes,
which is the same framing at ``bits = 8`` and ``16``.

Layout on the chip: a block holds ``bc`` chunks as rows (sublanes) and a
chunk's ``wpc`` words along the lanes, with each chunk's (lo, scale) as a
one-lane column that broadcasts along its row. Code j of every word (one
static shift + mask) accumulates in plane j; the planes go back into chunk
order through exact 0/1 matmuls once per block, after the client loop.

Layout contract (produced by ``quantize_codec``'s encode):

  codes:  (K, N_pad) uint8/uint16, N_pad a multiple of ``chunk``; code q in
          [0, levels] represents lo_c + q/levels * scale_c of its chunk c.
          (Packed: (K, C * wpc) uint32 words.)
  lo:     (K, C) fp32, C = N_pad // chunk — per-chunk offset.
  scale:  (K, C) fp32 — per-chunk range (hi - lo; 0 for constant chunks,
          which dequantize exactly to lo).
  weights:(K,) fp32, **pre-normalized to sum to 1** — same contract as
          ``fedavg_aggregate``, normalization happens in exactly one
          sanctioned place (``core.compression.decode_aggregate`` /
          ``core.fedavg.server_aggregate``). Asserted eagerly on concrete
          weights, documented for traced ones.

``interpret=True`` runs the kernel body in the Pallas interpreter — the
CPU test/CI fallback (Pallas does not lower on the CPU backend). On TPU
leave the default: ``block_chunks=None`` sizes the tiles from K
(:func:`chunk_block`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fedavg_agg import VMEM_TILE_BUDGET


def chunk_block(k: int, wpc: int, ppw: int, chunk: int, c: int) -> int:
    """Hardware ``block_chunks`` for a K-client cohort: the most chunk rows
    (a power of two from 8 to 128) whose tiles fit
    :data:`~repro.kernels.fedavg_agg.VMEM_TILE_BUDGET`, or all ``c`` chunks
    when they are fewer. A block's chunk rows sit on the sublane axis, so
    any multiple of 8 meets the TPU tiling rule. Per chunk row each client
    costs its double-buffered words and the two double-buffered one-lane
    lo/scale columns (padded to 128 lanes); the ``ppw`` accumulator planes,
    per-client temporaries and the double-buffered output row come once."""
    lanes = -(-wpc // 128) * 128
    per_row = (
        k * 2 * (lanes + 2 * 128) * 4 + (ppw + 3) * lanes * 4 + 3 * chunk * 4
    )
    bc = 8
    while bc * 2 <= 128 and bc * 2 * per_row <= VMEM_TILE_BUDGET:
        bc *= 2
    return c if c <= bc else bc


def _qagg_kernel(w_ref, words_ref, lo_ref, scale_ref, o_ref, *,
                 bits, levels, accum_dtype):
    # words_ref: (K, bc, wpc) uint32; lo/scale_ref: (K, bc, 1);
    # w_ref: (K, 1, 1); o_ref: (bc, chunk). Plane j holds code j of every
    # word (one static shift + mask), i.e. the chunk's codes t * ppw + j.
    # One client at a time is unpacked, dequantized and weighted into the
    # planes, so the working set does not grow with K.
    K, bc, wpc = words_ref.shape
    chunk = o_ref.shape[1]
    ppw = 32 // bits
    mask = jnp.uint32(2**bits - 1)

    def client(k, planes):
        words = words_ref[k]                                     # (bc, wpc)
        step = (scale_ref[k] / levels).astype(accum_dtype)       # (bc, 1)
        lo = lo_ref[k].astype(accum_dtype)                       # (bc, 1)
        w = w_ref[k].astype(accum_dtype)                         # (1, 1)
        out = []
        for j, acc in enumerate(planes):
            q = ((words >> jnp.uint32(j * bits)) & mask).astype(jnp.int32)
            out.append(acc + w * (q.astype(accum_dtype) * step + lo))
        return tuple(out)

    zero = jnp.zeros((bc, wpc), accum_dtype)
    planes = jax.lax.fori_loop(0, K, client, (zero,) * ppw)
    # Interleave the planes back into chunk order on the MXU: plane j times
    # the 0/1 matrix that sends word t to column t * ppw + j (columns past
    # ``chunk`` are the frame's slack codes and are never formed). Every
    # output column picks exactly one plane value, so splitting each value
    # into three bf16 pieces (8 mantissa bits each, summing exactly to the
    # fp32 value) makes three one-pass bf16 matmuls an exact shuffle.
    t = jax.lax.broadcasted_iota(jnp.int32, (wpc, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (wpc, chunk), 1)
    acc = jnp.zeros((bc, chunk), jnp.float32)
    for j, plane in enumerate(planes):
        sel = (col == t * ppw + j).astype(jnp.bfloat16)
        rest = plane.astype(jnp.float32)
        for _ in range(3):
            piece = rest.astype(jnp.bfloat16)
            rest = rest - piece.astype(jnp.float32)
            acc = acc + jax.lax.dot_general(
                piece, sel, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
    o_ref[...] = acc.astype(o_ref.dtype)


def _pad_chunks(x, pad_c):
    return jnp.pad(x, ((0, 0), (0, pad_c)) + ((0, 0),) * (x.ndim - 2))


@functools.partial(
    jax.jit,
    static_argnames=("bits", "chunk", "levels", "block_chunks", "interpret",
                     "accum_dtype"),
)
def _qagg_impl(words, lo, scale, weights, *, bits, chunk, levels,
               block_chunks, interpret, accum_dtype):
    ppw = 32 // bits
    wpc = -(-chunk // ppw)
    K, n_words = words.shape
    C = n_words // wpc
    bc = min(block_chunks, C)
    pad_c = (-C) % bc
    # Zero words decode to code 0; zero lo/scale dequantize that to
    # exactly 0, so padded chunks contribute nothing.
    words = _pad_chunks(words.reshape(K, C, wpc), pad_c)
    lo = _pad_chunks(lo[:, :, None], pad_c)
    scale = _pad_chunks(scale[:, :, None], pad_c)
    nb = (C + pad_c) // bc
    w3 = weights.reshape(K, 1, 1).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_qagg_kernel, bits=bits, levels=levels,
                          accum_dtype=accum_dtype),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((K, 1, 1), lambda i: (0, 0, 0)),
            pl.BlockSpec((K, bc, wpc), lambda i: (0, i, 0)),
            pl.BlockSpec((K, bc, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((K, bc, 1), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((bc, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * bc, chunk),
                                       jnp.dtype(accum_dtype)),
        interpret=interpret,
    )(w3, words, lo, scale)
    return out.reshape(-1)[: C * chunk]


def _check_ranges(name, k, c, lo, scale, weights):
    if lo.shape != (k, c) or scale.shape != (k, c):
        raise ValueError(
            f"lo/scale must be (K, C)={(k, c)}; got lo {lo.shape}, "
            f"scale {scale.shape}"
        )
    if not isinstance(weights, jax.core.Tracer):
        s = float(jnp.sum(jnp.asarray(weights, jnp.float32)))
        if abs(s - 1.0) > 1e-3:
            raise ValueError(
                f"{name} requires pre-normalized weights "
                f"(sum==1); got sum={s:.6f}. Normalize raw counts in "
                "core.compression.decode_aggregate, nowhere else."
            )


def _aggregate_words(words, lo, scale, weights, *, bits, chunk, levels,
                     block_chunks, interpret, accum_dtype):
    ppw = 32 // bits
    K, C = lo.shape
    if block_chunks is None:
        # Interpret mode: one block covering all C chunks (capped at 1M
        # emulated columns) — the emulated grid is an XLA while loop whose
        # per-step overhead dwarfs the block math at simulation sizes (same
        # policy as ``fedavg_agg.interpret_block_n``).
        block_chunks = (
            min(C, max(1, (1 << 20) // chunk)) if interpret
            else chunk_block(K, -(-chunk // ppw), ppw, chunk, C)
        )
    return _qagg_impl(
        words, lo, scale, weights,
        bits=bits, chunk=chunk, levels=levels, block_chunks=block_chunks,
        interpret=interpret, accum_dtype=jnp.dtype(accum_dtype),
    )


def quantized_aggregate(
    codes: jnp.ndarray,    # (K, N_pad) uint8/uint16 quantization codes
    lo: jnp.ndarray,       # (K, C) per-chunk offsets, C = N_pad // chunk
    scale: jnp.ndarray,    # (K, C) per-chunk ranges
    weights: jnp.ndarray,  # (K,) normalized (sum to 1)
    *,
    chunk: int,
    levels: int,
    block_chunks=None,
    interpret: bool = False,
    accum_dtype=jnp.float32,
) -> jnp.ndarray:
    """Fused dequantize + weighted mean over the client axis -> (N_pad,).

    Matches ``fedavg_aggregate(dequantize(codes, lo, scale), weights)`` to
    fp32 accumulation tolerance without ever materializing the (K, N_pad)
    dense fp32 client deltas. The codes are bitcast to uint32 words of
    ``4 // itemsize`` codes (little-endian, so code j of a word is its
    j-th byte or half-word) and go through the packed kernel; ``chunk``
    must be a multiple of that count.

    ``block_chunks=None`` picks the backend policy: :func:`chunk_block`'s
    VMEM-sized tile on hardware, one grid step under the interpreter.
    """
    if codes.ndim != 2 or codes.shape[1] % chunk:
        raise ValueError(
            f"codes must be (K, C*chunk); got {codes.shape} with chunk={chunk}"
        )
    size = jnp.dtype(codes.dtype).itemsize
    if size not in (1, 2) or chunk % (4 // size):
        raise ValueError(
            f"codes must be uint8 or uint16 with chunk a multiple of "
            f"{4 // max(size, 1)}; got {codes.dtype} with chunk={chunk}"
        )
    K = codes.shape[0]
    _check_ranges("quantized_aggregate", K, codes.shape[1] // chunk, lo,
                  scale, weights)
    words = jax.lax.bitcast_convert_type(
        codes.reshape(K, -1, 4 // size), jnp.uint32
    )
    return _aggregate_words(
        words, lo, scale, weights, bits=8 * size, chunk=chunk, levels=levels,
        block_chunks=block_chunks, interpret=interpret,
        accum_dtype=accum_dtype,
    )


def dequantize_ref(codes, lo, scale, *, chunk, levels):
    """Pure-jnp oracle: expand codes back to dense fp32 (K, N_pad).

    The reference the kernel is tested against (dequantize-then-
    ``fedavg_aggregate``); also documents the code -> value mapping."""
    K, n_pad = codes.shape
    C = n_pad // chunk
    q = codes.astype(jnp.float32).reshape(K, C, chunk)
    x = q * (scale / levels)[:, :, None] + lo[:, :, None]
    return x.reshape(K, n_pad)


def packed_quantized_aggregate(
    words: jnp.ndarray,    # (K, C*wpc) uint32 bit-packed codes (chunk frames)
    lo: jnp.ndarray,       # (K, C) per-chunk offsets
    scale: jnp.ndarray,    # (K, C) per-chunk ranges
    weights: jnp.ndarray,  # (K,) normalized (sum to 1)
    *,
    bits: int,
    chunk: int,
    levels: int,
    block_chunks=None,
    interpret: bool = False,
    accum_dtype=jnp.float32,
) -> jnp.ndarray:
    """Fused unpack + dequantize + weighted mean -> (C*chunk,).

    The bit-packed twin of :func:`quantized_aggregate`: the input is the
    bit-packed uint32 wire form itself (``utils.bitpack`` chunk framing,
    ``wpc = ceil(chunk / (32 // bits))`` words per chunk), unpacked in the
    kernel body — dense codes never exist outside VMEM. Any width 1..15
    works (the generic ``32 // bits`` codes-per-word unpack covers the odd
    9..15 widths the quantize codec now packs too); 16-bit codes ship as
    exact uint16 stores through :func:`quantized_aggregate` instead.
    Weights follow the same pre-normalized contract; block policy mirrors
    ``quantized_aggregate``.
    """
    if not 1 <= bits <= 15:
        raise ValueError(
            f"packed aggregation is for bits in 1..15, got {bits}"
        )
    wpc = -(-chunk // (32 // bits))
    if words.ndim != 2 or words.shape[1] % wpc:
        raise ValueError(
            f"words must be (K, C*{wpc}) for chunk={chunk}, bits={bits}; "
            f"got {words.shape}"
        )
    _check_ranges("packed_quantized_aggregate", words.shape[0],
                  words.shape[1] // wpc, lo, scale, weights)
    return _aggregate_words(
        words, lo, scale, weights, bits=bits, chunk=chunk, levels=levels,
        block_chunks=block_chunks, interpret=interpret,
        accum_dtype=accum_dtype,
    )


def unpack_ref(words, *, bits, chunk):
    """Pure-jnp oracle: (K, C*wpc) packed words -> (K, C*chunk) uint32 codes
    (``utils.bitpack.unpack_codes`` vmapped over the client axis)."""
    from repro.utils.bitpack import unpack_codes, words_per_chunk

    C = words.shape[1] // words_per_chunk(chunk, bits)
    return jax.vmap(
        lambda w: unpack_codes(w, bits, chunk, C).reshape(-1)
    )(words)
