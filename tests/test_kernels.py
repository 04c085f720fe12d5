"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(deliverable c). Small shapes — interpret mode executes the kernel body in
Python per grid cell."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.ref import densify_ref
from repro.kernels.ce_loss import fused_cross_entropy
from repro.kernels.fedavg_agg import fedavg_aggregate
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quantized_agg import (
    dequantize_ref,
    packed_quantized_aggregate,
    quantized_aggregate,
    unpack_ref,
)
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels import ops
from repro.utils.bitpack import pack_codes, words_per_chunk


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,D,bq,bk,causal,window", [
    (16, 8, 8, 8, True, 0),
    (37, 16, 8, 8, True, 0),
    (24, 8, 8, 16, False, 0),
    (33, 8, 16, 8, True, 9),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(rng, S, D, bq, bk, causal, window, dtype):
    q = jnp.asarray(rng.normal(size=(2, S, D)).astype(np.float32)).astype(dtype)
    k = jnp.asarray(rng.normal(size=(2, S, D)).astype(np.float32)).astype(dtype)
    v = jnp.asarray(rng.normal(size=(2, S, D)).astype(np.float32)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                                   v.astype(jnp.float32), causal=causal, window=window)
    atol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), want, atol=atol
    )


def test_mha_flash_gqa_wrapper(rng):
    B, S, H, K, D = 1, 16, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, K, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, K, D)).astype(np.float32))
    out = ops.mha_flash(q, k, v, block_q=8, block_k=8, interpret=True)
    from repro.models.attention_core import naive_attention
    want = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, atol=1e-5)


# ---------------------------------------------------------------------------
# fedavg aggregation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K,N,block", [(2, 64, 16), (5, 1000, 128), (8, 33, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_aggregate_sweep(rng, K, N, block, dtype):
    st_ = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32)).astype(dtype)
    w = jnp.asarray(rng.uniform(0.1, 5, K).astype(np.float32))
    w = w / w.sum()
    out = fedavg_aggregate(st_, w, block_n=block, interpret=True)
    want = ref.fedavg_aggregate_ref(st_, w)
    atol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out.astype(np.float32), want.astype(np.float32), atol=atol)


@settings(max_examples=15, deadline=None)
@given(k=st.integers(2, 6), n=st.integers(4, 200), seed=st.integers(0, 2**31 - 1))
def test_fedavg_aggregate_hypothesis(k, n, seed):
    r = np.random.default_rng(seed)
    st_ = jnp.asarray(r.normal(size=(k, n)).astype(np.float32))
    w = jnp.asarray(r.uniform(0.1, 5, k).astype(np.float32))
    w = w / w.sum()
    out = fedavg_aggregate(st_, w, block_n=32, interpret=True)
    np.testing.assert_allclose(out, ref.fedavg_aggregate_ref(st_, w), atol=1e-5)


def test_fedavg_aggregate_hardware_block_policy_k512(rng):
    """The chip's block policy at a 512-client cohort — several ragged
    column blocks of hardware_block_n(512) — equals the oracle."""
    from repro.kernels.fedavg_agg import hardware_block_n

    K, bn = 512, hardware_block_n(512)
    N = 2 * bn + 37
    st_ = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.1, 5, K).astype(np.float32))
    w = w / w.sum()
    out = fedavg_aggregate(st_, w, block_n=bn, interpret=True)
    np.testing.assert_allclose(out, ref.fedavg_aggregate_ref(st_, w),
                               atol=1e-5)


def test_tree_fedavg_aggregate_matches_server_line(rng):
    """Kernel path == Algorithm 1 server line on a real param pytree."""
    from repro.models import mnist_2nn
    from repro.utils.tree import tree_weighted_mean

    model = mnist_2nn(n_classes=3, d_in=6)
    stacked = jax.vmap(lambda s: model.init(jax.random.PRNGKey(s)))(jnp.arange(3))
    w = jnp.asarray([1.0, 2.0, 3.0])
    a = ops.tree_fedavg_aggregate(stacked, w, interpret=True)
    b = tree_weighted_mean(stacked, w)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(x, y, atol=1e-5)


# ---------------------------------------------------------------------------
# quantized aggregation (fused dequantize + weighted mean)
# ---------------------------------------------------------------------------

def _quantized_payload(rng, K, N, chunk, code_dtype=np.uint8, levels=255):
    n_pad = -(-N // chunk) * chunk
    codes = rng.integers(0, levels + 1, (K, n_pad)).astype(code_dtype)
    lo = rng.normal(size=(K, n_pad // chunk)).astype(np.float32)
    scale = rng.uniform(0.0, 2.0, (K, n_pad // chunk)).astype(np.float32)
    scale[rng.uniform(size=scale.shape) < 0.2] = 0.0  # constant chunks
    return jnp.asarray(codes), jnp.asarray(lo), jnp.asarray(scale)


@pytest.mark.parametrize("K", [1, 2, 17])
@pytest.mark.parametrize("N,chunk,bc", [(33, 16, 4), (1000, 64, 3)])  # ragged
def test_quantized_aggregate_matches_dequantize_oracle(rng, K, N, chunk, bc):
    """Acceptance: the fused kernel == dequantize-then-fedavg_aggregate for
    K in {1, 2, 17}, uint8 payloads, ragged N (incl. scale==0 chunks)."""
    codes, lo, scale = _quantized_payload(rng, K, N, chunk)
    w = jnp.asarray(rng.uniform(0.1, 5.0, K).astype(np.float32))
    w = w / w.sum()
    out = quantized_aggregate(codes, lo, scale, w, chunk=chunk, levels=255,
                              block_chunks=bc, interpret=True)
    dense = dequantize_ref(codes, lo, scale, chunk=chunk, levels=255)
    want = fedavg_aggregate(dense, w, interpret=True)
    n_pad = codes.shape[1]
    assert out.shape == (n_pad,) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_aggregate_hardware_block_policy(rng, bits):
    """The chip's chunk_block policy at K=100 (16-chunk blocks, ragged
    last block) equals dequantize -> fedavg_aggregate."""
    from repro.kernels.quantized_agg import chunk_block

    K, chunk = 100, 64
    ppw = 32 // bits
    C = 2 * chunk_block(K, chunk // ppw, ppw, chunk, 10**6) + 3
    w = jnp.asarray(rng.uniform(0.1, 5.0, K).astype(np.float32))
    w = w / w.sum()
    bc = chunk_block(K, chunk // ppw, ppw, chunk, C)
    if bits == 8:
        codes, lo, scale = _quantized_payload(rng, K, C * chunk, chunk)
        out = quantized_aggregate(codes, lo, scale, w, chunk=chunk,
                                  levels=255, block_chunks=bc,
                                  interpret=True)
    else:
        words, lo, scale, codes = _packed_payload(rng, K, C * chunk, chunk,
                                                  bits)
        out = packed_quantized_aggregate(words, lo, scale, w, bits=bits,
                                         chunk=chunk, levels=2**bits - 1,
                                         block_chunks=bc, interpret=True)
    dense = dequantize_ref(codes, lo, scale, chunk=chunk,
                           levels=2**bits - 1)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.fedavg_aggregate_ref(dense, w)),
                               atol=1e-5)


def test_quantized_aggregate_uint16_levels(rng):
    codes, lo, scale = _quantized_payload(rng, 3, 100, 32,
                                          code_dtype=np.uint16, levels=65535)
    w = jnp.full((3,), 1 / 3, jnp.float32)
    out = quantized_aggregate(codes, lo, scale, w, chunk=32, levels=65535,
                              block_chunks=2, interpret=True)
    dense = dequantize_ref(codes, lo, scale, chunk=32, levels=65535)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(fedavg_aggregate(dense, w, interpret=True)),
        atol=1e-4)


def test_quantized_aggregate_rejects_bad_inputs(rng):
    codes, lo, scale = _quantized_payload(rng, 2, 64, 16)
    with pytest.raises(ValueError, match="pre-normalized"):
        quantized_aggregate(codes, lo, scale, jnp.asarray([1.0, 2.0]),
                            chunk=16, levels=255, interpret=True)
    with pytest.raises(ValueError, match="C\\*chunk"):
        quantized_aggregate(codes[:, :30], lo, scale,
                            jnp.asarray([0.5, 0.5]), chunk=16, levels=255,
                            interpret=True)


# ---------------------------------------------------------------------------
# packed sub-byte aggregation (in-kernel bit unpack)
# ---------------------------------------------------------------------------

def _packed_payload(rng, K, N, chunk, bits):
    """Random packed wire words + ranges; returns (words, lo, scale, codes)
    with ``codes`` the dense (K, C*chunk) ground truth."""
    n_pad = -(-N // chunk) * chunk
    levels = 2**bits - 1
    codes = rng.integers(0, levels + 1, (K, n_pad)).astype(np.uint32)
    words = jax.vmap(
        lambda c: pack_codes(c.reshape(-1, chunk), bits, chunk)
    )(jnp.asarray(codes))
    C = n_pad // chunk
    lo = rng.normal(size=(K, C)).astype(np.float32)
    scale = rng.uniform(0.0, 2.0, (K, C)).astype(np.float32)
    scale[rng.uniform(size=scale.shape) < 0.2] = 0.0  # constant chunks
    return words, jnp.asarray(lo), jnp.asarray(scale), jnp.asarray(codes)


@pytest.mark.parametrize("K", [1, 2, 17])
@pytest.mark.parametrize("N,chunk,bc,bits", [
    (33, 16, 4, 4),    # ragged N, 8 codes/word
    (1000, 64, 3, 2),  # ragged N, 16 codes/word
    (250, 30, 2, 3),   # width AND chunk that don't divide the word
    (100, 16, 2, 12),  # odd WIDE width (9..15 lane), 2 codes/word
])
def test_packed_quantized_aggregate_matches_oracle(rng, K, N, chunk, bc, bits):
    """Acceptance: the fused unpack+dequantize+accumulate kernel ==
    unpack_ref -> dequantize_ref -> fedavg_aggregate, for K in {1, 2, 17},
    ragged N, slack-bit widths, scale==0 chunks."""
    levels = 2**bits - 1
    words, lo, scale, codes = _packed_payload(rng, K, N, chunk, bits)
    w = jnp.asarray(rng.uniform(0.1, 5.0, K).astype(np.float32))
    w = w / w.sum()
    out = packed_quantized_aggregate(words, lo, scale, w, bits=bits,
                                     chunk=chunk, levels=levels,
                                     block_chunks=bc, interpret=True)
    unpacked = unpack_ref(words, bits=bits, chunk=chunk)
    np.testing.assert_array_equal(np.asarray(unpacked), np.asarray(codes))
    dense = dequantize_ref(unpacked.astype(jnp.uint32), lo, scale,
                           chunk=chunk, levels=levels)
    want = fedavg_aggregate(dense, w, interpret=True)
    n_pad = codes.shape[1]
    assert out.shape == (n_pad,) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_packed_quantized_aggregate_rejects_bad_inputs(rng):
    words, lo, scale, _ = _packed_payload(rng, 2, 64, 16, 4)
    with pytest.raises(ValueError, match="pre-normalized"):
        packed_quantized_aggregate(words, lo, scale, jnp.asarray([1.0, 2.0]),
                                   bits=4, chunk=16, levels=15,
                                   interpret=True)
    # 16-bit codes are exact uint16 stores through the UNPACKED kernel;
    # the packed path covers every width 1..15 (odd 9..15 included)
    with pytest.raises(ValueError, match="bits in 1..15"):
        packed_quantized_aggregate(words, lo, scale, jnp.asarray([0.5, 0.5]),
                                   bits=16, chunk=16, levels=65535,
                                   interpret=True)
    wpc = words_per_chunk(16, 4)
    with pytest.raises(ValueError, match=f"C\\*{wpc}"):
        packed_quantized_aggregate(words[:, :3], lo, scale,
                                   jnp.asarray([0.5, 0.5]), bits=4, chunk=16,
                                   levels=15, interpret=True)


# ---------------------------------------------------------------------------
# sparse top-k scatter-accumulate aggregation (XLA scatter-add)
# ---------------------------------------------------------------------------

def _sparse_payload(rng, K, n, k, dtype=np.float32):
    idx = np.stack(
        [rng.choice(n, size=k, replace=False) for _ in range(K)]
    ).astype(np.int32)
    vals = rng.normal(size=(K, k)).astype(dtype)
    return jnp.asarray(idx), jnp.asarray(vals)


@pytest.mark.parametrize("K", [1, 2, 17])
@pytest.mark.parametrize("n,k", [(37, 3), (513, 25), (300, 15)])
def test_sparse_aggregate_matches_densify_oracle(rng, K, n, k):
    """Acceptance: the scatter-add aggregate of RAW example counts ==
    densify_ref -> weighted mean with normalized weights, for K in
    {1, 2, 17} and ragged n."""
    idx, vals = _sparse_payload(rng, K, n, k)
    w = jnp.asarray(rng.uniform(0.1, 5.0, K).astype(np.float32))
    out = ops.sparse_fedavg_aggregate(idx, vals, w, n)
    want = ref.fedavg_aggregate_ref(densify_ref(idx, vals, n), w / w.sum())
    assert out.shape == (n,) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_sparse_aggregate_bf16_values(rng):
    """bf16 payload values accumulate in fp32 (the accum_dtype contract)."""
    idx, vals = _sparse_payload(rng, 5, 200, 11)
    vals16 = vals.astype(jnp.bfloat16)
    w = jnp.full((5,), 0.2, jnp.float32)
    out = ops.sparse_fedavg_aggregate(idx, vals16, w, 200)
    want = ref.fedavg_aggregate_ref(densify_ref(idx, vals16, 200), w)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-2)


def test_sparse_aggregate_zero_weight_client_vanishes(rng):
    """A weight-0 (ghost) client contributes nothing — the cohort-padding
    contract the sharded lane relies on."""
    idx, vals = _sparse_payload(rng, 3, 100, 7)
    w = jnp.asarray([0.5, 0.5, 0.0])
    out = ops.sparse_fedavg_aggregate(idx, vals, w, 100)
    w2 = jnp.asarray([0.5, 0.5])
    want = ops.sparse_fedavg_aggregate(idx[:2], vals[:2], w2, 100)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


def test_sparse_aggregate_duplicate_indices_accumulate(rng):
    """Duplicate indices WITHIN a client add — the aggregate and
    densify_ref agree on additive semantics (top-k never emits duplicates;
    add == set there)."""
    idx = jnp.asarray([[2, 2, 5]], jnp.int32)
    vals = jnp.asarray([[1.0, 3.0, -2.0]], jnp.float32)
    w = jnp.ones((1,), jnp.float32)
    out = ops.sparse_fedavg_aggregate(idx, vals, w, 8)
    want = np.zeros(8, np.float32)
    want[2], want[5] = 4.0, -2.0
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(densify_ref(idx, vals, 8)[0]), atol=1e-6
    )


def test_sparse_aggregate_rejects_bad_inputs(rng):
    idx, vals = _sparse_payload(rng, 2, 64, 4)
    with pytest.raises(ValueError, match="n must be"):
        ops.sparse_fedavg_aggregate(idx, vals, jnp.asarray([1.0, 2.0]), 0)
    with pytest.raises(ValueError, match="share a"):
        ops.sparse_fedavg_aggregate(idx[:, :3], vals, jnp.asarray([0.5, 0.5]),
                                    64)
    with pytest.raises(ValueError, match="weights must be"):
        ops.sparse_fedavg_aggregate(idx, vals, jnp.asarray([1.0]), 64)


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,D,N,bd", [(1, 8, 4, 2, 4), (2, 24, 8, 4, 4), (1, 16, 16, 8, 8)])
def test_ssm_scan_sweep(rng, B, T, D, N, bd):
    dt = jnp.asarray(np.abs(rng.normal(size=(B, T, D))).astype(np.float32) * 0.1)
    Bm = jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    Cm = jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
    A = -jnp.asarray(np.abs(rng.normal(size=(D, N))).astype(np.float32))
    h0 = jnp.zeros((B, D, N))
    y, h = ssm_scan(dt, Bm, Cm, x, A, h0, block_d=bd, interpret=True)
    y2, h2 = ref.ssm_scan_ref(dt, Bm, Cm, x, A, h0)
    np.testing.assert_allclose(y, y2, atol=1e-5)
    np.testing.assert_allclose(h, h2, atol=1e-5)


def test_ssm_scan_chunked_state_carry(rng):
    """ops.mamba_ssm_scan with chunking == unchunked (state threads through)."""
    B, T, D, N = 1, 20, 4, 2
    dt = jnp.asarray(np.abs(rng.normal(size=(B, T, D))).astype(np.float32) * 0.1)
    Bm = jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    Cm = jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
    A = -jnp.asarray(np.abs(rng.normal(size=(D, N))).astype(np.float32))
    h0 = jnp.zeros((B, D, N))
    y1, h1 = ops.mamba_ssm_scan(dt, Bm, Cm, x, A, h0, chunk=8, interpret=True)
    y2, h2 = ref.ssm_scan_ref(dt, Bm, Cm, x, A, h0)
    np.testing.assert_allclose(y1, y2, atol=1e-5)
    np.testing.assert_allclose(h1, h2, atol=1e-5)


# ---------------------------------------------------------------------------
# fused cross entropy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,d,V,bt,bv", [(8, 8, 32, 4, 8), (7, 16, 50, 4, 16), (16, 8, 17, 8, 8)])
def test_fused_ce_sweep(rng, T, d, V, bt, bv):
    hid = jnp.asarray(rng.normal(size=(T, d)).astype(np.float32))
    head = jnp.asarray(rng.normal(size=(d, V)).astype(np.float32))
    lbl = jnp.asarray(rng.integers(0, V, T).astype(np.int32))
    out = fused_cross_entropy(hid, head, lbl, block_t=bt, block_v=bv, interpret=True)
    logits = hid @ head
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lbl[:, None], axis=-1)[:, 0]
    np.testing.assert_allclose(out, logz - gold, atol=1e-5)


# ---------------------------------------------------------------------------
# gossip neighbor mixing
# ---------------------------------------------------------------------------

from repro.core.topology import TOPOLOGIES
from repro.kernels.gossip_mix import gossip_mix, gossip_mix_ref


def _plan_arrays(kind, n):
    plan = TOPOLOGIES[kind]().build(n)
    return jnp.asarray(plan.idx), jnp.asarray(plan.weight)


@pytest.mark.parametrize("kind", sorted(TOPOLOGIES))
@pytest.mark.parametrize("n,N,bn,bc", [
    (5, 16, None, None),      # single block
    (8, 37, 4, 16),           # ragged N, multi-block on both axes
    (16, 130, 8, 64),         # N % block_n != 0
])
def test_gossip_mix_matches_oracle_sweep(rng, kind, n, N, bn, bc):
    x = jnp.asarray(rng.normal(size=(n, N)).astype(np.float32))
    idx, w = _plan_arrays(kind, n)
    out = gossip_mix(x, idx, w, block_nodes=bn, block_n=bc, interpret=True)
    np.testing.assert_allclose(out, gossip_mix_ref(x, idx, w), atol=1e-5)


def test_gossip_mix_hardware_block_policy_n100(rng):
    """The chip's gossip_blocks policy for a 100-node ring (all nodes in
    one row block, several ragged column blocks) equals the dense oracle."""
    from repro.kernels.gossip_mix import gossip_blocks

    n = 100
    idx, w = _plan_arrays("ring", n)
    bnodes, bc = gossip_blocks(n, idx.shape[1])
    N = 2 * bc + 5
    x = jnp.asarray(rng.normal(size=(n, N)).astype(np.float32))
    out = gossip_mix(x, idx, w, block_nodes=bnodes, block_n=bc,
                     interpret=True)
    np.testing.assert_allclose(out, gossip_mix_ref(x, idx, w), atol=1e-5)


def test_gossip_mix_bf16_values_fp32_accumulate(rng):
    n, N = 8, 48
    x = jnp.asarray(rng.normal(size=(n, N)).astype(np.float32)).astype(
        jnp.bfloat16
    )
    idx, w = _plan_arrays("smallworld", n)
    out = gossip_mix(x, idx, w, interpret=True)
    assert out.dtype == jnp.bfloat16
    want = gossip_mix_ref(x, idx, w)
    np.testing.assert_allclose(
        out.astype(np.float32), np.asarray(want, np.float32), atol=3e-2
    )


def test_gossip_mix_degree_one_pair_swap(rng):
    """The 2-node graph: MH weight 1/2 each way — one mix step averages the
    pair exactly."""
    x = jnp.asarray(rng.normal(size=(2, 12)).astype(np.float32))
    idx = jnp.asarray([[0, 1], [0, 1]], jnp.int32)
    w = jnp.full((2, 2), 0.5, jnp.float32)
    out = gossip_mix(x, idx, w, interpret=True)
    want = jnp.tile(x.mean(axis=0, keepdims=True), (2, 1))
    np.testing.assert_allclose(out, want, atol=1e-6)


def test_gossip_mix_self_loop_identity(rng):
    """Rows whose only live slot is self (weight 1) pass through unchanged —
    the padded-slot convention taken to the limit."""
    n, N = 4, 20
    x = jnp.asarray(rng.normal(size=(n, N)).astype(np.float32))
    idx = jnp.tile(jnp.arange(n, dtype=jnp.int32)[:, None], (1, 3))
    w = jnp.concatenate(
        [jnp.ones((n, 1), jnp.float32), jnp.zeros((n, 2), jnp.float32)],
        axis=1,
    )
    out = gossip_mix(x, idx, w, interpret=True)
    np.testing.assert_allclose(out, x, atol=0)


def test_gossip_mix_duplicate_neighbor_ids_accumulate(rng):
    """Duplicate slot ids are multigraph edges: their weights add, exactly
    as the dense W @ X oracle's scatter does."""
    x = jnp.asarray(rng.normal(size=(3, 8)).astype(np.float32))
    idx = jnp.asarray([[1, 1, 0], [0, 2, 1], [2, 2, 2]], jnp.int32)
    w = jnp.asarray(
        [[0.25, 0.25, 0.5], [0.3, 0.3, 0.4], [0.5, 0.5, 0.0]], jnp.float32
    )
    out = gossip_mix(x, idx, w, interpret=True)
    W = np.zeros((3, 3), np.float32)
    np.add.at(W, (np.repeat(np.arange(3), 3), np.asarray(idx).ravel()),
              np.asarray(w).ravel())
    np.testing.assert_allclose(out, W @ np.asarray(x), atol=1e-6)
    np.testing.assert_allclose(out, gossip_mix_ref(x, idx, w), atol=1e-6)


def test_gossip_mix_zero_weight_padding_inert(rng):
    """Padded slots (idx == self, weight 0) contribute nothing: widening a
    plan with extra dead slots leaves the output bit-identical."""
    n, N = 6, 24
    x = jnp.asarray(rng.normal(size=(n, N)).astype(np.float32))
    idx, w = _plan_arrays("ring", n)
    pad_idx = jnp.concatenate(
        [idx, jnp.tile(jnp.arange(n, dtype=jnp.int32)[:, None], (1, 2))],
        axis=1,
    )
    pad_w = jnp.concatenate([w, jnp.zeros((n, 2), jnp.float32)], axis=1)
    a = gossip_mix(x, idx, w, interpret=True)
    b = gossip_mix(x, pad_idx, pad_w, interpret=True)
    np.testing.assert_allclose(a, b, atol=0)


def test_gossip_mix_preserves_node_mean(rng):
    """Doubly stochastic W preserves the column mean — the conservation law
    that makes gossip an unbiased FedAvg stand-in."""
    for kind in sorted(TOPOLOGIES):
        n, N = 9, 33
        x = jnp.asarray(rng.normal(size=(n, N)).astype(np.float32))
        idx, w = _plan_arrays(kind, n)
        out = gossip_mix(x, idx, w, interpret=True)
        np.testing.assert_allclose(
            out.mean(axis=0), x.mean(axis=0), atol=1e-5
        )


@settings(max_examples=15, deadline=None)
@given(n=st.integers(3, 10), N=st.integers(4, 120),
       seed=st.integers(0, 2**31 - 1))
def test_gossip_mix_hypothesis(n, N, seed):
    r = np.random.default_rng(seed)
    kind = ["ring", "full", "random"][seed % 3]
    topo = TOPOLOGIES[kind]() if kind != "random" else TOPOLOGIES[kind](
        p=0.4, seed=seed % 97
    )
    plan = topo.build(n)
    x = jnp.asarray(r.normal(size=(n, N)).astype(np.float32))
    idx, w = jnp.asarray(plan.idx), jnp.asarray(plan.weight)
    out = gossip_mix(x, idx, w, block_nodes=4, block_n=32, interpret=True)
    np.testing.assert_allclose(out, gossip_mix_ref(x, idx, w), atol=1e-5)


def test_gossip_mix_rejects_bad_inputs(rng):
    x = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    idx, w = _plan_arrays("ring", 4)
    with pytest.raises(ValueError, match="row-stochastic"):
        gossip_mix(x, idx, w * 2.0, interpret=True)
    with pytest.raises(ValueError, match="max_slots"):
        gossip_mix(x, idx[:, :1], w, interpret=True)
    with pytest.raises(ValueError, match="max_slots"):
        gossip_mix(x, idx[:2], w[:2], interpret=True)


def test_tree_gossip_mix_matches_flat_kernel(rng):
    """ops.tree_gossip_mix == ravel -> gossip_mix -> unravel on a real
    model pytree (the engine's mixing step)."""
    from repro.models import mnist_2nn
    from repro.utils.tree import tree_ravel_stacked

    model = mnist_2nn(n_classes=3, d_in=6)
    stacked = jax.vmap(lambda s: model.init(jax.random.PRNGKey(s)))(
        jnp.arange(5)
    )
    idx, w = _plan_arrays("ring", 5)
    mixed = ops.tree_gossip_mix(stacked, idx, w, interpret=True)
    flat, _ = tree_ravel_stacked(stacked)
    want = gossip_mix_ref(flat, idx, w)
    got, _ = tree_ravel_stacked(mixed)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert jax.tree.structure(mixed) == jax.tree.structure(stacked)
